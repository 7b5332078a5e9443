"""Layer 3: the compile manifest — a committed lockfile of what compiles.

For every geometry cell and engine entry point the manifest records

* the **abstract signature** (dtype + shape of every argument leaf) — a
  change here is retrace-shaped: callers built against the old signature
  now trigger a fresh trace per call site;
* a **structural hash** of the traced jaxpr (primitive sequence, avals,
  stable params, nested sub-jaxprs) — the compile fingerprint;
* the **donation set** of the entry's pjit — lost donation silently
  doubles peak pool memory;
* the **transfer count** — host callbacks/transfers inside the step
  (must be zero; the jaxpr audit hard-fails them, the manifest pins the
  count so a rule gap still shows up as drift).

``python -m repro.analysis --update`` regenerates
``src/repro/analysis/jit_manifest.lock`` and prints a human-readable
diff; ``--check`` (the CI gate) fails with a pointed message when the
current tree drifts from the committed lockfile.
"""
from __future__ import annotations

import difflib
import hashlib
import json
from pathlib import Path
from typing import Any, Dict, List, Sequence

import jax

from .jaxpr_audit import (DEFAULT_GEOMETRIES, TRANSFER_PRIMS, Geometry,
                          _sub_jaxprs, build_audit_engine)

LOCKFILE = Path(__file__).resolve().parent / "jit_manifest.lock"

_FORMAT = 1

# param reprs containing any of these are id/address-dependent and would
# make the hash unstable across processes; they are dropped (nested
# jaxprs are hashed by recursion instead)
_UNSTABLE_REPR = ("0x", "<function", "<lambda", "object at", "<jax")


def _signature(args) -> str:
    """Deterministic one-line abstract signature of an args tuple."""
    leaves, treedef = jax.tree_util.tree_flatten(args)
    shapes = ",".join(f"{l.dtype}{list(l.shape)}" for l in leaves)
    return f"{treedef.num_leaves} leaves: {shapes}"


def _hash_lines(jaxpr, out: List[str]) -> None:
    for eqn in jaxpr.eqns:
        # literals carry their value (x*2 vs x*3 must hash apart);
        # variables carry only their aval
        ins = ",".join(f"lit:{v.val!r}" if hasattr(v, "val")
                       else str(v.aval) for v in eqn.invars)
        outs = ",".join(str(v.aval) for v in eqn.outvars)
        params = []
        for k in sorted(eqn.params):
            v = eqn.params[k]
            if _sub_jaxprs(v):
                continue                      # hashed by recursion below
            r = repr(v)
            if any(tok in r for tok in _UNSTABLE_REPR):
                continue
            params.append(f"{k}={r}")
        out.append(f"{eqn.primitive.name}({ins})->({outs})"
                   f"{{{';'.join(params)}}}")
        for k in sorted(eqn.params):
            subs = _sub_jaxprs(eqn.params[k])
            for i, sub in enumerate(subs):
                out.append(f"<{eqn.primitive.name}.{k}[{i}]>")
                _hash_lines(sub, out)
                out.append("</>")


def _structural_hash(closed) -> str:
    lines: List[str] = []
    _hash_lines(closed.jaxpr, lines)
    digest = hashlib.sha256("\n".join(lines).encode()).hexdigest()
    return f"sha256:{digest[:16]}"


def _donated(closed) -> List[int]:
    """Donated argument indices of the entry's top-level jit (the
    primitive is ``jit`` since JAX 0.7, ``pjit`` before)."""
    for eqn in closed.jaxpr.eqns:
        if eqn.primitive.name in ("jit", "pjit"):
            don = eqn.params.get("donated_invars", ())
            return [i for i, d in enumerate(don) if d]
    return []


def _transfers(closed) -> int:
    count = 0

    def walk(jaxpr):
        nonlocal count
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in TRANSFER_PRIMS:
                count += 1
            for v in eqn.params.values():
                for sub in _sub_jaxprs(v):
                    walk(sub)
    walk(closed.jaxpr)
    return count


def fingerprint(closed, args) -> Dict[str, Any]:
    """One lockfile record for a traced entry point."""
    return {
        "signature": _signature(args),
        "hash": _structural_hash(closed),
        "donated": _donated(closed),
        "transfers": _transfers(closed),
    }


def build_manifest(geometries: Sequence[Geometry] = DEFAULT_GEOMETRIES,
                   cfg=None) -> Dict[str, Any]:
    """Trace every geometry cell's entry points and fingerprint them."""
    manifest: Dict[str, Any] = {"_format": _FORMAT}
    for g in geometries:
        eng = build_audit_engine(g, cfg=cfg)
        cell: Dict[str, Any] = {}
        for name, (fn, args) in sorted(eng.entry_points().items()):
            closed = jax.make_jaxpr(fn)(*args)
            cell[name] = fingerprint(closed, args)
        manifest[g.name] = cell
    return manifest


def render_manifest(manifest: Dict[str, Any]) -> str:
    """Human-readable rendering (the --update diff is over this form)."""
    lines = [f"# jit compile manifest (format {manifest.get('_format')})"]
    for geo in sorted(k for k in manifest if not k.startswith("_")):
        lines.append(f"[{geo}]")
        for entry, rec in sorted(manifest[geo].items()):
            lines.append(f"  {entry}:")
            lines.append(f"    signature: {rec['signature']}")
            lines.append(f"    hash:      {rec['hash']}")
            lines.append(f"    donated:   {rec['donated']}")
            lines.append(f"    transfers: {rec['transfers']}")
    return "\n".join(lines) + "\n"


def write_manifest(manifest: Dict[str, Any], path: Path = LOCKFILE) -> str:
    """Write the lockfile; returns a unified diff vs the previous content
    (empty when nothing changed or no lockfile existed)."""
    path = Path(path)
    diff = ""
    if path.is_file():
        old = json.loads(path.read_text())
        diff = "\n".join(difflib.unified_diff(
            render_manifest(old).splitlines(),
            render_manifest(manifest).splitlines(),
            fromfile="jit_manifest.lock (committed)",
            tofile="jit_manifest.lock (current tree)", lineterm=""))
    path.write_text(json.dumps(manifest, indent=2, sort_keys=True) + "\n")
    return diff


def check_manifest(manifest: Dict[str, Any],
                   path: Path = LOCKFILE) -> List[str]:
    """Compare the current tree's manifest against the committed lockfile.

    Returns pointed drift messages (empty = pass).  Wording names the
    class of regression each field guards so a CI failure reads as a
    diagnosis, not a checksum mismatch.
    """
    path = Path(path)
    if not path.is_file():
        return [f"lockfile {path} missing — run "
                "`python -m repro.analysis --update` and commit it"]
    locked = json.loads(path.read_text())
    problems: List[str] = []
    if locked.get("_format") != manifest.get("_format"):
        problems.append("lockfile format version drift — regenerate with "
                        "--update")
    geos = [k for k in manifest if not k.startswith("_")]
    for geo in geos:
        if geo not in locked:
            problems.append(f"{geo}: geometry cell missing from lockfile "
                            "(new geometry? run --update)")
            continue
        for entry, rec in manifest[geo].items():
            old = locked[geo].get(entry)
            if old is None:
                problems.append(
                    f"{geo}/{entry}: new jitted entry point not in "
                    "lockfile — audit it, then run --update")
                continue
            if old["signature"] != rec["signature"]:
                problems.append(
                    f"{geo}/{entry}: retrace-shaped signature change\n"
                    f"    locked:  {old['signature']}\n"
                    f"    current: {rec['signature']}")
            elif old["hash"] != rec["hash"]:
                problems.append(
                    f"{geo}/{entry}: jaxpr structural hash changed "
                    f"({old['hash']} -> {rec['hash']}) — the compiled "
                    "step is not the one the lockfile pinned; review the "
                    "diff, then run --update")
            if rec["transfers"] > old["transfers"]:
                problems.append(
                    f"{geo}/{entry}: NEW host transfer inside the jitted "
                    f"step ({old['transfers']} -> {rec['transfers']})")
            lost = set(old["donated"]) - set(rec["donated"])
            if lost:
                problems.append(
                    f"{geo}/{entry}: donation LOST for args "
                    f"{sorted(lost)} — peak pool memory doubles for "
                    "those buffers")
        for entry in locked[geo]:
            if entry not in manifest[geo]:
                problems.append(
                    f"{geo}/{entry}: entry point vanished from the "
                    "engine registry (lockfile stale? run --update)")
    for geo in locked:
        if not geo.startswith("_") and geo not in geos:
            problems.append(f"{geo}: geometry cell in lockfile but not "
                            "produced by this tree (run --update)")
    return problems
