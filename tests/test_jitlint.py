"""Analyzer coverage: fixture corpus, clean tree, jaxpr rules, lockfile.

Three layers, three test groups:

* lint — every bad fixture is flagged by EXACTLY its intended rule, the
  clean fixture and the real tree produce zero findings, pragmas work;
* jaxpr audit — hand-built jaxprs trip each rule (host transfer, arena
  gather in PROMISE_IN_BOUNDS, silent bf16->f32) and their fixed
  counterparts don't;
* manifest — lockfile round-trip (write then check passes) and pointed
  failures for each mutation class (signature / hash / transfer /
  donation).
"""
from pathlib import Path

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from repro.analysis import lint_file, lint_tree
from repro.analysis.jaxpr_audit import Geometry, audit_jaxpr
from repro.analysis.manifest import (check_manifest, fingerprint,
                                     render_manifest, write_manifest)

FIXTURES = Path(__file__).parent / "fixtures" / "jitlint"
GEO = Geometry("fixture", paged=True, spec=False)


# --------------------------------------------------------------------------
# layer 1: AST lint over the fixture corpus
# --------------------------------------------------------------------------

@pytest.mark.parametrize("name,rule,count", [
    ("bad_host_sync.py", "host-sync", 4),
    ("bad_hot_path.py", "hot-path-op", 4),
    ("bad_assert.py", "bare-assert", 2),
    ("bad_block.py", "block-until-ready", 1),
])
def test_fixture_flagged_by_exactly_intended_rule(name, rule, count):
    findings = lint_file(FIXTURES / name, jit_reachable=True, hot_path=True)
    assert {f.rule for f in findings} == {rule}, findings
    assert len(findings) == count, findings
    assert all(f.line > 0 for f in findings)


def test_clean_fixture_zero_findings():
    assert lint_file(FIXTURES / "clean.py",
                     jit_reachable=True, hot_path=True) == []


def test_lint_clean_tree():
    """The committed tree is lint-clean — the CI gate's baseline."""
    findings = lint_tree()
    assert findings == [], "\n".join(str(f) for f in findings)


def test_scope_gating():
    """Outside jit-reachable scope, host-sync/bare-assert don't fire;
    hot-path-op is gated on hot_path."""
    bad = FIXTURES / "bad_host_sync.py"
    assert lint_file(bad, jit_reachable=False, hot_path=False) == []
    hot = FIXTURES / "bad_hot_path.py"
    assert lint_file(hot, jit_reachable=True, hot_path=False) == []


# --------------------------------------------------------------------------
# layer 2: jaxpr audit rules on hand-built jaxprs
# --------------------------------------------------------------------------

def _abs(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_audit_flags_promise_in_bounds_arena_gather():
    n_phys = 29

    def bad(arena, table_row):
        return arena[table_row]            # default: PROMISE_IN_BOUNDS

    closed = jax.make_jaxpr(bad)(_abs((n_phys, 4, 8), jnp.float32),
                                 _abs((3,), jnp.int32))
    findings, _ = audit_jaxpr(closed, "bad", GEO, n_phys=n_phys)
    assert any(f.rule == "table-gather-bounds" for f in findings), findings


def test_audit_accepts_clipped_arena_gather():
    n_phys = 29

    def good(arena, table_row):
        return jnp.take(arena, table_row, axis=0, mode="clip")

    closed = jax.make_jaxpr(good)(_abs((n_phys, 4, 8), jnp.float32),
                                  _abs((3,), jnp.int32))
    findings, _ = audit_jaxpr(closed, "good", GEO, n_phys=n_phys)
    assert findings == [], findings


def test_audit_checks_the_stacked_arena_gather():
    """The paged arena is stored ``[P, n_phys, ...]``: a gather of
    ``(layer, table entry)`` out of it is arena access too, flagged in
    PROMISE_IN_BOUNDS mode and accepted clipped."""
    n_phys = 29
    arena = _abs((2, n_phys, 4, 8, 128), jnp.float32)
    ids = (_abs((), jnp.int32), _abs((3,), jnp.int32))

    def bad(arena, layer, table_row):
        return arena.at[layer, table_row].get(mode="promise_in_bounds")

    def good(arena, layer, table_row):
        return arena.at[layer, table_row].get(mode="clip")

    findings, _ = audit_jaxpr(jax.make_jaxpr(bad)(arena, *ids), "bad", GEO,
                              n_phys=n_phys)
    assert any(f.rule == "table-gather-bounds" for f in findings), findings
    findings, _ = audit_jaxpr(jax.make_jaxpr(good)(arena, *ids), "good",
                              GEO, n_phys=n_phys)
    assert findings == [], findings


def test_audit_ignores_non_arena_gather():
    """PROMISE_IN_BOUNDS over a non-arena-shaped operand is fine — the
    rule keys on the leading dim matching n_phys."""
    def f(x, ids):
        return x[ids]

    closed = jax.make_jaxpr(f)(_abs((7, 4), jnp.float32),
                               _abs((3,), jnp.int32))
    findings, _ = audit_jaxpr(closed, "f", GEO, n_phys=29)
    assert findings == []


def test_audit_flags_host_transfer():
    def bad(x):
        jax.debug.print("x={x}", x=x)      # lowers to a callback prim
        return x + 1

    closed = jax.make_jaxpr(bad)(_abs((3,), jnp.float32))
    findings, _ = audit_jaxpr(closed, "bad", GEO)
    assert any(f.rule == "transfer-prim" for f in findings), findings


def test_audit_reports_dtype_promotion():
    def widen(x):
        return x.astype(jnp.float32) * 2.0

    closed = jax.make_jaxpr(widen)(_abs((4,), jnp.bfloat16))
    findings, sites = audit_jaxpr(closed, "widen", GEO)
    assert any(f.rule == "dtype-promote" for f in findings), findings
    assert len(sites) == 1 and not sites[0]["allowed"]
    assert sites[0]["from"] == "bfloat16" and sites[0]["to"] == "float32"


# --------------------------------------------------------------------------
# layer 3: manifest lockfile round-trip + mutation classes
# --------------------------------------------------------------------------

def _tiny_manifest():
    def step(x, y):
        return jnp.dot(x, y) + 1.0

    args = (_abs((4, 8), jnp.float32), _abs((8, 2), jnp.float32))
    closed = jax.make_jaxpr(step)(*args)
    return {"_format": 1, "flat": {"step": fingerprint(closed, args)}}


def test_fingerprint_deterministic_and_structure_sensitive():
    def f(x):
        return x * 2.0 + 1.0

    def g(x):
        return x * 3.0 + 1.0

    args = (_abs((4,), jnp.float32),)
    h1 = fingerprint(jax.make_jaxpr(f)(*args), args)["hash"]
    h2 = fingerprint(jax.make_jaxpr(f)(*args), args)["hash"]
    h3 = fingerprint(jax.make_jaxpr(g)(*args), args)["hash"]
    assert h1 == h2
    assert h1 != h3


def test_lockfile_round_trip(tmp_path):
    lock = tmp_path / "jit_manifest.lock"
    man = _tiny_manifest()
    assert "missing" in check_manifest(man, path=lock)[0]
    write_manifest(man, path=lock)
    assert check_manifest(man, path=lock) == []
    # second write of the same manifest is diff-free
    assert write_manifest(man, path=lock) == ""


@pytest.mark.parametrize("mutate,needle", [
    (lambda r: r.update(signature="1 leaves: float32[9]"),
     "retrace-shaped signature change"),
    (lambda r: r.update(hash="sha256:deadbeefdeadbeef"),
     "structural hash changed"),
    (lambda r: r.update(transfers=r["transfers"] + 1),
     "NEW host transfer"),
])
def test_lockfile_catches_each_mutation_class(tmp_path, mutate, needle):
    lock = tmp_path / "jit_manifest.lock"
    write_manifest(_tiny_manifest(), path=lock)
    drifted = _tiny_manifest()
    mutate(drifted["flat"]["step"])
    problems = check_manifest(drifted, path=lock)
    assert problems, "mutation not caught"
    assert needle in "\n".join(problems)
    assert "flat/step" in "\n".join(problems)


def test_lockfile_catches_lost_donation(tmp_path):
    lock = tmp_path / "jit_manifest.lock"
    locked = _tiny_manifest()
    locked["flat"]["step"]["donated"] = [0]     # pin a donation
    write_manifest(locked, path=lock)
    current = _tiny_manifest()                  # trace donates nothing
    problems = check_manifest(current, path=lock)
    assert any("donation LOST" in p for p in problems), problems


def test_lockfile_catches_new_and_vanished_entries(tmp_path):
    lock = tmp_path / "jit_manifest.lock"
    write_manifest(_tiny_manifest(), path=lock)
    cur = _tiny_manifest()
    cur["flat"]["extra"] = dict(cur["flat"]["step"])
    msgs = "\n".join(check_manifest(cur, path=lock))
    assert "flat/extra: new jitted entry point" in msgs
    gone = _tiny_manifest()
    del gone["flat"]["step"]
    msgs = "\n".join(check_manifest(gone, path=lock))
    assert "flat/step: entry point vanished" in msgs


def test_render_is_deterministic():
    man = _tiny_manifest()
    assert render_manifest(man) == render_manifest(_tiny_manifest())
    assert "[flat]" in render_manifest(man)


def test_committed_lockfile_exists():
    """The real lockfile ships with the tree; `python -m repro.analysis
    --check` (CI) verifies the expensive part — here we only pin that it
    is present and well-formed."""
    import json

    from repro.analysis import LOCKFILE
    assert LOCKFILE.is_file(), "run `python -m repro.analysis --update`"
    data = json.loads(LOCKFILE.read_text())
    assert data["_format"] == 1
    cells = [k for k in data if not k.startswith("_")]
    assert set(cells) >= {"flat", "paged", "flat-spec", "paged-spec"}
    for cell in cells:
        for entry, rec in data[cell].items():
            assert rec["transfers"] == 0, (cell, entry)
