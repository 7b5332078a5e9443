"""Layer 2: trace-time audit of every registered engine entry point.

For a small geometry matrix (flat/paged pool x speculation on/off) this
module builds a :class:`~repro.serving.engine.ContinuousEngine` over
**abstract** parameters (``jax.eval_shape`` of the init — no weights ever
materialize), pulls its registered jitted transitions from
:meth:`~repro.serving.engine.ContinuousEngine.entry_points` (the same
registry :meth:`trace_counts` reports on), traces each under its
``ShapeDtypeStruct`` example args, and walks the closed jaxprs:

``transfer-prim``
    No host-callback or transfer primitive anywhere in a transition
    (``pure_callback``, ``io_callback``, ``debug_callback``,
    ``device_put``, infeed/outfeed).  A transition that phones home per
    tick is a silent serving-throughput bug.

``dynamic-shape``
    Every intermediate aval must have a fully static integer shape — a
    dynamically-shaped op would force per-length retraces, which is
    exactly what the pool design exists to prevent.

``dtype-promote``
    Report of every ``convert_element_type`` that silently widens
    ``bfloat16 -> float32``.  Deliberate f32 accumulation (the kernels'
    ``preferred_element_type`` discipline, rms-norm/rope/softmax math) is
    allowlisted per file below; anything else must carry a
    ``# jitlint: disable=dtype-promote`` pragma at the flagged source
    line or it is a finding.  Every site — allowed or not — lands in the
    JSON report for the CI artifact.

``table-gather-bounds``
    Any gather/scatter whose operand leads with the paged arena axis
    (``n_phys`` rows — the audit geometry picks a prime arena size so the
    dimension is unambiguous), or carries it second under the stacked
    arena's period axis, must stay in ``CLIP`` or ``FILL_OR_DROP``
    mode.  ``PROMISE_IN_BOUNDS`` on a block-table access would turn a
    corrupt table entry into out-of-bounds memory traffic instead of the
    pool's documented clip/sentinel-drop discipline.
"""
from __future__ import annotations

import dataclasses
import functools
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.extend.core
import jax.numpy as jnp
from jax._src import source_info_util

from .lint import _PRAGMA_RE

AUDIT_RULES: Dict[str, str] = {
    "transfer-prim": "host callback/transfer primitive inside a jitted "
                     "transition",
    "dynamic-shape": "non-static shape in a jitted transition",
    "dtype-promote": "silent bf16->f32 upcast without pragma/allowlist",
    "table-gather-bounds": "arena gather/scatter not in clip/drop mode",
}

# primitives that move data to/from the host or another device placement
TRANSFER_PRIMS = {
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "callback",
    "device_put", "infeed", "outfeed",
    "host_local_array_to_global_array", "global_array_to_host_local_array",
    "check",
}

# files where bf16 -> f32 widening is the documented accumulation idiom:
# every kernel accumulates at f32 (``preferred_element_type`` discipline),
# and the normalization / rotary / softmax / router math in the model
# stack runs at f32 by design.  serving/sampling.py is deliberately NOT
# here — its upcast sites carry in-source pragmas (the bf16 tp>1 greedy
# drift caveat in BENCH_mesh.json is why they must stay visible).
DTYPE_ALLOW_FILES: Sequence[str] = (
    "kernels/",
    "core/",
    "models/layers.py",
    "models/flash.py",
    "models/attention.py",
    "models/moe.py",
    "models/ssm.py",
    "models/lm.py",
)


@dataclasses.dataclass(frozen=True)
class Geometry:
    """One cell of the audit matrix."""
    name: str
    paged: bool
    spec: bool


DEFAULT_GEOMETRIES: Tuple[Geometry, ...] = (
    Geometry("flat", paged=False, spec=False),
    Geometry("paged", paged=True, spec=False),
    Geometry("flat-spec", paged=False, spec=True),
    Geometry("paged-spec", paged=True, spec=True),
)

# distinctive prime arena size: no other dimension in the reduced config
# collides with it, so "operand leads with n_phys" (or, for the stacked
# [P, n_phys, ...] arena, carries it second) identifies arena ops
AUDIT_PHYS_BLOCKS = 29


@dataclasses.dataclass(frozen=True)
class AuditFinding:
    rule: str
    entry: str         # entry-point name (trace_counts key)
    geometry: str      # Geometry.name
    message: str
    file: str = ""     # repo-relative source file, when resolvable
    line: int = 0

    def __str__(self) -> str:
        where = f" ({self.file}:{self.line})" if self.file else ""
        return (f"{self.geometry}/{self.entry}: [{self.rule}] "
                f"{self.message}{where}")


def _audit_cfg():
    """The tiny serving config every geometry traces under: reduced
    qwen3 stack at default bf16 compute (so dtype widening is visible),
    sparse KV, one-block tail."""
    import dataclasses as dc

    from repro.configs import get_config
    cfg = get_config("qwen3-0.6b").reduced()
    return dc.replace(cfg, kv_k_sparsity=0.3, kv_v_sparsity=0.5,
                      kv_tail=16)


def build_audit_engine(geometry: Geometry, cfg=None):
    """An engine over abstract params for one geometry cell.

    ``jax.eval_shape`` of the initializer means no parameter memory is
    ever allocated; the pool state is real but tiny (reduced config).
    Built as an accelerator builds it, donating the state through every
    program, and with ``overlap=True``: the overlapped engine's entry-point
    set is a strict superset of the serial one (every serial transition plus
    the chained ``decode_chain`` dispatch), so every geometry cell audits
    the pipelined path too — the in-flight tick cannot smuggle a host
    transfer past the matrix."""
    from repro.models import lm
    from repro.serving.engine import ContinuousEngine
    from repro.serving.spec import SpecConfig
    cfg = cfg if cfg is not None else _audit_cfg()
    params = jax.eval_shape(
        functools.partial(lm.init_params, cfg, jax.random.PRNGKey(0)))
    return ContinuousEngine(
        params, cfg, slots=4, max_tokens=64, bs=8, prefill_chunk=16,
        paged=geometry.paged,
        phys_blocks=AUDIT_PHYS_BLOCKS if geometry.paged else 0,
        spec=SpecConfig(k=2) if geometry.spec else None,
        checkify=False, overlap=True, donate=True)


def collect_entries(geometry: Geometry, cfg=None
                    ) -> Dict[str, Tuple[Any, tuple]]:
    """``{entry name: (jitted, abstract args)}`` for one geometry cell —
    a thin veneer over :meth:`ContinuousEngine.entry_points` so the audit
    and the manifest share one discovery path."""
    return build_audit_engine(geometry, cfg=cfg).entry_points()


# --------------------------------------------------------------------------
# jaxpr traversal
# --------------------------------------------------------------------------

def _walk_eqns(jaxpr, visit) -> None:
    """Depth-first over every eqn of ``jaxpr`` including nested (pjit /
    scan / while / cond) sub-jaxprs."""
    for eqn in jaxpr.eqns:
        visit(eqn)
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                _walk_eqns(sub, visit)


def _sub_jaxprs(v) -> List[Any]:
    core = jax.extend.core
    if isinstance(v, core.ClosedJaxpr):
        return [v.jaxpr]
    if isinstance(v, core.Jaxpr):
        return [v]
    if isinstance(v, (tuple, list)):
        out: List[Any] = []
        for x in v:
            out.extend(_sub_jaxprs(x))
        return out
    return []


def _frame(eqn) -> Tuple[str, int]:
    """(repo-relative file, line) of the user code that emitted ``eqn``,
    or ("", 0) when no user frame survives."""
    fr = source_info_util.user_frame(eqn.source_info.traceback)
    if fr is None:
        return "", 0
    rel = _relativize(fr.file_name)
    return rel, int(fr.start_line or 0)


def _package_root() -> Path:
    return Path(__file__).resolve().parent.parent


def _relativize(file_name: str) -> str:
    try:
        return str(Path(file_name).resolve()
                   .relative_to(_package_root().resolve()))
    except ValueError:
        return file_name


@functools.lru_cache(maxsize=None)
def _pragma_lines(rel: str) -> frozenset:
    """Lines of ``rel`` (repo-relative) carrying a dtype-promote pragma."""
    path = _package_root() / rel
    if not path.is_file():
        return frozenset()
    out = set()
    for i, text in enumerate(path.read_text().splitlines(), start=1):
        m = _PRAGMA_RE.search(text)
        if m and {"dtype-promote", "all"} & {
                r.strip() for r in m.group(1).split(",")}:
            out.add(i)
    return frozenset(out)


def _dtype_allowed(rel: str, line: int) -> Optional[str]:
    """Why a bf16->f32 site is acceptable, or None if it is a finding."""
    if any(rel.startswith(p) for p in DTYPE_ALLOW_FILES):
        return "file-allowlist"
    pragmas = _pragma_lines(rel)
    if line in pragmas or (line - 1) in pragmas or (line + 1) in pragmas:
        return "pragma"
    return None


def audit_jaxpr(closed, entry: str, geometry: Geometry,
                n_phys: int = 0) -> Tuple[List[AuditFinding],
                                          List[Dict[str, Any]]]:
    """Walk one traced entry point.  Returns ``(findings, dtype_sites)``
    where ``dtype_sites`` records every bf16->f32 widening (allowed or
    flagged) for the promotion report."""
    findings: List[AuditFinding] = []
    dtype_sites: List[Dict[str, Any]] = []
    seen_dtype = set()
    promise = jax.lax.GatherScatterMode.PROMISE_IN_BOUNDS

    def visit(eqn):
        name = eqn.primitive.name
        if name in TRANSFER_PRIMS:
            rel, line = _frame(eqn)
            findings.append(AuditFinding(
                "transfer-prim", entry, geometry.name,
                f"primitive `{name}` crosses the host/device boundary "
                "inside a jitted transition", rel, line))
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(var, "aval", None)
            shape = getattr(aval, "shape", ())
            if not all(isinstance(d, int) for d in shape):
                rel, line = _frame(eqn)
                findings.append(AuditFinding(
                    "dynamic-shape", entry, geometry.name,
                    f"`{name}` carries a non-static shape {shape}",
                    rel, line))
                break
        if name == "convert_element_type":
            src = eqn.invars[0].aval
            dst = eqn.params.get("new_dtype")
            if (getattr(src, "dtype", None) == jnp.bfloat16
                    and dst == jnp.float32):
                rel, line = _frame(eqn)
                key = (rel, line)
                if key in seen_dtype:
                    return
                seen_dtype.add(key)
                reason = _dtype_allowed(rel, line)
                dtype_sites.append({
                    "geometry": geometry.name, "entry": entry,
                    "file": rel, "line": line,
                    "from": "bfloat16", "to": "float32",
                    "allowed": reason is not None, "reason": reason})
                if reason is None:
                    findings.append(AuditFinding(
                        "dtype-promote", entry, geometry.name,
                        "silent bf16->f32 upcast (allowlist the file or "
                        "add `# jitlint: disable=dtype-promote`)",
                        rel, line))
        if name in ("gather", "scatter", "scatter-add", "scatter_add",
                    "dynamic_update_slice", "dynamic_slice") and n_phys:
            operand = eqn.invars[0].aval
            shape = getattr(operand, "shape", ())
            mode = eqn.params.get("mode")
            if (n_phys in tuple(shape)[:2] and mode is not None
                    and mode == promise):
                rel, line = _frame(eqn)
                findings.append(AuditFinding(
                    "table-gather-bounds", entry, geometry.name,
                    f"`{name}` over the [{n_phys}, ...] arena uses "
                    "PROMISE_IN_BOUNDS; block-table access must stay in "
                    "CLIP or FILL_OR_DROP mode", rel, line))

    _walk_eqns(closed.jaxpr, visit)
    return findings, dtype_sites


def run_audit(geometries: Sequence[Geometry] = DEFAULT_GEOMETRIES,
              cfg=None) -> Tuple[List[AuditFinding], List[Dict[str, Any]]]:
    """Trace + audit every entry point of every geometry cell.

    Returns ``(findings, dtype_report)``; an empty findings list is the
    CI bar.  The dtype report lists every bf16->f32 site with its
    allow/deny verdict — uploaded as a CI artifact so widening changes
    are reviewable even when they are allowed.
    """
    findings: List[AuditFinding] = []
    report: List[Dict[str, Any]] = []
    for g in geometries:
        eng = build_audit_engine(g, cfg=cfg)
        n_phys = eng.pool.n_phys
        for name, (fn, args) in sorted(eng.entry_points().items()):
            closed = jax.make_jaxpr(fn)(*args)
            fs, sites = audit_jaxpr(closed, name, g, n_phys=n_phys)
            findings.extend(fs)
            report.extend(sites)
    return findings, report
