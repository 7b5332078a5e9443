"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

What is read:

* device operations: the events of the ``XLA Ops`` line of every
  ``/device:TPU:<n>`` plane, each with its start, end and HLO instruction
  name (``sparse_gemv.42``: a Pallas kernel's instruction is named after
  the ``name=`` of its ``pallas_call``; ``base`` strips the number);
* device programs: the events of the ``XLA Modules`` line (one per run of
  a jitted program, ``jit_<function>(<id>)``; ``base`` keeps
  ``jit_<function>``);
* host spans: the harness's own ``jax.profiler.TraceAnnotation`` events,
  whose names start with ``chipbench.``.

All times are nanoseconds on the profiler's one clock.  Nothing here
imports the program under test.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

SPAN_WINDOW = "chipbench.window"
SPAN_STEP = "chipbench.step"
SPAN_SUBMIT = "chipbench.submit"
HOST_PREFIX = "chipbench."

# the engine's decode-step programs: the overlapped pipeline's chained
# decode, and the plain one (a serial engine, or a cold pipeline)
DECODE_STEPS = ("jit__decode_chain", "jit__decode")

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:"
# operations that only contain others (a scan's loop, a branch)
CONTAINERS = ("while", "conditional", "call")


def base(name: str) -> str:
    """``sparse_gemv.42`` -> ``sparse_gemv``; ``jit__decode(123)`` ->
    ``jit__decode``."""
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"(\.\d+|\.clone)+$", "", name)


def _op_name(name: str) -> str:
    """The instruction name of an ``XLA Ops`` event (whose name is the
    whole HLO instruction: ``%sparse_gemv.42 = bf16[...] custom-call(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float      # ns
    end: float        # ns
    device: int = -1  # device plane index; -1 for host spans

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: List[Event]
    modules: List[Event]
    host: List[Event]
    n_devices: int

    def window(self) -> Tuple[float, float]:
        """The harness's window span, or the extent of all events."""
        spans = [e for e in self.host if e.name == SPAN_WINDOW]
        if spans:
            return spans[0].start, spans[0].end
        evs = self.ops + self.modules + self.host
        if not evs:
            return 0.0, 0.0
        return min(e.start for e in evs), max(e.end for e in evs)


def load(path: str) -> Trace:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    ops: List[Event] = []
    modules: List[Event] = []
    host: List[Event] = []
    n_dev = 0
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            if "TPU" not in plane.name and "GPU" not in plane.name:
                continue
            idx = n_dev
            n_dev += 1
            for line in plane.lines:
                dst = (ops if line.name == OPS_LINE else
                       modules if line.name == MODULES_LINE else None)
                if dst is None:
                    continue
                name = _op_name if dst is ops else str
                for e in line.events:
                    dst.append(Event(name(e.name), float(e.start_ns),
                                     float(e.end_ns), idx))
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append(Event(e.name, float(e.start_ns),
                                          float(e.end_ns)))
    host.sort(key=lambda e: e.start)
    return Trace(ops, modules, host, n_dev)


def _clip(events: Sequence[Event], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    out = []
    for e in events:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge intervals into disjoint sorted ones."""
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_ns(tr: Trace, device: int, lo: float, hi: float) -> float:
    """Length of the union of ``device``'s operations within [lo, hi]."""
    return sum(b - a for a, b in union(
        _clip([e for e in tr.ops if e.device == device], lo, hi)))


def busy_s(tr: Trace) -> float:
    """Device-busy seconds in the window, averaged over the devices."""
    lo, hi = tr.window()
    if not tr.n_devices:
        return 0.0
    return sum(busy_ns(tr, d, lo, hi)
               for d in range(tr.n_devices)) / tr.n_devices / 1e9


def window_s(tr: Trace) -> float:
    lo, hi = tr.window()
    return (hi - lo) / 1e9


def in_window(events: Sequence[Event], tr: Trace) -> List[Event]:
    lo, hi = tr.window()
    return [e for e in events if e.start >= lo and e.end <= hi]


def op_seconds(tr: Trace, match: Callable[[str], bool],
               within: Optional[Sequence[Event]] = None) -> Tuple[float, int]:
    """Summed device seconds and count of the window's operations whose
    name ``match`` accepts; with ``within``, only operations that lie
    inside one of those events (e.g. one program's runs), on its device."""
    evs = [e for e in in_window(tr.ops, tr) if match(e.name)]
    if within is not None:
        spans: Dict[int, List[Tuple[float, float]]] = {}
        for w in within:
            spans.setdefault(w.device, []).append((w.start, w.end))
        for v in spans.values():
            v.sort()
        import bisect
        kept = []
        for e in evs:
            sp = spans.get(e.device, [])
            i = bisect.bisect_right(sp, (e.start, float("inf"))) - 1
            if i >= 0 and sp[i][0] <= e.start and e.end <= sp[i][1]:
                kept.append(e)
        evs = kept
    return sum(e.dur for e in evs) / 1e9, len(evs)


def modules(tr: Trace, match: Callable[[str], bool]) -> List[Event]:
    return [e for e in in_window(tr.modules, tr) if match(e.name)]


def decode_steps(tr: Trace) -> List[Event]:
    """The window's runs of a decode-step program."""
    return modules(tr, lambda n: base(n) in DECODE_STEPS)


def _enclosing(tr: Trace) -> Callable[[Event], str]:
    """A function naming the program (``XLA Modules`` event, by base name)
    whose run holds an operation on its device, or ``""``."""
    import bisect
    runs: Dict[int, List[Tuple[float, float, str]]] = {}
    for m in tr.modules:
        runs.setdefault(m.device, []).append((m.start, m.end, base(m.name)))
    for v in runs.values():
        v.sort()
    starts = {d: [r[0] for r in v] for d, v in runs.items()}

    def find(e: Event) -> str:
        v = runs.get(e.device, [])
        i = bisect.bisect_right(starts.get(e.device, []), e.start) - 1
        if i >= 0 and v[i][0] <= e.start and e.end <= v[i][1]:
            return v[i][2]
        return ""
    return find


def top_ops(tr: Trace, n: int = 10) -> List[List]:
    """The ``n`` operations with the most device seconds in the window,
    each named ``<program>/<operation>`` by base names (the program run
    that holds it; the operation alone where none does), containers left
    out, summed over devices and divided by their count."""
    lo, hi = tr.window()
    prog = _enclosing(tr)
    tot: Dict[str, float] = {}
    for e in tr.ops:
        a, b, name = max(e.start, lo), min(e.end, hi), base(e.name)
        if b > a and name not in CONTAINERS:
            m = prog(e)
            key = f"{m}/{name}" if m else name
            tot[key] = tot.get(key, 0.0) + (b - a)
    div = max(tr.n_devices, 1) * 1e9
    return [[k, v / div] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def top_programs(tr: Trace, n: int = 10) -> List[List]:
    """The ``n`` programs (``XLA Modules`` base names) with the most device
    seconds in the window: ``[name, runs, seconds]``, seconds summed over
    devices and divided by their count."""
    lo, hi = tr.window()
    tot: Dict[str, List[float]] = {}
    for e in tr.modules:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a:
            t = tot.setdefault(base(e.name), [0, 0.0])
            t[0] += 1
            t[1] += b - a
    div = max(tr.n_devices, 1)
    return [[k, v[0] // div, v[1] / div / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1][1])[:n]]


def idle_gaps(tr: Trace, n: int = 10) -> List[List]:
    """The ``n`` longest device-idle gaps of device 0 in the window, each
    named after the harness span that covers most of it (``host`` when
    none does), with its length in seconds."""
    lo, hi = tr.window()
    busy = union(_clip([e for e in tr.ops if e.device == 0], lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    spans = [e for e in tr.host if e.name != SPAN_WINDOW]
    out = []
    for a, b in gaps[:n]:
        best, cover = "host", 0.0
        for s in spans:
            c = min(b, s.end) - max(a, s.start)
            if c > cover:
                best, cover = s.name, c
        out.append([best, (b - a) / 1e9])
    return out


def summary(tr: Trace) -> Dict:
    """The numbers a test pins for a recorded trace."""
    return {"n_devices": tr.n_devices,
            "window_s": window_s(tr),
            "busy_s": busy_s(tr),
            "n_ops": len(in_window(tr.ops, tr)),
            "n_modules": len(in_window(tr.modules, tr)),
            "n_host_spans": len(tr.host),
            "top_ops": top_ops(tr),
            "top_programs": top_programs(tr),
            "idle_gaps": idle_gaps(tr),
            "module_names": sorted({e.name for e in tr.modules})}
