"""``trace_reduce`` on a small trace recorded on a TPU v5e chip by
``record_trace.py``: a two-layer cut of qwen3-0.6b at full width served on
4 slots, 8 ticks traced (9 decode-step runs, 2 prefill chunks)."""
from pathlib import Path

import pytest

from chipbench import trace_reduce as tr

TRACE = Path(__file__).resolve().parent / "data" / "small.xplane.pb"


@pytest.fixture(scope="module")
def trace():
    return tr.load(str(TRACE))


def test_planes_lines_and_spans(trace):
    assert trace.n_devices == 1
    names = [e.name for e in trace.host]
    assert names.count(tr.SPAN_WINDOW) == 1
    assert names.count(tr.SPAN_STEP) == 8
    assert names.count(tr.SPAN_SUBMIT) == 1
    assert tr.window_s(trace) == pytest.approx(0.18116231)
    assert {tr.base(e.name) for e in tr.in_window(trace.modules, trace)} \
        == {"jit__decode_chain", "jit__prefill", "jit__lambda",
            "jit__threefry_seed", "jit_convert_element_type"}


def test_kernel_calls_match_the_model(trace):
    steps = tr.modules(trace, lambda n: tr.base(n) == "jit__decode_chain")
    assert len(steps) == 9
    assert tr.decode_steps(trace) == steps
    # 7 sparse linears per layer, 2 layers, one gemv each at 4 slots
    secs, calls = tr.op_seconds(
        trace, lambda n: tr.base(n) == "sparse_gemv", within=steps)
    assert calls == 9 * 7 * 2
    assert secs == pytest.approx(0.008557036)
    # one fused paged decode attention per layer per decode step
    _, attn = tr.op_seconds(
        trace, lambda n: tr.base(n) == "sparse_decode_attention_fused_paged",
        within=steps)
    assert attn == 9 * 2
    # the two prefill chunks run their linears as sparse_matmul
    pre = tr.modules(trace, lambda n: tr.base(n) == "jit__prefill")
    _, mm = tr.op_seconds(trace, lambda n: tr.base(n) == "sparse_matmul",
                          within=pre)
    assert (len(pre), mm) == (2, 2 * 7 * 2)


def test_busy_idle_and_breakdown(trace):
    busy = tr.busy_s(trace)
    assert busy == pytest.approx(0.101330112)
    assert 0 < busy < tr.window_s(trace)
    # the union never exceeds the sum of the operations it merges
    lo, hi = trace.window()
    raw = sum(min(e.end, hi) - max(e.start, lo) for e in trace.ops
              if e.end > lo and e.start < hi) / 1e9
    assert busy <= raw
    top = tr.top_ops(trace)
    assert len(top) == 10
    # named by the program run that holds them
    assert [k for k, _ in top[:4]] == [
        "jit__prefill/fusion", "jit__decode_chain/dense_matmul",
        "jit__decode_chain/copy", "jit__decode_chain/sparse_gemv"]
    assert top[3][1] == pytest.approx(0.008557036)
    assert all(k.split("/")[-1] not in tr.CONTAINERS for k, _ in top)
    progs = tr.top_programs(trace)
    assert [p[:2] for p in progs[:2]] == [["jit__prefill", 3],
                                          ["jit__decode_chain", 9]]
    # a program's runs hold its operations: never less time than them
    assert progs[1][2] >= sum(v for k, v in top
                              if k.startswith("jit__decode_chain/"))
    gaps = tr.idle_gaps(trace)
    assert len(gaps) == 10
    assert gaps[0] == ["chipbench.step", pytest.approx(0.010506674)]
    assert all(a[1] >= b[1] for a, b in zip(gaps, gaps[1:]))
    assert sum(g for _, g in gaps) <= tr.window_s(trace) - busy + 1e-9


def test_union_and_base_names():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 7)]) == [(0, 3), (5, 7)]
    assert tr.base("sparse_gemv.42") == "sparse_gemv"
    assert tr.base("broadcast.782.clone") == "broadcast"
    assert tr.base("jit__decode_chain(13971644348384832865)") \
        == "jit__decode_chain"
