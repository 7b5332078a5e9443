"""``refreeze_ms``: the mean device time of the whole ``jit__refreeze`` runs
in the traced window, read from synthetic traces, from the recorded chip
trace (whose program did not name its refreeze) and through a traced run
of the tiny cell."""
import json
import types
from pathlib import Path

import jax
import pytest

from chipbench import counts, run, spec
from chipbench import trace_reduce as tr
from chipbench.tests import tiny

REPO = Path(__file__).resolve().parents[2]
TRACE = Path(__file__).resolve().parent / "data" / "small.xplane.pb"
S = 1e9     # ns


def _trace(modules):
    window = tr.Event(tr.SPAN_WINDOW, 0.0, 4 * S)
    return tr.Trace(ops=[], modules=modules, host=[window], n_devices=1)


def _read(trace):
    read = spec.reader("refreeze_ms", REPO)
    return read(types.SimpleNamespace(trace=trace))


def test_mean_of_the_whole_folds_in_the_window():
    mods = [tr.Event("jit__refreeze(11)", 0.5 * S, 1.6 * S, 0),
            tr.Event("jit__decode_chain(7)", 1.6 * S, 1.65 * S, 0),
            tr.Event("jit__lambda(3)", 1.7 * S, 1.9 * S, 0),
            tr.Event("jit__refreeze(11)", 2.0 * S, 3.2 * S, 0),
            # runs past the window's close: not a whole fold in it
            tr.Event("jit__refreeze(11)", 3.5 * S, 4.6 * S, 0)]
    assert _read(_trace(mods)) == pytest.approx(1150.0)


@pytest.mark.parametrize("modules", [
    [],
    [tr.Event("jit__lambda(3)", 0.5 * S, 1.6 * S, 0)],
    [tr.Event("jit__refreeze(11)", 3.5 * S, 4.6 * S, 0)]],
    ids=["empty", "unnamed-refreeze", "cut-by-the-close"])
def test_no_whole_fold_reads_none(modules):
    assert _read(_trace(modules)) is None


def test_untraced_run_and_recorded_trace_read_none():
    assert _read(None) is None
    # recorded before the program named its refreeze: jit__lambda only
    recorded = tr.load(str(TRACE))
    assert "jit__lambda" in {tr.base(e.name) for e in recorded.modules}
    assert _read(recorded) is None


def test_traced_tiny_run_leaves_it_out_without_a_device_trace(
        tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices())
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "off")
    v5e = counts.peaks("TPU v5 lite")
    monkeypatch.setattr(counts, "peaks", lambda kind: v5e)
    root = tiny.make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry, = [m for m in bench["per_layer"] if m["name"] == "refreeze_ms"]
    entry["workloads"].append(tiny.CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    rc = run.main(["--workload", tiny.CELL, "--seed", str(2 ** 31 + 7),
                   "--seconds", "2", "--trace", "1"], root=root)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    # the CPU's trace has no device plane, so no XLA Modules to read
    assert "refreeze_ms" not in line["metrics"]
    assert "prefill_tick_share" in line["metrics"]
