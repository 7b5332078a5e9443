"""The benchmark is driven by data: every cell resolves its parts by name,
a cell, mix and metric added as files are found without editing any, the
generator is a pure function of the seed, and a run's last line has the
keys the driver reads."""
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from chipbench import counts, run, spec, traffic
from chipbench.tests import tiny

REPO = Path(__file__).resolve().parents[2]
NAME_CHARS = set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
                 "0123456789_.-")


def test_benchmark_json_resolves_every_cell():
    bench = spec.benchmark(REPO)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["chipbench"]
    assert bench["command"] == ["python3", "chipbench/run.py"]
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert c["file"].startswith("chipbench/")
        conf = spec.config(bench, c["name"], REPO)
        assert conf["source"].startswith(c["source"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for w in bench["workloads"]:
        assert set(w["name"]) <= NAME_CHARS and len(w["why"]) <= 200
        assert spec.cell(bench, w["name"]) is w
        spec.traffic(w["traffic"], REPO)
        lim = spec.limits(w["name"], REPO)
        assert set(lim["checks"]) <= set(run.RESULT_KEYS)
        per_layer = spec.metrics(bench, "per_layer", w["name"])
        assert per_layer
        for m in per_layer:
            assert callable(spec.reader(m["name"], REPO))
            assert m["moves"] in e2e


def test_added_cell_mix_and_metric_are_found(tmp_path):
    root = tiny.make_root(tmp_path)
    bench = spec.benchmark(root)
    cell = spec.cell(bench, tiny.CELL)
    assert spec.config(bench, cell["config"], root)["hidden_size"] == 256
    assert spec.traffic(cell["traffic"], root) == tiny.MIX
    assert spec.limits(tiny.CELL, root) == tiny.LIMITS
    (root / "chipbench/metrics/probe.py").write_text(
        "def read(run):\n    return 42.0\n")
    assert spec.reader("probe", root)(None) == 42.0
    with pytest.raises(KeyError):
        spec.cell(bench, "no-such.cell")


@pytest.mark.parametrize("name", ["decode-short"])
@pytest.mark.parametrize("slots", [8, 32])
def test_generator_is_a_function_of_the_seed(name, slots):
    mix = spec.traffic(name, REPO)
    seed = 2 ** 31 + 99

    def draw(s, n=3 * slots):
        st = traffic.Stream(mix, s, 1000, slots)
        return [st.next() for _ in range(n)], st.warmup(128, 128)

    (a, wa), (b, wb), (c, wc) = draw(seed), draw(seed), draw(seed + 1)
    for x, y in zip(a + wa, b + wb):
        assert x.max_new_tokens == y.max_new_tokens
        np.testing.assert_array_equal(x.prompt, y.prompt)
    # another seed: other tokens, the same lengths in the same order
    assert all(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    assert [(len(x.prompt), x.max_new_tokens) for x in a + wa] == \
        [(len(y.prompt), y.max_new_tokens) for y in c + wc]
    # each cycle of sends is a permutation of the levels
    n = len(traffic.levels(mix["prompt_len"]))
    for i in range(0, len(a) - n + 1, n):
        assert sorted(len(r.prompt) for r in a[i:i + n]) == \
            traffic.levels(mix["prompt_len"])
    # the first wave's outputs are staggered shares of whole lengths
    outs = traffic.levels(mix["output_len"])
    assert all(r.max_new_tokens <= max(outs) for r in a[:slots])
    assert all(r.max_new_tokens in outs for r in a[slots:])


def test_warmup_covers_what_the_first_wave_does_not():
    mix = spec.traffic("decode-short", REPO)
    st = traffic.Stream(mix, 3, 1000, 4)
    first = st.first_wave_levels()
    assert len(first) == 4
    warm = st.warmup(128, 128)
    lens = [len(r.prompt) for r in warm]
    levels = traffic.levels(mix["prompt_len"])
    # every level the first wave leaves out, and the one whose prompt
    # leaves the most tokens in the ring, decoding until the ring is full
    assert set(lens) | set(first) == set(levels)
    ring = max(levels, key=lambda p: p % 128)
    assert [r.max_new_tokens for r in warm if len(r.prompt) == ring] == \
        [128 - ring % 128 + 2]
    assert all(r.max_new_tokens == 2 for r in warm
               if len(r.prompt) != ring)


def test_no_chip_no_result(capsys):
    rc = run.main(["--workload", "qwen3-0.6b-s50.decode-short", "--seed",
                   "1", "--seconds", "1", "--trace", "0"], root=REPO)
    assert rc == 2
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("trace", [0, 1])
def test_last_line_keys(trace, tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices())
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "off")
    v5e = counts.peaks("TPU v5 lite")
    monkeypatch.setattr(counts, "peaks", lambda kind: v5e)
    root = tiny.make_root(tmp_path)
    (root / "chipbench/metrics/probe.py").write_text(
        "def read(run):\n    return float(run.counters['ticks'])\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "probe", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "engine",
        "moves": "tokens_per_s", "workloads": [tiny.CELL]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    rc = run.main(["--workload", tiny.CELL, "--seed", str(2 ** 31 + 5),
                   "--seconds", "2", "--trace", str(trace)], root=root)
    assert rc == 0
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    want = ["correct", "attempted", "failed", "metrics", "device"]
    want += ["breakdown"] if trace else []
    assert list(line) == want + ["checks"]
    assert line["correct"] is True
    assert line["attempted"] >= tiny.CONFIG["deployment"]["slots"]
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    names = set(line["metrics"])
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert {"compiles_in_window", "prefill_tick_share",
                "probe"} <= names
        assert line["metrics"]["probe"]["value"] > 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert names == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    # the numbers compared end standard error too
    tail = err.strip().splitlines()[-len(line["checks"]):]
    assert [t.split()[1] for t in tail] == list(line["checks"])
