"""The engine's program names, program spans and fold counters.

* **Program names** — every jitted step is a named function, so XLA names
  its program ``jit_<function>`` (``jit__refreeze``, never ``jit__lambda``
  or ``jit_on_mesh``) in the plain, checkify and mesh branches of the
  engine's jit.  A profiler trace tells the programs apart by that name.
* **Program spans** — under ``jax.profiler`` a paged, overlapped engine
  records the ``repro.*`` spans of :mod:`repro.obs.spans` on the
  profiler's clock, each phase nested in its ``repro.tick``.
* **Fold counters** — ``repro_refreezes_total`` and
  ``repro_refreeze_slots_total{tail=full|compressed}`` count what the
  device folds: the full slots the device's own ``tail_len`` shows at each
  refreeze dispatch, and every slot row the program compresses.
"""
import dataclasses
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.obs import Observability, spans
from repro.serving import ContinuousEngine, SamplingParams, SpecConfig

WORKER = os.path.join(os.path.dirname(__file__), "workers",
                      "program_names_worker.py")
# entry point -> the XLA program it compiles to
MODULES = {"decode": "jit__decode", "decode_chain": "jit__decode_chain",
           "prefill_chunk": "jit__prefill", "refreeze": "jit__refreeze",
           "assign": "jit__assign", "set_lane": "jit__set_lane",
           "release": "jit_release", "verify": "jit__verify"}
KV_TAIL = 16


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen3-0.6b").reduced()
    cfg = dataclasses.replace(cfg, kv_k_sparsity=0.3, kv_v_sparsity=0.5,
                              kv_tail=KV_TAIL)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _prompts(cfg, lens=(9, 23, 5), seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, (n,)).tolist() for n in lens]


def _serve(eng, prompts, new=(20, 12, 26)):
    for p, n in zip(prompts, new):
        eng.submit(p, SamplingParams(max_new_tokens=n))
    return eng.run()


def module_names(eng):
    """``{entry point: XLA module name}`` of every jitted step."""
    out = {}
    for name, (fn, args) in eng.entry_points().items():
        text = fn.lower(*args).as_text()
        out[name] = re.match(r"module @(\S+)", text).group(1)
    return out


def _host_events(path):
    """``[(name, start_ns, end_ns, line id)]`` of the host planes."""
    pd = jax.profiler.ProfileData.from_file(str(path))
    out = []
    for i, plane in enumerate(pd.planes):
        if plane.name.startswith("/device:"):
            continue
        for j, line in enumerate(plane.lines):
            for e in line.events:
                out.append((e.name, e.start_ns, e.end_ns, (i, j)))
    return out


def _profiled(eng, prompts, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        _serve(eng, prompts)
    path, = Path(tmp_path).rglob("*.xplane.pb")
    return _host_events(path)


def _parent(ev, events):
    """The innermost ``repro.*`` span on ``ev``'s line that holds it."""
    name, a, b, line = ev
    holders = [e for e in events if e is not ev and e[3] == line
               and e[0].startswith(spans.PREFIX)
               and e[1] <= a and b <= e[2] and (e[1], e[2]) != (a, b)]
    if not holders:
        return None
    return min(holders, key=lambda e: e[2] - e[1])[0]


@pytest.mark.parametrize("paged", [True, False])
def test_every_step_compiles_under_its_own_name(setup, paged):
    cfg, params = setup
    eng = ContinuousEngine(params, cfg, slots=2, max_tokens=96,
                           prefill_chunk=16, paged=paged, overlap=True,
                           spec=SpecConfig(k=2))
    names = module_names(eng)
    assert names == {k: MODULES[k] for k in names}
    assert "refreeze" in names and ("assign" in names) == paged


def test_profiled_ticks_nest_program_spans(setup, tmp_path):
    cfg, params = setup
    eng = ContinuousEngine(params, cfg, slots=2, max_tokens=96,
                           prefill_chunk=16, paged=True, overlap=True)
    prompts = _prompts(cfg)
    _serve(eng, prompts)                 # compile outside the trace
    events = _profiled(eng, prompts, tmp_path)
    ours = [e for e in events if e[0].startswith(spans.PREFIX)]
    assert {e[0] for e in ours} == set(spans.ALL)
    ticks = [e for e in ours if e[0] == spans.TICK]
    assert ticks and all(_parent(e, events) is None for e in ticks)
    for e in ours:
        parent = _parent(e, events)
        if e[0] in (spans.ADMIT, spans.REFREEZE, spans.PREFILL,
                    spans.DECODE):
            assert parent == spans.TICK, e
        elif e[0] == spans.SYNC:
            # the decode tick's commit, the drain of a fold, or quiesce
            assert parent in (spans.TICK, spans.REFREEZE, None), e
        elif e[0] == spans.RELEASE:
            assert parent in (spans.TICK, None), e
    # the programs the ticks dispatch go by their names, never a lambda's
    jits = {e[0] for e in events if e[0].startswith("PjitFunction(")
            and any(t[3] == e[3] and t[1] <= e[1] and e[2] <= t[2]
                    for t in ticks)}
    assert {"PjitFunction(_refreeze)", "PjitFunction(_prefill)",
            "PjitFunction(_decode_chain)",
            "PjitFunction(_set_lane)"} <= jits
    assert not any("lambda" in j for j in jits), jits
    # each fold's dispatch lies inside its repro.refreeze span
    folds = [e for e in events if e[0] == "PjitFunction(_refreeze)"]
    assert folds and all(_parent(e, events) == spans.REFREEZE
                         for e in folds)


def test_checkify_steps_keep_their_names(setup, tmp_path):
    cfg, params = setup
    eng = ContinuousEngine(params, cfg, slots=2, max_tokens=96,
                           prefill_chunk=16, paged=True, overlap=True,
                           checkify=True)
    assert eng.pool.checkify
    events = _profiled(eng, _prompts(cfg), tmp_path)
    jits = {e[0] for e in events if e[0].startswith("PjitFunction(")}
    assert {"PjitFunction(_refreeze)", "PjitFunction(_prefill)",
            "PjitFunction(_decode_chain)", "PjitFunction(_set_lane)",
            "PjitFunction(release)"} <= jits
    assert not any("lambda" in j for j in jits), jits
    assert "PjitFunction(run)" not in jits


def test_mesh_steps_keep_their_names():
    out = subprocess.run([sys.executable, WORKER], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["devices"] == 4
    for label, names in rec["engines"].items():
        assert names == {k: MODULES[k] for k in names}, label
        assert "refreeze" in names, label


@pytest.mark.parametrize("paged,overlap,spec", [
    (True, True, False), (False, False, False), (True, True, True)])
def test_fold_counters_match_the_devices_full_slots(setup, paged, overlap,
                                                    spec):
    cfg, params = setup
    obs = Observability()
    eng = ContinuousEngine(params, cfg, slots=3, max_tokens=96,
                           prefill_chunk=16, paged=paged, overlap=overlap,
                           spec=SpecConfig(k=2) if spec else None, obs=obs)
    folds = []
    dispatch = eng._refreeze

    def counted(state, *ids):
        # the device's own view of the full rows, read at the dispatch
        tail_len = np.asarray(state["tail_len"])
        folds.append(int((tail_len >= eng.pool.tail).sum()))
        return dispatch(state, *ids)
    eng._refreeze = counted
    _serve(eng, _prompts(cfg, lens=(9, 23, 5, 14), seed=1),
           new=(20, 12, 40, 30))
    s = obs.snapshot()
    assert len(folds) >= 2 and min(folds) >= 1
    assert min(folds) < eng.pool.slots      # some folds find slots not full
    assert s["repro_refreezes_total"] == len(folds)
    assert s['repro_refreeze_slots_total{tail="full"}'] == sum(folds)
    assert s['repro_refreeze_slots_total{tail="compressed"}'] == sum(folds)


def test_obs_labels_dispatch_spans_as_host_time(tmp_path):
    obs = Observability(trace_path=str(tmp_path / "t.json"))
    obs.decode_tick(1.0, 0.5, 2, spec=False, overlapped=True)
    obs.prefill_chunk(0, 1, 2.0, 0.25, 16, final=True)
    obs.refreeze(1, 4)
    obs.close()
    evs = json.loads((tmp_path / "t.json").read_text())
    thread = [e["args"]["name"] for e in evs
              if e["name"] == "thread_name" and e["tid"] == 1]
    assert thread == ["host dispatch"]
    steps = [e for e in evs if e["name"] in ("decode", "prefill_chunk")]
    assert [e["cat"] for e in steps] == ["dispatch", "dispatch"]
    s = obs.snapshot()
    assert s["repro_decode_tick_seconds{mode=\"plain\"}"]["count"] == 1
    assert s["repro_prefill_chunk_seconds"]["count"] == 1
    assert s["repro_refreezes_total"] == 1.0
    assert s['repro_refreeze_slots_total{tail="full"}'] == 1.0
    assert s['repro_refreeze_slots_total{tail="compressed"}'] == 4.0
    help_ = {f.name: f.help for f in obs.registry.families()}
    assert "not device time" in help_["repro_decode_tick_seconds"]
    assert "not device time" in help_["repro_prefill_chunk_seconds"]
