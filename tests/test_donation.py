"""The engine donates its state: every program updates the pool in place.

``ContinuousEngine(donate=True)`` (the default off the CPU backend) hands
the pool state to each jitted program as a donated argument, so the
program's output reuses the input's buffers and the pool is held once.
Donation may change no token: the engine that keeps its inputs
(``donate=False``) is the oracle, across flat/paged pools, overlapped and
serial ticks, speculation, checkify, a quiesced snapshot and a warm
restart, and the fault-requeue path.  A donated input is deleted after the
tick that consumed it, and the committed compile manifest pins non-empty
donation sets for every program.
"""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.manifest import LOCKFILE
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.serving import ContinuousEngine, SamplingParams, SpecConfig
from repro.serving.faults import PAGE_EXHAUSTION, Fault, FaultPlan


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen3-0.6b").reduced()
    cfg = dataclasses.replace(cfg, kv_k_sparsity=0.3, kv_v_sparsity=0.5,
                              kv_tail=16, compute_dtype="float32",
                              param_dtype="float32")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


def _engine(params, cfg, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_tokens", 96)
    kw.setdefault("bs", 16)
    kw.setdefault("prefill_chunk", 32)
    return ContinuousEngine(params, cfg, **kw)


def _prompts(cfg, seed=0, lens=(9, 17, 5, 23, 12)):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, (n,)).tolist() for n in lens]


def _serve(eng, prompts, sp=None):
    """A staggered wave (two requests, three ticks, then the rest), so
    admissions, folds and releases land while a tick is in flight."""
    sp = sp or SamplingParams(max_new_tokens=20)
    rids = [eng.submit(p, sp) for p in prompts[:2]]
    for _ in range(3):
        eng.step()
    rids += [eng.submit(p, sp) for p in prompts[2:]]
    out = eng.run()
    return [(list(out[r].token_ids), list(out[r].logprobs)) for r in rids]


def test_cpu_default_keeps_inputs_and_donate_flag_overrides(setup):
    cfg, params = setup
    assert jax.default_backend() == "cpu"
    assert _engine(params, cfg).donate is False
    assert _engine(params, cfg, donate=True).donate is True


@pytest.mark.parametrize("paged", [False, True], ids=["flat", "paged"])
def test_donated_state_is_deleted_after_a_tick(setup, paged):
    cfg, params = setup
    sp = SamplingParams(max_new_tokens=8)
    for donate in (True, False):
        eng = _engine(params, cfg, paged=paged, overlap=True, donate=donate)
        eng.submit(_prompts(cfg)[1], sp)
        for _ in range(3):              # prefill, then decode ticks
            before = jax.tree_util.tree_leaves(eng.state)
            eng.step()
            assert all(a.is_deleted() == donate for a in before)
        # the params are never donated
        assert not any(a.is_deleted()
                       for a in jax.tree_util.tree_leaves(eng.params))
        eng.run()


@pytest.mark.parametrize("spec", [False, True], ids=["nospec", "spec"])
@pytest.mark.parametrize("overlap", [False, True], ids=["serial", "overlap"])
@pytest.mark.parametrize("paged", [False, True], ids=["flat", "paged"])
def test_donation_changes_no_token(setup, paged, overlap, spec):
    cfg, params = setup
    kw = dict(paged=paged, overlap=overlap,
              spec=SpecConfig(k=2) if spec else None)
    prompts = _prompts(cfg)
    want = _serve(_engine(params, cfg, donate=False, **kw), prompts)
    assert _serve(_engine(params, cfg, donate=True, **kw), prompts) == want


@pytest.mark.parametrize("entry", ["decode", "decode_chain"])
def test_decode_step_hands_the_arena_back_in_place(setup, entry):
    """Paged and donated: a decode step returns every arena leaf in the
    buffer it was given, with the bytes unchanged -- the step reads the
    stacked arena where it lies and never writes it."""
    from repro.serving import ARENA_KEYS
    cfg, params = setup
    eng = _engine(params, cfg, paged=True, overlap=True, donate=True)
    eng.submit(_prompts(cfg, lens=(40,))[0], SamplingParams(max_new_tokens=4))
    eng.run()                           # frozen pages in the arena
    arena = lambda st: [st["layers"][name]["kv"][k]  # noqa: E731
                        for name in st["layers"] for k in ARENA_KEYS]
    ptrs = [a.unsafe_buffer_pointer() for a in arena(eng.state)]
    # copies: a host view of a buffer would keep it from being donated
    want = [np.array(a, copy=True) for a in arena(eng.state)]
    b = eng.pool.slots
    mask = jnp.ones((b,), bool)
    if entry == "decode":
        _, _, st = eng._decode(eng.params, eng.state,
                               jnp.ones((b, 1), jnp.int32), mask)
    else:
        zeros = jnp.zeros((b,), jnp.int32)
        _, _, st = eng._decode_chain(eng.params, eng.state, zeros, zeros,
                                     jnp.zeros((b,), bool), mask)
    assert [a.unsafe_buffer_pointer() for a in arena(st)] == ptrs
    for got, w in zip(arena(st), want):
        np.testing.assert_array_equal(np.asarray(got), w)


def test_donation_under_checkify(setup):
    cfg, params = setup
    prompts = _prompts(cfg, seed=1)
    want = _serve(_engine(params, cfg, paged=True, overlap=True,
                          donate=False), prompts)
    eng = _engine(params, cfg, paged=True, overlap=True, donate=True,
                  checkify=True)
    assert eng.pool.checkify
    assert _serve(eng, prompts) == want


def test_donation_on_a_mesh(setup):
    """The mesh variant of the programs donates too (a one-device mesh:
    the same shardings and shard_map kernels as a larger one)."""
    cfg, params = setup
    mesh = make_mesh((1, 1), ("data", "model"))
    prompts = _prompts(cfg, seed=2)
    want = _serve(_engine(params, cfg, paged=True, overlap=True,
                          donate=False), prompts)
    eng = _engine(params, cfg, paged=True, overlap=True, donate=True,
                  mesh=mesh)
    before = jax.tree_util.tree_leaves(eng.state)
    assert _serve(eng, prompts) == want
    assert all(a.is_deleted() for a in before)


def test_quiesce_snapshot_and_restart_under_donation(setup, tmp_path):
    """A save mid-traffic quiesces the in-flight tick and reads the live
    state, never a donated one; a warm restart replays the follow-up
    wave as the engine that keeps its inputs does."""
    cfg, params = setup
    rng = np.random.default_rng(3)
    shared = rng.integers(0, cfg.vocab, (48,)).tolist()
    wave = [shared + rng.integers(0, cfg.vocab, (4,)).tolist()
            for _ in range(2)]
    followup = [shared + rng.integers(0, cfg.vocab, (6,)).tolist()
                for _ in range(2)]
    sp = SamplingParams(max_new_tokens=6)

    def run(donate, snap):
        eng = _engine(params, cfg, paged=True, overlap=True, donate=donate)
        rids = [eng.submit(p, sp) for p in wave]
        for _ in range(4):
            eng.step()
        assert eng._inflight is not None
        eng.save_snapshot(snap)
        assert eng._inflight is None
        assert not eng.quiesce()          # nothing left in flight
        out = eng.run()
        first = [list(out[r].token_ids) for r in rids]
        eng.save_snapshot(snap)
        fresh = _engine(params, cfg, paged=True, overlap=True,
                        donate=donate)
        assert fresh.load_snapshot(snap) == len(eng._trie)
        rids = [fresh.submit(p, sp) for p in followup]
        res = fresh.run()
        return first, [list(res[r].token_ids) for r in rids]

    assert run(True, str(tmp_path / "a")) == run(False, str(tmp_path / "b"))


def test_fault_requeue_under_donation(setup):
    """Injected page exhaustion defers an admission with backoff; the
    requeued request's tokens are those of the engine that keeps its
    inputs."""
    cfg, params = setup
    prompts = _prompts(cfg, seed=4)

    def run(donate):
        plan = FaultPlan([Fault(PAGE_EXHAUSTION, 2),
                          Fault(PAGE_EXHAUSTION, 5)])
        eng = _engine(params, cfg, paged=True, overlap=True, donate=donate,
                      faults=plan)
        got = _serve(eng, prompts)
        assert eng.fault_counters["injected_page_exhaustion"] == 2
        return got

    assert run(True) == run(False)


def test_lockfile_pins_donation_for_every_program():
    lock = json.loads(LOCKFILE.read_text())
    geos = [k for k in lock if not k.startswith("_")]
    assert geos
    for geo in geos:
        for entry, rec in lock[geo].items():
            assert rec["donated"], f"{geo}/{entry} donates nothing"


def test_program_memory_compiles_nothing_and_reaches_obs(setup):
    """Read after a warm-up: a compile-cache hit per program that ran,
    with the donated state aliased to the output."""
    from repro.obs import Observability
    cfg, params = setup
    obs = Observability()
    eng = _engine(params, cfg, paged=True, overlap=True, donate=True,
                  obs=obs)
    assert obs.snapshot()["repro_pool_bytes"] == eng.pool.nbytes() > 0
    # a 40-token prompt runs the 32-token chunk the program is read at
    eng.submit(_prompts(cfg, lens=(40,))[0],
               SamplingParams(max_new_tokens=20))
    eng.run()
    compiled = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, duration, **_: compiled.append(event))
    got = eng.program_memory()
    assert not [e for e in compiled if "backend_compile" in e]
    ran = {k for k, v in eng.trace_counts().items() if v}
    assert set(got) == ran
    snap = obs.snapshot()
    for name in ran:
        for kind in ("argument", "output", "alias", "temp"):
            key = f'repro_program_bytes{{kind="{kind}",program="{name}"}}'
            assert snap[key] == got[name][kind]
        # the whole state comes back in the arguments' own buffers
        assert got[name]["alias"] >= eng.pool.nbytes()
