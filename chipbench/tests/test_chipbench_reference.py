"""The plain reference at a CPU size: its compression rules agree with the
program's bit for bit on the same inputs, the served engine passes the
comparison, and the float8 control fails it."""
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import reference, run, weights
from chipbench.tests import tiny


def test_weight_pruning_matches_the_program():
    from repro.core.pruning import make_mask
    k = jax.random.PRNGKey(3)
    # coarse values: many exact ties at every block's threshold
    w = jnp.round(jax.random.normal(k, (512, 384)) * 4) / 4
    w = w.astype(jnp.bfloat16).astype(jnp.float32)
    ours = reference.prune_weight(w, 0.5, (256, 128)) != 0
    theirs = make_mask(w, 0.5, "balanced", (256, 128)) & (w != 0)
    assert bool(jnp.all(ours == theirs))
    blocks = ours.reshape(2, 256, 3, 128).sum(axis=(1, 3))
    nz = (w != 0).reshape(2, 256, 3, 128).sum(axis=(1, 3))
    assert bool(jnp.all(blocks == jnp.minimum(nz, 256 * 128 // 2)))


@pytest.mark.parametrize("hot_head", [False, True])
def test_kv_pruning_matches_the_program(hot_head):
    from repro.core.sparse_format import unpack
    from repro.core.sparse_kv import freeze_chunk_blocks, pooled_view
    hkv, s, hd, bs = 2, 48, 64, 16
    x = jax.random.normal(jax.random.PRNGKey(4), (1, hkv, s, hd))
    if hot_head:      # head 0 keeps everything: its share hits the cap
        x = x.at[:, 0].multiply(10.0)
    x = x.astype(jnp.bfloat16)
    for sparsity in (0.3, 0.5):
        cap = reference.kv_capacity(1 - sparsity, bs, hd, 1.25)
        bm, vals, _, _ = freeze_chunk_blocks(x, x, sparsity, sparsity, bs,
                                             cap, cap)
        theirs = unpack(pooled_view(bm, vals, bs, hd))[0]     # [H, S, D]
        ours = reference.prune_kv(
            x[0].transpose(1, 0, 2).astype(jnp.float32), sparsity, bs, cap)
        np.testing.assert_array_equal(
            np.asarray(ours.transpose(1, 0, 2)),
            np.asarray(theirs.astype(jnp.float32)))


def test_layer_weights_same_bits_one_by_one_and_stacked():
    c = dict(tiny.CONFIG)
    key = weights.root_key(2 ** 33 + 5)
    stacked = jax.jit(jax.vmap(lambda l: weights.layer(key, c, l)))(
        jnp.arange(2))
    for l in range(2):
        one = weights.layer(key, c, l)
        for name in one:
            np.testing.assert_array_equal(np.asarray(one[name]),
                                          np.asarray(stacked[name][l]))
    assert not np.array_equal(np.asarray(weights.root_key(5)),
                              np.asarray(weights.root_key(5 + 2 ** 32)))


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices())
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "off")
    return tiny.make_root(tmp_path)


def test_served_tokens_pass_and_float8_control_fails(tiny_root):
    seed = 2 ** 31 + 77
    out = run.serve_cell(types.SimpleNamespace(
        workload=tiny.CELL, seed=seed, seconds=2.0, trace=0), tiny_root)
    assert out.failed == 0 and out.checked
    res = reference.max_gap(out.c, seed, out.checked, control=True)
    assert res["tokens"] >= 200
    assert run.judge(out.limits, res, out.failed)[0] is True
    correct, checks = run.judge(out.limits, reference.as_control(res),
                                out.failed)
    assert correct is False and not checks["mean_logit_gap"]["ok"]
    # the widest gap is bounded by how far the logits can be off
    assert res["gap"] < res["control_gap"]
    assert res["request_mean_gap"] < res["control_request_mean_gap"]


def test_float8_control_in_the_programs_place_is_not_correct(
        tiny_root, monkeypatch, capsys):
    """A whole run with the control's readings in place of the program's:
    the run's own check has to print ``correct: false``."""
    real = reference.max_gap

    def control(c, seed, requests):
        return reference.as_control(real(c, seed, requests, control=True))

    monkeypatch.setattr(reference, "max_gap", control)
    rc = run.main(["--workload", tiny.CELL, "--seed", str(2 ** 32 + 11),
                   "--seconds", "2", "--trace", "0"], root=tiny_root)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    gap = line["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_program_params_same_in_chunks_and_whole():
    from chipbench import system
    c = dict(tiny.CONFIG)
    cfg = system.program_config(c)
    whole = system.program_params(c, cfg, 9)
    one_layer_at_a_time = system.program_params(c, cfg, 9, chunk_bytes=1)
    assert (jax.tree_util.tree_structure(whole)
            == jax.tree_util.tree_structure(one_layer_at_a_time))
    for a, b in zip(jax.tree_util.tree_leaves(whole),
                    jax.tree_util.tree_leaves(one_layer_at_a_time)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
