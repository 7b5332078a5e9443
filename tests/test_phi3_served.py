"""Phi-3-mini's shape served through the paged pool, against the plain
float32 reference of the benchmark (``chipbench/reference.py``).

The configuration is the Phi-3-shaped tiny cell (``chipbench/tests/
tiny_phi3.py``): MHA, no query/key norm, head_dim 96, RMSNorm eps 1e-5,
and an untied 500-row output head that the engine pads to 512 once.  A
prompt crosses a prefill chunk and its decode crosses two tail folds; at
every served position the program's log-probability of its token agrees
with the reference's log-softmax, and the token's logit lies within the
benchmark's gaps of the reference's best.  The head's pad rows are never
sampled, even when they would win.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import counts, reference, system, weights
from chipbench.tests import tiny_phi3
from repro.serving import ContinuousEngine, SamplingParams

SEED = 2 ** 31 + 15
# bf16 weights and activations in the program against float32 at
# "highest" in the reference, over two layers and two folds: the largest
# difference of a served token's log-probability read 0.088-0.156 over
# four seeds (this one 0.156), so twice the largest
LOGPROB_TOL = 0.3
# the tiny cell's correctness limits
GAP = tiny_phi3.LIMITS["checks"]["widest_logit_gap"]["at_most"]
MEAN_GAP = tiny_phi3.LIMITS["checks"]["mean_logit_gap"]["at_most"]


@pytest.fixture(scope="module")
def served():
    c = tiny_phi3.CONFIG
    cfg = system.program_config(c)
    params = system.program_params(c, cfg, SEED)
    eng = ContinuousEngine(params, cfg, slots=2, max_tokens=96,
                           prefill_chunk=c["deployment"]["prefill_chunk"],
                           paged=True, overlap=True,
                           capacity_slack=c["format"]["kv_capacity_slack"])
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, c["vocab_size"], 40).tolist()
    rid = eng.submit(prompt, SamplingParams(max_new_tokens=40))
    out = eng.run()[rid]
    return c, cfg, eng, prompt, out


def _reference_logits(c, seed, tokens, prompt_len):
    """The reference's logits at every position of ``tokens``, with the
    frozen prefix each position sees as the engine lays it out."""
    bs = c["format"]["kv_block"]
    s = -(-len(tokens) // bs) * bs
    ids = np.zeros(s, np.int32)
    ids[:len(tokens)] = tokens
    frozen = jnp.asarray([counts.frozen(i, prompt_len, c)
                          for i in range(s)])
    key = weights.root_key(seed)
    g = weights.globals_(key, c)
    with jax.default_matmul_precision("highest"):
        x = g["embed"][ids].astype(jnp.float32)
        for l in range(c["num_hidden_layers"]):
            x = reference.layer_forward(reference.layer_weights(key, c, l),
                                        x, jnp.arange(s), frozen, c, False)
        x = reference.rms(x, g["final_norm"], c["rms_norm_eps"])
        return np.asarray(x @ g["lm_head"].astype(jnp.float32))


def test_program_takes_the_models_own_numbers(served):
    c, cfg, eng, _, _ = served
    assert cfg.rms_eps == c["rms_norm_eps"] == 1e-5
    assert not cfg.qk_norm and cfg.n_kv == cfg.n_heads and cfg.hd == 96
    # the unaligned head is padded once, to whole 128-wide tiles
    assert eng.params["embed"]["lm_head"].shape == (768, 512)


def test_prefill_then_decode_across_folds_matches_reference(served):
    c, _, eng, prompt, out = served
    tokens = list(out.token_ids)
    assert len(tokens) == 40 and out.finish_reason == "length"
    # two folds: the 16-token tail ring filled twice while decoding
    assert counts.frozen(len(prompt) + 39, len(prompt), c) >= 64
    logits = _reference_logits(c, SEED, prompt + tokens, len(prompt))
    n = len(prompt)
    rows = logits[n - 1:n - 1 + len(tokens)]
    logp = jax.nn.log_softmax(jnp.asarray(rows), axis=-1)
    ref = np.asarray(logp)[np.arange(len(tokens)), tokens]
    np.testing.assert_allclose(out.logprobs, ref, atol=LOGPROB_TOL)
    gaps = rows.max(-1) - rows[np.arange(len(tokens)), tokens]
    assert gaps.max() <= GAP and gaps.mean() <= MEAN_GAP
    # the benchmark's own comparison reads the same
    res = reference.max_gap(c, SEED, [(prompt, tokens)])
    assert res["tokens"] == len(tokens)
    assert res["gap"] <= GAP and res["mean_gap"] <= MEAN_GAP


def test_head_pad_rows_are_never_sampled(served):
    """Pad columns made to win every argmax and most of the probability:
    neither greedy nor sampled requests may emit their ids."""
    c, cfg, eng, prompt, _ = served
    head = eng.params["embed"]["lm_head"]
    eng.params = {**eng.params, "embed": {
        **eng.params["embed"],
        "lm_head": head.at[:, cfg.vocab:].set(1.0)}}
    sps = [SamplingParams(max_new_tokens=24),
           SamplingParams(max_new_tokens=24, temperature=1.5, seed=3)]
    rids = [eng.submit(prompt, sp) for sp in sps]
    out = eng.run()
    for r in rids:
        toks = out[r].token_ids
        assert len(toks) == 24 and max(toks) < cfg.vocab
        assert np.all(np.isfinite(out[r].logprobs))


def test_arena_holds_whole_tiles_and_zero_padding(served):
    """head_dim 96: a block's bitmap words and packed values fill rows of
    128 lanes that are neither whole nor a multiple of 8.  The paged
    arena keeps each block as one ``[rows, 128]`` tile, the rows rounded
    up to a multiple of 8, under ``[P, n_phys, Hkv]``; what prefill and
    the folds wrote past a block's own entries is zero."""
    from repro.core.sparse_kv import arena_rows
    _, cfg, eng, _, _ = served
    pool = eng.pool
    words = pool.bs * cfg.hd // 32
    assert cfg.hd == 96 and words % 128
    for leaf in eng.state["layers"].values():
        for key, x in (("k_bitmap", words), ("k_values", pool.cap_k),
                       ("v_bitmap", words), ("v_values", pool.cap_v)):
            a = np.asarray(leaf["kv"][key])
            assert a.shape == (cfg.n_layers, pool.n_phys, cfg.n_kv,
                               arena_rows(x), 128)
            assert a.shape[3] % 8 == 0 and a.shape[3] * 128 >= x
            flat = a.reshape(*a.shape[:3], -1)
            assert flat[..., :x].any(), key      # pages were written
            assert not flat[..., x:].any(), key
