"""A whole run at a CPU size with the timed path broken underneath: each
fault the cell can have must come out ``correct: false``.  (The cells run
on one chip, so there is no exchange between chips to leave out.)"""
import json

import jax
import jax.numpy as jnp
import pytest

from chipbench import run
from chipbench.tests import tiny


def _stale_state(f, eng):
    """The decode step returns the state it was given."""
    def step(*args):
        tok, logp, _ = f(*args)
        return tok, logp, args[1]
    return step


def _half_batch(f, eng):
    """The decode step leaves the second half of the slots out."""
    def step(*args):
        m = args[-1]
        keep = jnp.arange(m.shape[0]) < m.shape[0] // 2
        return f(*args[:-1], jnp.logical_and(m, keep))
    return step


def _token_altered(f, eng):
    """Every decoded token is replaced by the next id."""
    vocab = eng.cfg.vocab

    def step(*args):
        tok, logp, st = f(*args)
        return (tok + 1) % vocab, logp, st
    return step


def _rare_token_altered(f, eng):
    """Slot 0's token is replaced by the next id at every 40th decode step:
    a few tokens of one slot, which a mean over every compared token
    dilutes."""
    vocab = eng.cfg.vocab
    calls = [0]

    def step(*args):
        tok, logp, st = f(*args)
        calls[0] += 1
        if calls[0] % 40 == 0:
            tok = tok.at[0].set((tok[0] + 1) % vocab)
        return tok, logp, st
    return step


@pytest.mark.parametrize("fault, caught_by", [
    (_stale_state, "mean_logit_gap"), (_half_batch, "mean_logit_gap"),
    (_token_altered, "mean_logit_gap"),
    (_rare_token_altered, "widest_logit_gap")],
    ids=["stale_state", "half_batch", "token_altered", "rare_token_altered"])
def test_broken_decode_is_not_correct(fault, caught_by, tmp_path,
                                      monkeypatch, capsys):
    from repro.serving import ContinuousEngine
    init = ContinuousEngine.__init__

    def broken_init(self, *a, **k):
        init(self, *a, **k)
        for name in ("_decode", "_decode_chain"):
            f = getattr(self, name)
            if f is not None:
                setattr(self, name, fault(f, self))

    monkeypatch.setattr(ContinuousEngine, "__init__", broken_init)
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices())
    monkeypatch.setattr(run, "enable_compile_cache", lambda: "off")
    root = tiny.make_root(tmp_path)
    rc = run.main(["--workload", tiny.CELL, "--seed", "4242",
                   "--seconds", "2", "--trace", "0"], root=root)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    gap = line["checks"][caught_by]
    assert gap["value"] > gap["limit"]
