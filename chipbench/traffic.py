"""The one traffic generator: reads a mix from ``chipbench/traffic/<name>.json``.

A mix file holds parameters only:

* ``loop``: ``"closed"`` -- one client per engine slot, each sending its
  next request when its last one finishes.
* ``prompt_len``, ``output_len``: ``{"dist": "loguniform"|"uniform",
  "min", "max", "levels"}``.  The lengths are the ``levels`` quantiles
  ``(i + 0.5) / levels`` of the distribution.  They are sent in cycles,
  each cycle a permutation of the levels drawn from ``order_seed`` (a
  number of the mix, not the run's seed).  So every seed sends the same
  lengths in the same order and the engine does the same work: the same
  prefill shapes, ticks, refreezes and completions.  The run's seed draws
  the tokens (and the weights).

The first wave is staggered: the first request of client ``i`` of ``n``
gets only a share ``(j + 0.5) / n`` of its output length (``j`` a
permutation of the clients from ``order_seed``), so completions are spread
from the start as in a loop that has run for a while.

Token ids are uniform over the vocabulary.  Requests are a pure function
of (mix, seed, vocabulary size): the program receives only the tokens.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List

import numpy as np

WARM_SALT = 0x5EED


def levels(spec: Dict) -> List[int]:
    """The mix's length levels, ascending."""
    dist, n = spec["dist"], int(spec["levels"])
    lo, hi = float(spec["min"]), float(spec["max"])
    qs = [(i + 0.5) / n for i in range(n)]
    if dist == "loguniform":
        vals = [lo * (hi / lo) ** q for q in qs]
    elif dist == "uniform":
        vals = [lo + (hi - lo) * q for q in qs]
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return [int(round(v)) for v in vals]


@dataclasses.dataclass
class Request:
    prompt: np.ndarray          # int32 token ids
    max_new_tokens: int


class Stream:
    """Request source of one run: lengths from the mix, tokens from the
    seed."""

    def __init__(self, mix: Dict, seed: int, vocab: int, slots: int):
        if mix.get("loop") != "closed":
            raise ValueError(f"unsupported loop {mix.get('loop')!r}")
        self.mix = mix
        self.vocab = vocab
        self.slots = slots
        self.clients = slots
        self.prompt_levels = levels(mix["prompt_len"])
        self.output_levels = levels(mix["output_len"])
        self._order = np.random.default_rng(int(mix.get("order_seed", 0)))
        self._first = self._order.permutation(self.clients)
        self._p_order: List[int] = []
        self._o_order: List[int] = []
        self._rng = np.random.default_rng(seed)
        self._warm_rng = np.random.default_rng([seed, WARM_SALT])
        self.sent = 0

    def _draw(self, order: List[int], n: int) -> int:
        if not order:
            order.extend(self._order.permutation(n).tolist())
        return order.pop()

    def _prompt(self, rng, n_tok: int) -> np.ndarray:
        return rng.integers(0, self.vocab, n_tok, dtype=np.int32)

    def next(self) -> Request:
        """The next request in send order (client ``sent % clients`` for
        the first wave)."""
        p = self.prompt_levels[self._draw(self._p_order,
                                          len(self.prompt_levels))]
        o = self.output_levels[self._draw(self._o_order,
                                          len(self.output_levels))]
        if self.sent < self.clients:
            share = (self._first[self.sent] + 0.5) / self.clients
            o = max(1, int(math.ceil(o * share)))
        self.sent += 1
        return Request(self._prompt(self._rng, p), o)

    def max_context(self) -> int:
        return max(self.prompt_levels) + max(self.output_levels)

    def first_wave_levels(self) -> List[int]:
        """Prompt levels of the first ``clients`` requests."""
        probe = Stream(self.mix, 0, 2, self.slots)
        return sorted({len(probe.next().prompt)
                       for _ in range(self.clients)})

    def warmup(self, tail: int, bs: int) -> List[Request]:
        """Requests served before the first wave, from a stream of their
        own, so that nothing the window runs compiles in it: one per prompt
        level the first wave does not send (its prefill shape), and one
        that decodes until its ``tail``-token ring is full (the refreeze),
        taken from the level whose prompt leaves the most tokens in the
        ring (``len % bs``), so the fewest decode steps get there."""
        longest = max(self.prompt_levels, key=lambda p: p % bs)
        seen = set(self.first_wave_levels())
        out = []
        for p in self.prompt_levels:
            if p == longest:
                steps = tail - p % bs + 2
            elif p not in seen:
                steps = 2
            else:
                continue
            out.append(Request(self._prompt(self._warm_rng, p), steps))
        return out
