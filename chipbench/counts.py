"""Operations and bytes the benchmark counts for a piece of work.

Counted from the configuration's declared format, never from the program's
arrays, so the same operation counts the same whatever implements it:

* a sparse linear ``[K, N]`` at weight density ``w``: ``w*K*N`` bf16
  values plus one bitmap bit per weight, the bf16 input ``[m, K]`` and
  output ``[m, N]``; ``2*m*w*K*N`` operations;
* decode attention of one query against ``n`` cached positions, of which
  ``f`` sit in the compressed prefix: per KV head, K at its density and V
  at its own as bf16 values plus a bitmap bit per element for the ``f``
  frozen positions, the ``n - f`` tail positions as dense bf16 K and V, and
  the query and output rows of the group's heads; ``4*hd*n`` operations per
  query head.

Peaks come from ``peaks.json``, keyed by the device kind JAX reports.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable, Sequence

from . import weights

PEAKS = Path(__file__).resolve().parent / "peaks.json"
BF16 = 2


def peaks(device_kind: str) -> Dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_s(flops: float, nbytes: float, peak: Dict) -> float:
    """Roofline time: the larger of compute and HBM time."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


def bound(flops: float, nbytes: float, peak: Dict) -> str:
    return ("compute" if flops / peak["bf16_flops_per_s"]
            >= nbytes / peak["hbm_bytes_per_s"] else "hbm")


def sparse_linear(k: int, n: int, m: int, density: float):
    """(operations, bytes) of ``x[m, K] @ W[K, N]`` with W in the bitmap
    format at ``density``."""
    nnz = density * k * n
    nbytes = nnz * BF16 + k * n / 8 + m * k * BF16 + m * n * BF16
    return 2.0 * m * nnz, nbytes


def linear_shapes(c: Dict):
    return [shape for shape, kind in weights.layer_shapes(c).values()
            if kind == "linear"]


def decode_linears(c: Dict, m: int):
    """(operations, bytes) of each sparse linear call of one decode step
    of ``m`` rows, layer by layer."""
    density = 1.0 - c["format"]["weight_sparsity"]
    calls = [sparse_linear(k, n, m, density) for k, n in linear_shapes(c)]
    return calls * c["num_hidden_layers"]


def linear_step_least_s(c: Dict, m: int, peak: Dict) -> float:
    """Least seconds of one decode step's sparse linears, summed per call."""
    return sum(least_s(f, b, peak) for f, b in decode_linears(c, m))


def frozen(i: int, prompt: int, c: Dict) -> int:
    """Positions held in the compressed prefix when position ``i`` is the
    newest one computed, for a request whose prompt has ``prompt``
    tokens: prompt chunks freeze the chunks before them, decoding folds the
    tail ring in whole once it is full."""
    f, dep = c["format"], c["deployment"]
    chunk, bs, tail = dep["prefill_chunk"], f["kv_block"], f["kv_tail"]
    if i < prompt:
        return chunk * (i // chunk)
    f0 = bs * (prompt // bs)
    return f0 + tail * ((i - f0) // tail)


def decode_attention(c: Dict, contexts: Iterable[Sequence[int]]):
    """(operations, bytes) of one fused decode-attention call per layer,
    summed over layers, for one tick whose live slots hold ``(n, f)`` =
    (cached positions, of which frozen)."""
    m = weights.dims(c)
    f = c["format"]
    hkv, h, hd = m["hkv"], m["h"], m["hd"]
    dk, dv = 1.0 - f["kv_k_sparsity"], 1.0 - f["kv_v_sparsity"]
    flops = nbytes = 0.0
    for n, fr in contexts:
        tail = n - fr
        per_head = (fr * hd * ((dk + dv) * BF16 + 2 / 8)
                    + tail * hd * 2 * BF16)
        nbytes += hkv * per_head + 2 * h * hd * BF16
        flops += 4.0 * h * hd * n
    layers = m["layers"]
    return flops * layers, nbytes * layers


def decode_token_flops(c: Dict, n: int) -> float:
    """Model operations of one decoded token with ``n`` cached positions:
    2 per non-zero linear weight, 2 per unembedding weight, and the
    attention's ``4*hd*n`` per query head per layer."""
    m = weights.dims(c)
    density = 1.0 - c["format"]["weight_sparsity"]
    lin = sum(k * n_ for k, n_ in linear_shapes(c)) * m["layers"]
    attn = 4.0 * m["h"] * m["hd"] * n * m["layers"]
    return 2.0 * density * lin + 2.0 * m["vocab"] * m["d"] + attn
