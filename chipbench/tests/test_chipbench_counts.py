"""The benchmark's operation and byte counts, by hand at qwen3-0.6b widths,
and its table of peaks."""
import json
from pathlib import Path

import pytest

from chipbench import counts

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def qwen():
    return json.loads((CONFIGS / "qwen3-0.6b-s50.json").read_text())


def test_peaks_known_and_unknown_kind():
    p = counts.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16 * 2 ** 30
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("TPU v9 imaginary")


def test_sparse_linear_counts_by_hand():
    # wq of qwen3-0.6b: [1024, 2048] at 50%, 32 decode rows
    flops, nbytes = counts.sparse_linear(1024, 2048, 32, 0.5)
    nnz = 1024 * 2048 // 2                       # 1,048,576 kept weights
    assert flops == 2 * 32 * nnz                 # 67,108,864
    assert nbytes == nnz * 2 + 1024 * 2048 // 8 + 32 * 1024 * 2 + 32 * 2048 * 2
    assert nbytes == 2_555_904


def test_decode_step_linears_at_qwen_widths():
    c = qwen()
    calls = counts.decode_linears(c, 32)
    assert len(calls) == 7 * 28
    # per layer: wq 1024x2048, wk/wv 1024x1024, wo 2048x1024,
    # gate/up 1024x3072, down 3072x1024 = 15,728,640 weights
    weights_per_layer = 2 * 1024 * 2048 + 2 * 1024 * 1024 + 3 * 1024 * 3072
    assert weights_per_layer == 15_728_640
    assert sum(f for f, _ in calls) == 28 * 2 * 32 * weights_per_layer // 2
    p = counts.peaks("TPU v5 lite")
    # every call is HBM-bound at 32 rows: 32 ops per weight against
    # 1.125 bytes per weight is far below the 240 ops/byte ridge
    assert all(counts.bound(f, b, p) == "hbm" for f, b in calls)
    least = counts.linear_step_least_s(c, 32, p)
    weight_bytes = 28 * weights_per_layer * (0.5 * 2 + 1 / 8)
    k_plus_n = ((1024 + 2048) + 2 * (1024 + 1024) + (2048 + 1024)
                + 3 * (1024 + 3072))
    assert k_plus_n == 22528
    act_bytes = 28 * 32 * 2 * k_plus_n
    assert least == pytest.approx((weight_bytes + act_bytes) / 819e9)
    assert 6.5e-4 < least < 6.6e-4


def test_decode_attention_by_hand():
    c = qwen()
    # one request with 300 cached positions, 256 of them frozen
    flops, nbytes = counts.decode_attention(c, [(300, 256)])
    hd, hkv, h, layers = 128, 8, 16, 28
    per_head = (256 * hd * ((0.7 + 0.5) * 2 + 2 / 8)
                + 44 * hd * 2 * 2)
    assert nbytes == pytest.approx(layers * (hkv * per_head + 2 * h * hd * 2))
    assert flops == layers * 4 * h * hd * 300


def test_decode_token_flops_by_hand():
    c = qwen()
    lin = 28 * 15_728_640
    want = 2 * 0.5 * lin + 2 * 151936 * 1024 + 4 * 16 * 128 * 28 * 1000
    assert counts.decode_token_flops(c, 1000) == want


@pytest.mark.parametrize("i,prompt,want", [
    (0, 200, 0), (127, 200, 0), (128, 200, 128), (199, 200, 128),
    # decoding: 200 -> 128 frozen and 72 in the ring, folded at 256
    (200, 200, 128), (255, 200, 128), (256, 200, 256), (383, 200, 256),
    (384, 200, 384),
    # a prompt of whole blocks leaves the ring empty
    (256, 256, 256), (383, 256, 256), (384, 256, 384)])
def test_frozen_positions(i, prompt, want):
    assert counts.frozen(i, prompt, qwen()) == want
