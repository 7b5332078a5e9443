"""A Phi-3-shaped tiny cell for the CPU tests: the tiny root of ``tiny.py``
with its configuration replaced by one of Phi-3-mini's shape -- MHA, no
query/key norm, head_dim 96 (which does not divide the 128 lanes), RMSNorm
eps 1e-5 and an untied output head whose vocabulary (500) is not a whole
number of 128-wide tiles."""
from __future__ import annotations

import json
from pathlib import Path

from chipbench.tests import tiny

CELL = "tiny-phi3-s50.short"

CONFIG = {
    "source": "test configuration",
    "program_arch": "phi3-mini-3.8b",
    "hidden_size": 768, "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 8, "num_key_value_heads": 8,
    "vocab_size": 500, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
    "tie_word_embeddings": False,
    "format": tiny.CONFIG["format"],
    "deployment": tiny.CONFIG["deployment"],
}
# readings on the CPU at this size in 8-s windows, seeds 5, 11-16 and
# 2**31 + 77: the program's mean gap 0.0019-0.0044 and widest gap
# 0.10-0.22, the float8 control's 0.050-0.062 and 0.52-1.16
LIMITS = {"checks": {"mean_logit_gap": {"at_most": 0.02},
                     "widest_logit_gap": {"at_most": 0.35},
                     "compared_tokens": {"at_least": 200}}}


def make_root(dst: Path) -> Path:
    """``tiny.make_root`` with this cell's configuration and limits."""
    dst = tiny.make_root(dst, cell=CELL)
    conf = CELL.split(".")[0]
    (dst / "chipbench/configs" / f"{conf}.json").write_text(
        json.dumps(CONFIG))
    (dst / "chipbench/limits" / f"{CELL}.json").write_text(
        json.dumps(LIMITS))
    return dst
