"""A tiny cell for the CPU tests: the benchmark's own files copied into a
scratch root, with one small configuration, mix and cell added as data."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
CELL = "tiny-s50.short"

CONFIG = {
    "source": "test configuration",
    "program_arch": "qwen3-0.6b",
    "hidden_size": 256, "intermediate_size": 512, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "vocab_size": 512, "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
    "tie_word_embeddings": True, "qk_norm": True,
    "format": {"dtype": "bfloat16", "weight_sparsity": 0.5,
               "weight_policy": "balanced", "weight_block": [256, 128],
               "kv_k_sparsity": 0.3, "kv_v_sparsity": 0.5, "kv_block": 16,
               "kv_tail": 16, "kv_capacity_slack": 1.25},
    "deployment": {"chips": 1, "slots": 4, "prefill_chunk": 32},
}
MIX = {"loop": "closed",
       "prompt_len": {"dist": "loguniform", "min": 20, "max": 70,
                      "levels": 3},
       "output_len": {"dist": "uniform", "min": 30, "max": 60, "levels": 3}}
# readings on the CPU at this size, seeds 5-16: the program's mean gap
# 0.00071-0.00134 and widest gap 0.042-0.101, the float8 control's
# 0.0157-0.0220 and 0.227-0.376; a decode fault's widest gap 1.3-1.6
LIMITS = {"checks": {"mean_logit_gap": {"at_most": 0.005},
                     "widest_logit_gap": {"at_most": 0.2},
                     "compared_tokens": {"at_least": 200}}}


def make_root(dst: Path, cell: str = CELL, untied: bool = False) -> Path:
    """``dst`` holding ``BENCHMARK.json`` (with ``cell`` added) and a copy of
    ``chipbench/`` (its recorded traces left out) with the tiny files."""
    dst = Path(dst)
    shutil.copytree(REPO / "chipbench", dst / "chipbench",
                    ignore=shutil.ignore_patterns("data", "__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    conf = cell.split(".")[0]
    bench["configs"].append({"name": conf, "source": "test",
                             "file": f"chipbench/configs/{conf}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": conf,
                               "traffic": "tiny-short", "chips": 1,
                               "why": "test"})
    (dst / "BENCHMARK.json").write_text(json.dumps(bench))
    c = dict(CONFIG, tie_word_embeddings=not untied)
    (dst / "chipbench/configs" / f"{conf}.json").write_text(json.dumps(c))
    (dst / "chipbench/traffic/tiny-short.json").write_text(json.dumps(MIX))
    (dst / "chipbench/limits" / f"{cell}.json").write_text(
        json.dumps(LIMITS))
    return dst
