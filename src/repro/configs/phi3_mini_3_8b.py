"""Phi-3-mini-3.8B (arXiv:2404.14219): RoPE, SwiGLU, MHA (32 query and 32 KV
heads of 96), RMSNorm eps 1e-5, untied 32064-row output head.

Values from ``microsoft/Phi-3-mini-4k-instruct`` ``config.json``
(https://huggingface.co/microsoft/Phi-3-mini-4k-instruct): hidden 3072,
intermediate 8192, 32 layers, ``rope_theta`` 10000 with no scaling, no
biases, ``hidden_act`` silu.  The checkpoint fuses ``qkv_proj`` and
``gate_up_proj``; here they are the separate ``wq``/``wk``/``wv`` and
``w_gate``/``w_up`` of the same mathematics.  Its ``sliding_window`` (2047)
is not modelled: served contexts must stay below it.
"""
from repro.configs import ArchConfig

CONFIG = ArchConfig(
    name="phi3-mini-3.8b", family="dense",
    n_layers=32, d_model=3072, n_heads=32, n_kv=32, d_ff=8192,
    vocab=32064, head_dim=96, rope_theta=1e4, rms_eps=1e-5,
)
