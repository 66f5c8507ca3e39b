"""AI21-Jamba2-3B: Mamba-1 / attention hybrid, one attention layer in 14.

Published config (huggingface.co/ai21labs/AI21-Jamba2-3B, config.json):
28 layers, hidden 2,560, 20 query heads and 1 KV head of 128, a SwiGLU FFN of
8,192 on every layer (the ``expert_layer_period`` 2 layers have one expert,
whose router softmaxes to a constant 1: the same dense FFN), vocabulary
65,536 with tied embeddings, RMSNorm eps 1e-6; Mamba ``d_state`` 16,
``d_conv`` 4, ``expand`` 2 (d_inner 5,120), ``dt_rank`` 160 (= 2,560/16),
conv bias on, projection bias off, RMSNorms on Δ, B and C.  Layer i is
attention iff i % 14 == 7 (``attn_layer_offset`` 7, ``attn_layer_period``
14); attention carries no position encoding.  Weights bf16; the activations
are carried in float32.
"""
from .base import ArchConfig

PERIOD = 14
ATTN_OFFSET = 7

CONFIG = ArchConfig(
    name="jamba2-3b",
    family="hybrid",
    n_layers=28, d_model=2560, n_heads=20, n_kv_heads=1, head_dim=128,
    d_ff=8192, vocab=65536,
    mixer_pattern=tuple("attn" if i % PERIOD == ATTN_OFFSET else "mamba"
                        for i in range(PERIOD)),
    rope=False,
    tie_embeddings=True,
    norm_eps=1e-6,
    dtype="bfloat16",
    compute_dtype="float32",
    ssm_state=16, ssm_conv=4, ssm_expand=2, ssm_inner_norm=True,
    citation="https://huggingface.co/ai21labs/AI21-Jamba2-3B",
)
