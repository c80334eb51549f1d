"""AI21-Jamba2-3B: Mamba-1 layers among two attention layers.

[hf:ai21labs/AI21-Jamba2-3B config.json; arXiv:2403.19887] — 28L,
d_model=2560, SwiGLU 8192 in every layer (num_experts 1: no MoE); layer i
is attention iff i % 14 == 7 (layers 7 and 21), 20 query heads and one KV
head of 128 (2560 / 20), no positional encoding; every other layer a
Mamba-1 mixer: expand 2 (inner 5120), state 16, conv 4 with bias, dt_rank
160, RMSNorm on dt/B/C (eps 1e-6).  No projection biases, tied
embeddings, vocab 65,536.
"""
from ..models.config import ArchConfig, SSMConfig

CONFIG = ArchConfig(
    name="jamba2-3b",
    family="hybrid",
    n_layers=28,
    d_model=2560,
    n_heads=20,
    n_kv_heads=1,
    head_dim=128,
    d_ff=8192,
    vocab=65536,
    norm="rmsnorm",
    mlp="swiglu",
    rope=False,
    attn_period=14,
    attn_offset=7,
    tie_embeddings=True,
    ssm=SSMConfig(kind="mamba", state_dim=16, expand=2, conv_dim=4,
                  dt_rank=160, inner_norms=True, conv_bias=True),
    source="[hf:ai21labs/AI21-Jamba2-3B; arXiv:2403.19887]",
)
