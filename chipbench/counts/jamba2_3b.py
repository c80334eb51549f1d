"""Model FLOPs of Jamba2-3B's prefill and decode steps, from the sizes of
``configs/jamba2_3b.json`` (forward pass only, as served).

Per token: in every layer the SwiGLU MLP; in a Mamba layer the in, x, dt
and out projections as matrix multiplications (2 FLOPs per multiply-add),
the depthwise conv with its bias, the RMSNorms of dt, B and C, the step
size's bias and softplus, the selective-scan update (decay, state update
and drive, read-out by C: 6 per channel and state) and the skip and gate;
in an attention layer the q, k, v and o projections and the scores and
weighted values over every earlier key and the token itself.  Prefill
computes the tied vocabulary head for the last token of each row only; a
decode step computes it for its one token.  Every row of the batch counts,
padded ones included: the step does that work.
"""
from __future__ import annotations


def _n_attn(s: dict) -> int:
    return sum(i % s["attn_period"] == s["attn_offset"]
               for i in range(s["n_layers"]))


def _mamba_per_token(s: dict) -> float:
    d, inner = s["d_model"], s["ssm_expand"] * s["d_model"]
    N, k, R = s["ssm_state"], s["ssm_conv"], s["dt_rank"]
    proj = 2 * d * 2 * inner + 2 * inner * (R + 2 * N) + 2 * R * inner \
        + 2 * inner * d
    conv = 2 * k * inner + inner
    norms = 4 * (R + 2 * N)
    dt = 2 * inner
    scan = 6 * inner * N
    skip_gate = 4 * inner
    return proj + conv + norms + dt + scan + skip_gate


def _attn_proj_per_token(s: dict) -> float:
    d, H, Hk, hd = s["d_model"], s["n_heads"], s["n_kv_heads"], s["head_dim"]
    return 2 * d * (H * hd + 2 * Hk * hd) + 2 * H * hd * d


def _attn_per_key(s: dict) -> float:
    return 2 * 2 * s["n_heads"] * s["head_dim"]


def _mlp_per_token(s: dict) -> float:
    return 3 * 2 * s["d_model"] * s["d_ff"]


def _per_token(s: dict) -> float:
    a = _n_attn(s)
    return (s["n_layers"] * _mlp_per_token(s)
            + (s["n_layers"] - a) * _mamba_per_token(s)
            + a * _attn_proj_per_token(s))


def _head(s: dict) -> float:
    return 2 * s["d_model"] * s["vocab"]


def prefill_flops(s: dict, batch: int, prompt: int) -> float:
    keys = prompt * (prompt + 1) // 2
    per_row = (prompt * _per_token(s)
               + _n_attn(s) * keys * _attn_per_key(s))
    return batch * (per_row + _head(s))


def decode_flops(s: dict, batch: int, pos: int) -> float:
    """One decode step writing the token at position ``pos``."""
    per_row = _per_token(s) + _n_attn(s) * (pos + 1) * _attn_per_key(s)
    return batch * (per_row + _head(s))


def decode_mean_flops(s: dict, batch: int, prompt: int, new: int) -> float:
    """Mean over the ``new - 1`` decode steps of a request of ``new``
    tokens after a prompt of ``prompt``."""
    steps = range(prompt, prompt + new - 1)
    return sum(decode_flops(s, batch, p) for p in steps) / max(1, len(steps))
