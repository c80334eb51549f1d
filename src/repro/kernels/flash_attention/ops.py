"""jit'd public wrapper: (B, S, H, hd) layout.  The kernel compiles for the
TPU; callers off the TPU (the CPU tests) pass ``interpret=True``."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import flash_attention_bhsd


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: float = 0.0, block_q: int = 256,
                    block_k: int = 256, interpret: bool = False):
    """q: (B, Sq, H, hd); k/v: (B, Sk, Hkv, hd) -> (B, Sq, H, hd)."""
    B, Sq, H, hd = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    qf = jnp.swapaxes(q, 1, 2).reshape(B * H, Sq, hd)
    kf = jnp.swapaxes(k, 1, 2).reshape(B * Hkv, Sk, hd)
    vf = jnp.swapaxes(v, 1, 2).reshape(B * Hkv, Sk, hd)
    o = flash_attention_bhsd(qf, kf, vf, causal=causal, window=window,
                             softcap=softcap, block_q=block_q,
                             block_k=block_k, interpret=interpret,
                             num_q_heads=H)
    return jnp.swapaxes(o.reshape(B, H, Sq, hd), 1, 2)
