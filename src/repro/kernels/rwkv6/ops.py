"""jit'd public wrapper for the WKV6 kernel: (B, T, H, hd) layout.  The
kernel compiles for the TPU; callers off the TPU (the CPU tests) pass
``interpret=True``."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import wkv6_bh


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, s0, *, chunk: int = 128, interpret: bool = False):
    """r/k/v/w: (B, T, H, hd); u: (H, hd); s0: (B, H, hd, hd) fp32.
    Returns (y (B, T, H*hd), s_final (B, H, hd, hd))."""
    B, T, H, hd = r.shape
    flat = lambda x: jnp.swapaxes(x, 1, 2).reshape(B * H, T, hd)
    uf = jnp.tile(u[None], (B, 1, 1)).reshape(B * H, hd)
    s0f = s0.reshape(B * H, hd, hd)
    y, sT = wkv6_bh(flat(r), flat(k), flat(v), flat(w), uf, s0f,
                    chunk=chunk, interpret=interpret)
    y = jnp.swapaxes(y.reshape(B, H, T, hd), 1, 2).reshape(B, T, H * hd)
    return y, sT.reshape(B, H, hd, hd)
