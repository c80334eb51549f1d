"""jit'd public wrapper for the selective-scan kernel: any sequence length
and state size, block sizes from the shapes.  The kernel compiles for the
TPU; callers off the TPU (the CPU tests) pass ``interpret=True``."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import LANES, SUBLANES, selective_scan_bsd

SCAN_BLOCK = 1024        # most channels one grid cell carries
SCAN_CHUNK = 256         # most time steps one grid cell reads


def channel_block(inner: int) -> int:
    """The widest multiple of 128 lanes that divides ``inner`` and is at
    most ``SCAN_BLOCK``: 1024 of Jamba's 5120, 640 of Hymba's 3200."""
    if inner % LANES:
        raise ValueError(f"inner {inner} is not a multiple of {LANES}")
    return max(b for b in range(LANES, min(inner, SCAN_BLOCK) + 1, LANES)
               if inner % b == 0)


def _pad_to(a, axis: int, size: int):
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, size - a.shape[axis])
    return jnp.pad(a, widths) if size > a.shape[axis] else a


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan(dt, u, Bm, Cm, A_T, h0, *, interpret: bool = False):
    """dt, u: (B, S, inner); Bm, Cm: (B, S, N); A_T: (N, inner), the
    negative state matrix; h0: (B, N, inner).  Returns y (B, S, inner) in
    u.dtype and the last state (B, N, inner) float32.  Time is padded to
    whole chunks of 128 lanes with Δ = 0, which leaves the state as it is;
    the state to whole sublanes with zero rows of A, B, C and h0, which
    stay zero and add nothing to y."""
    S, N = dt.shape[1], Bm.shape[-1]
    chunk = min(SCAN_CHUNK, -(-S // LANES) * LANES)
    S_pad, N_pad = -(-S // chunk) * chunk, -(-N // SUBLANES) * SUBLANES
    dt, u, Bm, Cm = (_pad_to(a, 1, S_pad) for a in (dt, u, Bm, Cm))
    Bm, Cm = (_pad_to(a, 2, N_pad) for a in (Bm, Cm))
    y, h = selective_scan_bsd(
        dt, u, Bm, Cm, _pad_to(A_T.astype(jnp.float32), 0, N_pad),
        _pad_to(h0.astype(jnp.float32), 1, N_pad),
        block=channel_block(dt.shape[2]), chunk=chunk, interpret=interpret)
    return y[:, :S], h[:, :N]
