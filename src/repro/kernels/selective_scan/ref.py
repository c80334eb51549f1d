"""Pure-jnp oracle for the selective scan: one step per time step, float32."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def selective_scan_ref(dt, u, Bm, Cm, A_T, h0):
    """dt, u: (B, S, inner); Bm, Cm: (B, S, N); A_T: (N, inner); h0:
    (B, N, inner).  Returns y (B, S, inner) in u.dtype and the last state."""
    f32 = jnp.float32

    def step(h, xs):
        d, x, b, c = xs                                   # (B, inner|N)
        h = jnp.exp(d[:, None, :] * A_T) * h + (d * x)[:, None, :] \
            * b[:, :, None]
        return h, jnp.sum(c[:, :, None] * h, axis=1)

    xs = tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (dt, u, Bm, Cm))
    h, ys = jax.lax.scan(step, h0.astype(f32), xs)
    return jnp.moveaxis(ys, 0, 1).astype(u.dtype), h
