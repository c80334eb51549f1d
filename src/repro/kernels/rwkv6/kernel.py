"""RWKV6 WKV recurrence as a Pallas TPU kernel.

The GPU reference (RWKV's CUDA wkv6 kernel) assigns one thread per channel
with shared-memory staging of r/k/v/w — a warp-level pattern with no direct
TPU analogue.  The TPU-native re-think: one grid row per (batch x head),
the per-head state S (hd x hd, fp32, stored transposed) lives in VMEM
scratch and persists across the sequential time-chunk grid dimension; each
grid step streams a (chunk x hd) tile of r/k/v/w from HBM, stages it in
fp32, and walks it with a ``fori_loop`` of rank-1 updates (outer products
and row-by-matrix products on the MXU).

State is carried in/out explicitly so decode and chunked prefill compose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sT_ref,
                state_ref, r_s, k_s, v_s, w_s, y_s, *, chunk: int,
                n_chunks: int):
    # state_ref holds the transposed state ST[j, i] = S[i, j]: every
    # per-token operand is then a (1, hd) row, and no row ever has to be
    # turned into a column inside the kernel.
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _load_state():
        state_ref[...] = s0_ref[0]

    # Stage the chunk in fp32 scratch: Mosaic loads and stores single rows
    # at a dynamic index only for 32-bit data.
    r_s[...] = r_ref[0].astype(jnp.float32)
    k_s[...] = k_ref[0].astype(jnp.float32)
    v_s[...] = v_ref[0].astype(jnp.float32)
    w_s[...] = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)                    # (1, hd)

    def step(t, _):
        row = pl.ds(t, 1)
        r, k, v, w = r_s[row, :], k_s[row, :], v_s[row, :], w_s[row, :]
        ST = state_ref[...]                             # (hd, hd) fp32
        # y[j] = sum_i r[i] (S[i, j] + u[i] k[i] v[j])
        y = jax.lax.dot_general(r, ST, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        y_s[row, :] = y + jnp.sum(r * u * k) * v
        # S[i, j] <- w[i] S[i, j] + k[i] v[j], kept transposed
        vk = jax.lax.dot_general(v, k, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        state_ref[...] = ST * w + vk
        return 0

    jax.lax.fori_loop(0, chunk, step, 0)
    o_ref[0] = y_s[...].astype(o_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _store_state():
        sT_ref[0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_bh(r, k, v, w, u, s0, *, chunk: int = 128, interpret: bool = False):
    """r/k/v/w: (BH, T, hd); u: (BH, hd); s0: (BH, hd, hd) fp32.
    Returns (y (BH, T, hd) in r.dtype, s_final (BH, hd, hd) fp32).

    ``u`` enters the kernel as (BH, 1, hd): a (1, hd) block of a (BH, hd)
    array breaks the TPU's (8, 128) tiling rule, while a block whose last
    two dims equal the array's is always allowed."""
    BH, T, hd = r.shape
    chunk = min(chunk, T)
    while T % chunk:
        chunk //= 2
    n_chunks = T // chunk

    kernel = functools.partial(_wkv_kernel, chunk=chunk, n_chunks=n_chunks)
    seq_spec = pl.BlockSpec((1, chunk, hd), lambda bh, ci: (bh, ci, 0))
    y, sT = pl.pallas_call(
        kernel,
        grid=(BH, n_chunks),
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec,
                  pl.BlockSpec((1, 1, hd), lambda bh, ci: (bh, 0, 0)),
                  pl.BlockSpec((1, hd, hd), lambda bh, ci: (bh, 0, 0))],
        out_specs=[seq_spec,
                   pl.BlockSpec((1, hd, hd), lambda bh, ci: (bh, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((BH, T, hd), r.dtype),
                   jax.ShapeDtypeStruct((BH, hd, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)]
        + [pltpu.VMEM((chunk, hd), jnp.float32)] * 5,
        interpret=interpret,
    )(r, k, v, w, u.reshape(BH, 1, hd), jnp.swapaxes(s0, 1, 2))
    return y, jnp.swapaxes(sT, 1, 2)
