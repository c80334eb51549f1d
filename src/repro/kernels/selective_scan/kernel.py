"""Mamba-1 selective scan (S6) as a Pallas TPU kernel.

Per channel l and state n:  h_t = exp(Δ_t A) ⊙ h_{t-1} + Δ_t u_t B_t,
y_t = C_t · h_t.  One grid cell per (batch, block of channels, time
chunk), time last and sequential: the (N, block) float32 state stays in
VMEM scratch across the chunks, and in vector registers across a chunk's
steps.  Δ and u are read in place, (batch, time, channels) in the dtype
the model computes them in, and cast to float32 inside; y is written once.
B and C, (batch, time, N), are turned state-major once a chunk, and each
group of steps rotates its columns to the first lanes, so a step's B_t and
C_t are static columns that broadcast along the channels.  A step leaves
C_t ⊙ h_t summed to 8 sublanes; after the group, strided loads sum those
slabs into the group's rows of y, so no step reduces across sublanes.
Every operation is elementwise float32: no step goes through the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
LANES, SUBLANES = 128, 8
GROUP = 32               # steps one loop iteration unrolls, whole sublanes


def _scan_kernel(dt_ref, u_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, hT_ref,
                 h_s, d_s, dx_s, y_s, q_s, *, chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _load_state():
        h_s[...] = h0_ref[0]

    # Mosaic loads single rows at a dynamic index only for 32-bit data, so
    # the chunk's Δ and Δu are staged in float32 scratch.
    d = dt_ref[0].astype(F32)                              # (chunk, blk)
    d_s[...] = d
    dx_s[...] = d * u_ref[0].astype(F32)
    bT = b_ref[0].astype(F32).T                            # (N, chunk)
    cT = c_ref[0].astype(F32).T
    A = a_ref[...]                                         # (N, blk)
    N, blk = A.shape
    tiles = blk // LANES

    def body(g, h):
        t0 = pl.multiple_of(g * GROUP, GROUP)
        dg = d_s[pl.ds(t0, GROUP), :]
        xg = dx_s[pl.ds(t0, GROUP), :]
        shift = (chunk - t0) % chunk                       # lane t0 -> 0
        bg = pltpu.roll(bT, shift, 1)
        cg = pltpu.roll(cT, shift, 1)
        for j in range(GROUP):
            h = jnp.exp(dg[j:j + 1] * A) * h + bg[:, j:j + 1] * xg[j:j + 1]
            p = cg[:, j:j + 1] * h                         # (N, blk)
            q = p[:SUBLANES]
            for r in range(SUBLANES, N, SUBLANES):
                q = q + p[r:r + SUBLANES]
            for l in range(tiles):                         # slab j
                q_s[l, pl.ds(SUBLANES * j, SUBLANES), :] = \
                    q[:, l * LANES:(l + 1) * LANES]
        # Row s of every slab, strided: y of step t0 + i is the sum over s
        # of row s of slab i.
        for k in range(GROUP // SUBLANES):
            base = k * SUBLANES * SUBLANES
            for l in range(tiles):
                acc = q_s[l, pl.ds(base, SUBLANES, stride=SUBLANES), :]
                for s in range(1, SUBLANES):
                    acc = acc + q_s[l, pl.ds(base + s, SUBLANES,
                                             stride=SUBLANES), :]
                y_s[pl.ds(t0 + k * SUBLANES, SUBLANES),
                    pl.ds(l * LANES, LANES)] = acc
        return h

    h = jax.lax.fori_loop(0, chunk // GROUP, body, h_s[...])
    h_s[...] = h
    y_ref[0] = y_s[...].astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _store_state():
        hT_ref[0] = h


@functools.partial(jax.jit, static_argnames=("block", "chunk", "interpret"))
def selective_scan_bsd(dt, u, Bm, Cm, A_T, h0, *, block: int, chunk: int,
                       interpret: bool = False):
    """dt, u: (B, S, inner); Bm, Cm: (B, S, N); A_T: (N, inner) float32;
    h0: (B, N, inner) float32.  S must be a multiple of ``chunk``, ``chunk``
    of ``GROUP``, N of 8, and inner of ``block``, itself of
    128 lanes; on a TPU ``chunk`` is a multiple of 128 lanes too.  Returns
    y (B, S, inner) in u.dtype and the last state (B, N, inner) float32."""
    Bsz, S, inner = dt.shape
    N = Bm.shape[-1]
    if S % chunk or chunk % GROUP or N % SUBLANES or inner % block \
            or block % LANES:
        raise ValueError(f"shapes {dt.shape}, N {N} do not tile by block "
                         f"{block} and chunk {chunk}")
    n_chunks = S // chunk
    kernel = functools.partial(_scan_kernel, chunk=chunk, n_chunks=n_chunks)
    seq = pl.BlockSpec((1, chunk, block), lambda b, j, c: (b, c, j))
    bc = pl.BlockSpec((1, chunk, N), lambda b, j, c: (b, c, 0))
    state = pl.BlockSpec((1, N, block), lambda b, j, c: (b, 0, j))
    return pl.pallas_call(
        kernel,
        grid=(Bsz, inner // block, n_chunks),
        in_specs=[seq, seq, bc, bc,
                  pl.BlockSpec((N, block), lambda b, j, c: (0, j)), state],
        out_specs=[seq, state],
        out_shape=[jax.ShapeDtypeStruct((Bsz, S, inner), u.dtype),
                   jax.ShapeDtypeStruct((Bsz, N, inner), F32)],
        scratch_shapes=[pltpu.VMEM((N, block), F32)]
        + [pltpu.VMEM((chunk, block), F32)] * 3
        + [pltpu.VMEM((block // LANES, SUBLANES * GROUP, LANES), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="mamba_scan",
    )(dt, u, Bm, Cm, A_T, h0)
