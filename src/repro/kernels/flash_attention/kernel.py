"""Blocked causal flash attention as a Pallas TPU kernel.

One grid cell per (batch, KV head, query block, KV block), KV blocks last
and sequential.  The query block holds ``block_q`` positions of all ``g``
query heads that share the KV head, as ``block_q * g`` rows ordered
(position, head): row r is position ``r // g``.  So the K and V tiles of a
grid cell serve every head of the group, and are read once per query
block, not once per head.  The rows are taken in chunks, and each chunk's
score tile (chunk x block_k) lives only in VMEM: it never goes through
HBM.  The online-softmax state (m, l, acc) is float32 VMEM scratch that
persists across the KV blocks.

Both dots take the operands in the model's dtype and accumulate in float32
on the MXU; p is cast to V's dtype before P.V, as the XLA chunked path in
``models/attention.py`` does.  A KV block that lies wholly above the
diagonal or outside the sliding window is skipped: its compute is guarded
by ``pl.when`` and its index map is clamped to the last block the query
block needs, so the pipeline issues no copy for it.  Only blocks that
straddle the diagonal or the window's edge build the position mask.

The queries come pre-scaled by ``hd ** -0.5``.  Optionally the kernel also
returns each row's log-sum-exp (m + log l, float32), the residual of the
chunked path's backward pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30
LANES = 128


def _needed(q_lo, q_hi, k_lo, k_hi, causal, window):
    """Whether some (query, key) pair of the two position ranges attends."""
    ok = True
    if causal:
        ok = k_lo <= q_hi
    if window > 0:
        ok = jnp.logical_and(ok, q_lo - k_hi < window)
    return ok


def _unmasked(q_lo, q_hi, k_lo, k_hi, causal, window):
    """Whether every (query, key) pair of the two ranges attends."""
    ok = True
    if causal:
        ok = k_hi <= q_lo
    if window > 0:
        ok = jnp.logical_and(ok, q_hi - k_lo < window)
    return ok


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *rest, block_q: int,
                  block_k: int, group: int, chunk: int, causal: bool,
                  window: int, softcap: float, n_k_blocks: int,
                  with_lse: bool):
    if with_lse:
        lse_ref, m_ref, l_ref, acc_ref = rest
    else:
        m_ref, l_ref, acc_ref = rest
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    rows = block_q * group

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo, k_lo = qi * block_q, kj * block_k
    q_hi, k_hi = q_lo + block_q - 1, k_lo + block_k - 1

    def update(masked: bool):
        k = k_ref[...]                                     # (bk, hd)
        v = v_ref[...]

        def body(c, carry):
            r0 = pl.multiple_of(c * chunk, chunk)
            rs = pl.ds(r0, chunk)
            s = jax.lax.dot_general(q_ref[rs, :], k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=F32)
            if softcap > 0:
                s = jnp.tanh(s / softcap) * softcap
            if masked:
                # Row r of the block is position q_lo + r // group; the
                # float quotient is exact at these sizes.
                r = (r0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0))
                q_pos = q_lo + jnp.floor(
                    (r.astype(F32) + 0.5) * (1.0 / group)).astype(jnp.int32)
                k_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                mask = jnp.ones(s.shape, jnp.bool_)
                if causal:
                    mask = k_pos <= q_pos
                if window > 0:
                    mask = jnp.logical_and(mask, q_pos - k_pos < window)
                s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[rs, :]                          # (chunk, 128)
            l_prev = l_ref[rs, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new[:, :1])
            alpha = jnp.exp(m_prev - m_new)
            l_ref[rs, :] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            m_ref[rs, :] = m_new
            pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                     (((1,), (0,)), ((), ())),
                                     preferred_element_type=F32)
            acc_ref[rs, :] = acc_ref[rs, :] * alpha[:, :1] + pv
            return carry

        jax.lax.fori_loop(0, rows // chunk, body, 0)

    run = _needed(q_lo, q_hi, k_lo, k_hi, causal, window)
    full = _unmasked(q_lo, q_hi, k_lo, k_hi, causal, window)
    if causal or window > 0:
        pl.when(jnp.logical_and(run, jnp.logical_not(full)))(
            lambda: update(True))
        pl.when(full)(lambda: update(False))
    else:
        update(False)

    @pl.when(kj == n_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l[:, :1]).astype(o_ref.dtype)
        if with_lse:
            lse = m_ref[...] + jnp.log(l)                  # (rows, 128)
            lse_ref[...] = lse.T[:1]                       # (1, rows)


@functools.partial(
    jax.jit, static_argnames=("group", "causal", "window", "softcap",
                              "block_q", "block_k", "chunk", "with_lse",
                              "interpret"))
def flash_attention_grouped(q, k, v, *, group: int, causal: bool = True,
                            window: int = 0, softcap: float = 0.0,
                            block_q: int, block_k: int, chunk: int,
                            with_lse: bool = False, interpret: bool = False):
    """q: (B, Hkv, Sq * group, hd), rows ordered (position, head) and
    pre-scaled by hd ** -0.5; k, v: (B, Hkv, Sk, hd).  ``block_q`` divides
    Sq and ``block_k`` Sk; ``chunk`` divides ``block_q * group`` and is a
    multiple of 16 rows.  Returns the output in q's layout and dtype and,
    with ``with_lse``, the rows' log-sum-exp (B, Hkv, 1, Sq * group)
    float32."""
    B, Hkv, rows_total, hd = q.shape
    Sk = k.shape[2]
    rows = block_q * group
    if rows_total % rows or Sk % block_k or rows % chunk or chunk % 16:
        raise ValueError(f"q {q.shape}, k {k.shape} do not tile by block_q "
                         f"{block_q} x {group} heads, block_k {block_k}, "
                         f"chunk {chunk}")
    nq, nk = rows_total // rows, Sk // block_k

    def kv_index(b, h, i, j):
        # Clamp to the blocks query block i needs, so a skipped step keeps
        # the tile it has and the pipeline copies nothing.
        if causal:
            j = jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
        if window > 0:
            j = jnp.maximum(j, jnp.maximum(i * block_q - window + 1, 0)
                            // block_k)
        return (b, h, j, 0)

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, group=group,
        chunk=chunk, causal=causal, window=window, softcap=softcap,
        n_k_blocks=nk, with_lse=with_lse)
    rows_spec = pl.BlockSpec((None, None, rows, hd),
                             lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, hd), kv_index)
    out_specs = [rows_spec]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((None, None, 1, rows),
                                      lambda b, h, i, j: (b, h, 0, i)))
        out_shape.append(jax.ShapeDtypeStruct((B, Hkv, 1, rows_total), F32))
    # Two buffers of q and o, the state, and a few score tiles in flight.
    vmem = (4 * rows * hd * q.dtype.itemsize + 4 * block_k * hd
            * k.dtype.itemsize + rows * (2 * LANES + max(hd, LANES)) * 4
            + 8 * chunk * block_k * 4)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, nq, nk),
        in_specs=[rows_spec, kv_spec, kv_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((rows, LANES), F32),
                        pltpu.VMEM((rows, LANES), F32),
                        pltpu.VMEM((rows, hd), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=max(32 << 20, vmem + (8 << 20))),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
    return tuple(out) if with_lse else out[0]
