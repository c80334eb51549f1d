"""Grouped-query attention: full/sliding-window causal, cross, and cached
decode.  With ``use_flash`` the blocked-softmax path runs the Pallas flash
kernel (kernels/flash_attention), which compiles for the TPU only; without
it, a pure-jnp blocked softmax.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from ..sharding.context import pin_attention_blocks, shard_heads, use_weight
from .layers import apply_rope, normal_init, rms_norm_heads, rope_angles

NEG_INF = -1e30


def init_attention(key, cfg, dtype=jnp.float32, cross: bool = False):
    d, hd = cfg.d_model, cfg.hd
    ks = jax.random.split(key, 6)
    p = {
        "wq": normal_init(ks[0], (d, cfg.n_heads * hd), dtype=dtype),
        "wk": normal_init(ks[1], (d, cfg.n_kv_heads * hd), dtype=dtype),
        "wv": normal_init(ks[2], (d, cfg.n_kv_heads * hd), dtype=dtype),
        "wo": normal_init(ks[3], (cfg.n_heads * hd, d), dtype=dtype),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.ones((hd,), dtype)
        p["k_norm"] = jnp.ones((hd,), dtype)
    return p


def _project_qkv(cfg, p, xq, xkv):
    B, Sq, _ = xq.shape
    Skv = xkv.shape[1]
    hd = cfg.hd
    q = (xq @ use_weight(p["wq"].astype(xq.dtype), (None, "model"))
         ).reshape(B, Sq, cfg.n_heads, hd)
    k = (xkv @ use_weight(p["wk"].astype(xq.dtype), (None, "model"))
         ).reshape(B, Skv, cfg.n_kv_heads, hd)
    v = (xkv @ use_weight(p["wv"].astype(xq.dtype), (None, "model"))
         ).reshape(B, Skv, cfg.n_kv_heads, hd)
    if "q_norm" in p:
        q = rms_norm_heads(q, p["q_norm"])
        k = rms_norm_heads(k, p["k_norm"])
    return shard_heads(q), shard_heads(k), shard_heads(v)


def _sdpa(cfg, q, k, v, mask):
    """q: (B,Sq,H,hd), k/v: (B,Skv,Hkv,hd), mask: (Sq,Skv) or (B,1,Sq,Skv)."""
    B, Sq, H, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    qg = q.reshape(B, Sq, Hkv, g, hd)
    scale = hd ** -0.5
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg * scale, k,
                        preferred_element_type=jnp.float32)
    if cfg.logit_softcap > 0:
        c = cfg.logit_softcap
        logits = jnp.tanh(logits / c) * c
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None, None, None]
        else:
            mask = mask[:, :, None]
        logits = jnp.where(mask, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", w, v)
    return o.reshape(B, Sq, H * hd)


import functools as _functools


def _block_scores(qblk, kblk, qi, kj, q_chunk, kv_chunk, *, causal, window,
                  softcap):
    """Masked (softcapped) score block in f32.  qblk pre-scaled.
    Returns (s, tanh_grad or None)."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qblk, kblk,
                   preferred_element_type=jnp.float32)
    tgrad = None
    if softcap > 0:
        t = jnp.tanh(s / softcap)
        tgrad = 1.0 - t * t
        s = t * softcap
    q_pos = qi * q_chunk + jnp.arange(q_chunk)
    k_pos = kj * kv_chunk + jnp.arange(kv_chunk)
    mask = jnp.ones((q_chunk, kv_chunk), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window > 0:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    return s, tgrad, mask


@_functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _chunked_attention(q, k, v, causal, window, softcap, q_chunk, kv_chunk):
    """Flash attention in pure lax: blocked online softmax with an O(S·d)
    custom VJP that recomputes score blocks (the autodiff'd scan would save
    every (m, l, acc) carry — ~19 GB/layer at 4k x d18432).  This is both
    the XLA fallback for long sequences and the numerical reference for the
    Pallas kernel."""
    out, _ = _chunked_fwd_impl(q, k, v, causal, window, softcap, q_chunk,
                               kv_chunk)
    return out


def _chunked_fwd_impl(q, k, v, causal, window, softcap, q_chunk, kv_chunk):
    B, Sq, H, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    nq, nk = Sq // q_chunk, Skv // kv_chunk
    scale = hd ** -0.5

    qg = jnp.moveaxis((q * scale).reshape(B, nq, q_chunk, Hkv, g, hd), 1, 0)
    kb = jnp.moveaxis(k.reshape(B, nk, kv_chunk, Hkv, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nk, kv_chunk, Hkv, hd), 1, 0)
    qg, kb, vb = pin_attention_blocks(qg, kb, vb)

    def q_block(_, qi_and_q):
        qi, qblk = qi_and_q

        def kv_block(carry, kj_and_kv):
            m, l, acc = carry
            kj, kblk, vblk = kj_and_kv
            s, _, _ = _block_scores(qblk, kblk, qi, kj, q_chunk, kv_chunk,
                                    causal=causal, window=window,
                                    softcap=softcap)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(vblk.dtype), vblk)
            acc_new = acc * corr[..., None].astype(acc.dtype) + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Hkv, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, g, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Hkv, g, q_chunk, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_block, (m0, l0, a0),
                                      (jnp.arange(nk), kb, vb))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))        # (B,Hkv,g,qc)
        out = jnp.moveaxis(out, 3, 1).reshape(B, q_chunk, H * hd)
        return None, (out, lse)

    _, (outs, lses) = jax.lax.scan(q_block, None, (jnp.arange(nq), qg))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, Sq, H * hd).astype(q.dtype)
    lse = jnp.moveaxis(lses, 0, 3).reshape(B, Hkv, g, Sq)
    return out, lse


def _chunked_fwd(q, k, v, causal, window, softcap, q_chunk, kv_chunk):
    out, lse = _chunked_fwd_impl(q, k, v, causal, window, softcap, q_chunk,
                                 kv_chunk)
    return out, (q, k, v, out, lse)


def _chunked_bwd(causal, window, softcap, q_chunk, kv_chunk, res, dout):
    q, k, v, out, lse = res
    B, Sq, H, hd = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    g = H // Hkv
    nq, nk = Sq // q_chunk, Skv // kv_chunk
    scale = hd ** -0.5

    do = dout.reshape(B, Sq, Hkv, g, hd).astype(jnp.float32)
    og = out.reshape(B, Sq, Hkv, g, hd).astype(jnp.float32)
    # D = rowsum(do * o): (B, Hkv, g, Sq)
    D = jnp.einsum("bqhgd,bqhgd->bhgq", do, og)

    qg = jnp.moveaxis((q * scale).reshape(B, nq, q_chunk, Hkv, g, hd), 1, 0)
    dog = jnp.moveaxis(do.reshape(B, nq, q_chunk, Hkv, g, hd), 1, 0)
    kb = jnp.moveaxis(k.reshape(B, nk, kv_chunk, Hkv, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nk, kv_chunk, Hkv, hd), 1, 0)
    lse_b = jnp.moveaxis(lse.reshape(B, Hkv, g, nq, q_chunk), 3, 0)
    D_b = jnp.moveaxis(D.reshape(B, Hkv, g, nq, q_chunk), 3, 0)

    def p_and_ds(qblk, kblk, vblk, doblk, lseblk, Dblk, qi, kj):
        s, tgrad, mask = _block_scores(qblk, kblk, qi, kj, q_chunk, kv_chunk,
                                       causal=causal, window=window,
                                       softcap=softcap)
        p = jnp.exp(s - lseblk[..., None])               # (B,h,g,qc,kc)
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", doblk, vblk)
        ds = p * (dp - Dblk[..., None])
        if softcap > 0:
            ds = ds * tgrad
        ds = jnp.where(mask[None, None, None], ds, 0.0)
        return p, ds

    # pass 1: dq, scanning q blocks (inner over kv)
    def dq_block(_, xs):
        qi, qblk, doblk, lseblk, Dblk = xs

        def inner(dq, kv):
            kj, kblk, vblk = kv
            _, ds = p_and_ds(qblk, kblk, vblk, doblk, lseblk, Dblk, qi, kj)
            return dq + jnp.einsum("bhgqk,bkhd->bqhgd", ds,
                                   kblk.astype(jnp.float32)), None

        dq0 = jnp.zeros((B, q_chunk, Hkv, g, hd), jnp.float32)
        dq, _ = jax.lax.scan(inner, dq0, (jnp.arange(nk), kb, vb))
        return None, dq * scale

    _, dqs = jax.lax.scan(dq_block, None,
                          (jnp.arange(nq), qg, dog, lse_b, D_b))
    dq = jnp.moveaxis(dqs, 0, 1).reshape(B, Sq, H, hd).astype(q.dtype)

    # pass 2: dk/dv, scanning kv blocks (inner over q)
    def dkv_block(_, xs):
        kj, kblk, vblk = xs

        def inner(carry, qs):
            dk, dv = carry
            qi, qblk, doblk, lseblk, Dblk = qs
            p, ds = p_and_ds(qblk, kblk, vblk, doblk, lseblk, Dblk, qi, kj)
            dk = dk + jnp.einsum("bhgqk,bqhgd->bkhd", ds, qblk)
            dv = dv + jnp.einsum("bhgqk,bqhgd->bkhd", p, doblk)
            return (dk, dv), None

        z = jnp.zeros((B, kv_chunk, Hkv, hd), jnp.float32)
        (dk, dv), _ = jax.lax.scan(inner, (z, z),
                                   (jnp.arange(nq), qg, dog, lse_b, D_b))
        return None, (dk, dv)

    _, (dks, dvs) = jax.lax.scan(dkv_block, None, (jnp.arange(nk), kb, vb))
    dk = jnp.moveaxis(dks, 0, 1).reshape(B, Skv, Hkv, hd).astype(k.dtype)
    dv = jnp.moveaxis(dvs, 0, 1).reshape(B, Skv, Hkv, hd).astype(v.dtype)
    return dq, dk, dv


_chunked_attention.defvjp(_chunked_fwd, _chunked_bwd)


def _sdpa_chunked(cfg, q, k, v, *, causal: bool = True, window: int = 0,
                  q_chunk: int = 512, kv_chunk: int = 1024):
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    while Sq % q_chunk:
        q_chunk //= 2
    while Skv % kv_chunk:
        kv_chunk //= 2
    return _chunked_attention(q, k, v, causal, window,
                              float(cfg.logit_softcap), q_chunk, kv_chunk)


CHUNKED_THRESHOLD = 2048


def causal_mask(Sq: int, Skv: int, window: int = 0, offset: int = 0):
    """(Sq, Skv) boolean: query i attends key j iff j <= i+offset and, with a
    sliding window, i+offset - j < window."""
    qi = jnp.arange(Sq)[:, None] + offset
    kj = jnp.arange(Skv)[None, :]
    m = kj <= qi
    if window > 0:
        m &= (qi - kj) < window
    return m


def attend_full(cfg, p, x, positions, *, window: int = 0,
                use_flash: bool = False, bidirectional: bool = False):
    """Self-attention over a full sequence (train / prefill).

    Returns (out, (k, v)) so prefill can seed the decode cache.
    """
    q, k, v = _project_qkv(cfg, p, x, x)
    cos, sin = rope_angles(positions, cfg.hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    S = x.shape[1]
    if use_flash and not bidirectional:
        from ..kernels.flash_attention import ops as flash_ops
        o = flash_ops.flash_attention(q, k, v, window=window,
                                      softcap=cfg.logit_softcap)
        o = o.reshape(*o.shape[:2], -1)
    elif S >= CHUNKED_THRESHOLD:
        # long sequences: blocked online-softmax (O(S^2) logits never
        # materialize — required for the 32k prefill cells to fit HBM)
        o = _sdpa_chunked(cfg, q, k, v, causal=not bidirectional,
                          window=window)
    else:
        mask = None if bidirectional else causal_mask(S, S, window)
        o = _sdpa(cfg, q, k, v, mask)
    return o @ use_weight(p["wo"].astype(x.dtype), ("model", None)), (k, v)


def attend_cross(cfg, p, x, kv_src):
    """Cross-attention (enc-dec): no rope, no mask (full source)."""
    q, k, v = _project_qkv(cfg, p, x, kv_src)
    o = _sdpa(cfg, q, k, v, None)
    return o @ use_weight(p["wo"].astype(x.dtype), ("model", None))


def init_kv_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    hd = cfg.hd
    return {"k": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((batch, max_len, cfg.n_kv_heads, hd), dtype)}


def attend_decode(cfg, p, x, cache, pos, *, window: int = 0):
    """Single-token decode against a KV cache.

    x: (B, 1, d); cache: dict(k,v) of (B, Smax, Hkv, hd); pos: scalar int —
    the index of the new token (same for the whole batch).
    """
    B = x.shape[0]
    q, k_new, v_new = _project_qkv(cfg, p, x, x)
    posv = jnp.full((B, 1), pos, jnp.int32)
    cos, sin = rope_angles(posv, cfg.hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k_new = apply_rope(k_new, cos, sin)
    Smax = k_new.shape[1] and cache["k"].shape[1]
    ring = window > 0 and Smax <= window     # ring buffer (slot = pos % W)
    slot = pos % Smax if ring else pos
    k = jax.lax.dynamic_update_slice(cache["k"], k_new.astype(cache["k"].dtype),
                                     (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new.astype(cache["v"].dtype),
                                     (0, slot, 0, 0))
    kj = jnp.arange(Smax)[None, :]
    if ring:
        # every resident slot is within the window by construction; only
        # not-yet-written slots (early decode) are masked out
        m = (kj <= pos) | jnp.full((1, Smax), pos >= Smax)
    else:
        m = kj <= pos                   # (1, Smax) == (Sq=1, Skv)
        if window > 0:
            m &= (pos - kj) < window
    o = _sdpa(cfg, q, k.astype(x.dtype), v.astype(x.dtype), m)
    return o @ use_weight(p["wo"].astype(x.dtype), ("model", None)), {"k": k, "v": v}
