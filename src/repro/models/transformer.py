"""Unified LM stack for all 11 registered architectures.

Layers are scanned in *groups* (``cfg.layer_group``): uniform stacks scan
layer-by-layer; gemma3's 5-local:1-global pattern scans groups of six with
static per-position window flags, and jamba's attention/Mamba pattern
groups of fourteen (one attention block, thirteen Mamba blocks).
Parameters and caches carry a leading ``n_groups`` axis so the whole stack
lowers to one rolled ``lax.scan`` — essential to keep the 96-layer/340B
HLO small enough to compile.

Three entry points per model:
* ``forward_train``  — full-sequence logits (+ MoE aux losses);
* ``forward_prefill``— full sequence, returns last-token logits + caches;
* ``forward_decode`` — one token against caches (KV / RWKV / Mamba state).
"""
from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from . import attention as A
from . import mamba as M
from . import moe as MOE
from . import rwkv as R
from .config import ArchConfig
from ..sharding.context import shard_activations, use_weight
from .layers import apply_mlp, apply_norm, init_mlp, init_norm, normal_init


# ----------------------------------------------------------------------
# per-position layer kinds within one scan group
# ----------------------------------------------------------------------
def layer_kinds(cfg: ArchConfig) -> List[str]:
    if cfg.family == "ssm":
        return ["rwkv"]
    if cfg.family == "hybrid":
        if cfg.attn_period:
            return ["attn_global" if i == cfg.attn_offset else "mamba"
                    for i in range(cfg.attn_period)]
        return ["hymba"]
    g = cfg.layer_group
    if g > 1:  # local:global pattern (gemma3: 5 local then 1 global)
        return ["attn_local"] * (g - 1) + ["attn_global"]
    if cfg.sliding_window > 0 and cfg.global_every == 0:
        return ["attn_local"]
    return ["attn_global"]


def _uses_moe(cfg: ArchConfig) -> bool:
    return cfg.moe is not None


# ----------------------------------------------------------------------
# block init
# ----------------------------------------------------------------------
def init_block(key, cfg: ArchConfig, kind: str, dtype=jnp.float32,
               cross: bool = False) -> Dict[str, Any]:
    ks = jax.random.split(key, 8)
    p: Dict[str, Any] = {"ln1": init_norm(cfg, cfg.d_model, dtype)}
    if kind == "rwkv":
        p["rwkv"] = R.init_rwkv(ks[0], cfg, dtype)
        p["ln2"] = init_norm(cfg, cfg.d_model, dtype)
        p["cmix"] = R.init_channel_mix(ks[1], cfg, dtype)
        return p
    if kind != "mamba":
        p["attn"] = A.init_attention(ks[0], cfg, dtype)
    if kind in ("mamba", "hymba"):
        p["mamba"] = M.init_mamba(ks[1], cfg, dtype)
    if cross:
        p["ln_cross"] = init_norm(cfg, cfg.d_model, dtype)
        p["cross"] = A.init_attention(ks[2], cfg, dtype, cross=True)
    p["ln2"] = init_norm(cfg, cfg.d_model, dtype)
    if _uses_moe(cfg):
        p["moe"] = MOE.init_moe(ks[3], cfg, dtype)
    else:
        p["ffn"] = init_mlp(ks[3], cfg, cfg.d_model, cfg.d_ff, dtype)
    return p


def init_block_cache(cfg: ArchConfig, kind: str, batch: int, max_len: int,
                     cross_len: int = 0, dtype=jnp.bfloat16):
    if kind == "rwkv":
        st = R.init_rwkv_state(cfg, batch)
        st["cmix_shift"] = jnp.zeros((batch, cfg.d_model), jnp.float32)
        return st
    if kind == "mamba":
        return {"mamba": M.init_mamba_state(cfg, batch)}
    # sliding-window layers only ever attend to the last `window` keys:
    # their cache is a ring buffer of that size (a 32k gemma3/hymba cache
    # would otherwise be ~40x larger than needed)
    if kind in ("attn_local", "hymba") and cfg.sliding_window > 0:
        max_len = min(max_len, cfg.sliding_window)
    c: Dict[str, Any] = dict(A.init_kv_cache(cfg, batch, max_len, dtype))
    if kind == "hymba":
        c["mamba"] = M.init_mamba_state(cfg, batch)
    if cross_len:
        c["cross_k"] = jnp.zeros((batch, cross_len, cfg.n_kv_heads, cfg.hd), dtype)
        c["cross_v"] = jnp.zeros((batch, cross_len, cfg.n_kv_heads, cfg.hd), dtype)
    return c


# ----------------------------------------------------------------------
# block apply
# ----------------------------------------------------------------------
def _ffn_part(cfg, p, x, aux):
    h = apply_norm(cfg, p["ln2"], x)
    if "moe" in p:
        y, a = MOE.apply_moe(cfg, p["moe"], h)
        aux = (aux[0] + a["moe_aux"], aux[1] + a["moe_z"])
    else:
        y = apply_mlp(cfg, p["ffn"], h)
    return x + y, aux


def apply_block_seq(cfg, kind, p, x, positions, aux, *, cache=None,
                    enc_out=None, bidirectional=False):
    """Full-sequence mode. Returns (x, aux, new_cache)."""
    h = apply_norm(cfg, p["ln1"], x)
    new_cache = None
    if kind == "rwkv":
        y, st = R.apply_rwkv_seq(cfg, p["rwkv"], h, cache if cache is not None
                                 else R.init_rwkv_state(cfg, x.shape[0]))
        x = x + y
        h2 = apply_norm(cfg, p["ln2"], x)
        y2, cshift = R.apply_channel_mix(
            cfg, p["cmix"], h2,
            cache["cmix_shift"] if cache is not None
            else jnp.zeros((x.shape[0], cfg.d_model), jnp.float32))
        st["cmix_shift"] = cshift.astype(jnp.float32)
        return x + y2, aux, st

    if kind in ("mamba", "hymba"):
        with jax.named_scope("mamba"):
            ym, mstate = M.apply_mamba_seq(
                cfg, p["mamba"], h,
                cache["mamba"] if cache is not None
                else M.init_mamba_state(cfg, x.shape[0]))
    if kind == "mamba":
        x, aux = _ffn_part(cfg, p, x + ym, aux)
        return x, aux, (None if cache is None else {"mamba": mstate})

    window = cfg.sliding_window if kind == "attn_local" or kind == "hymba" else 0
    with jax.named_scope("attn"):
        y, (k, v) = A.attend_full(cfg, p["attn"], h, positions, window=window,
                                  bidirectional=bidirectional)
    if kind == "hymba":
        y = 0.5 * (y + ym)
    x = x + y
    if "cross" in p and enc_out is not None:
        hc = apply_norm(cfg, p["ln_cross"], x)
        x = x + A.attend_cross(cfg, p["cross"], hc, enc_out)
    x, aux = _ffn_part(cfg, p, x, aux)
    if cache is not None:
        S = k.shape[1]
        W = cache["k"].shape[1]
        new_cache = dict(cache)
        if W < S:
            # ring buffer: token t lives at slot t % W
            kt = k[:, S - W:].astype(cache["k"].dtype)
            vt = v[:, S - W:].astype(cache["v"].dtype)
            shift = S % W
            new_cache["k"] = jnp.roll(kt, shift, axis=1)
            new_cache["v"] = jnp.roll(vt, shift, axis=1)
        else:
            new_cache["k"] = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
            new_cache["v"] = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
        if kind == "hymba":
            new_cache["mamba"] = mstate
        if "cross" in p and enc_out is not None:
            _, ck, cv = A._project_qkv(cfg, p["cross"], x, enc_out)
            new_cache["cross_k"] = ck.astype(cache["cross_k"].dtype)
            new_cache["cross_v"] = cv.astype(cache["cross_v"].dtype)
    return x, aux, new_cache


def apply_block_decode(cfg, kind, p, x, cache, pos, aux):
    """Single-token mode. Returns (x, aux, new_cache)."""
    h = apply_norm(cfg, p["ln1"], x)
    if kind == "rwkv":
        y, st = R.apply_rwkv_step(cfg, p["rwkv"], h, cache)
        x = x + y
        h2 = apply_norm(cfg, p["ln2"], x)
        y2, cshift = R.apply_channel_mix(cfg, p["cmix"], h2,
                                         cache["cmix_shift"].astype(x.dtype))
        st["cmix_shift"] = cshift.astype(jnp.float32)
        return x + y2, aux, st

    if kind in ("mamba", "hymba"):
        with jax.named_scope("mamba"):
            ym, mstate = M.apply_mamba_step(cfg, p["mamba"], h,
                                            cache["mamba"])
    if kind == "mamba":
        x, aux = _ffn_part(cfg, p, x + ym, aux)
        return x, aux, {"mamba": mstate}

    window = cfg.sliding_window if kind in ("attn_local", "hymba") else 0
    with jax.named_scope("attn"):
        y, kv = A.attend_decode(cfg, p["attn"], h, cache, pos, window=window)
    new_cache = dict(cache)
    new_cache.update(kv)
    if kind == "hymba":
        y = 0.5 * (y + ym)
        new_cache["mamba"] = mstate
    x = x + y
    if "cross" in p:
        hc = apply_norm(cfg, p["ln_cross"], x)
        o = A._sdpa(cfg,
                    (hc @ p["cross"]["wq"].astype(x.dtype)).reshape(
                        x.shape[0], 1, cfg.n_heads, cfg.hd),
                    cache["cross_k"].astype(x.dtype),
                    cache["cross_v"].astype(x.dtype), None)
        x = x + o @ p["cross"]["wo"].astype(x.dtype)
    x, aux = _ffn_part(cfg, p, x, aux)
    return x, aux, new_cache


# ----------------------------------------------------------------------
# whole-model init
# ----------------------------------------------------------------------
def init_lm(key, cfg: ArchConfig, dtype=jnp.float32):
    kinds = layer_kinds(cfg)
    g = len(kinds)
    n_groups = cfg.n_layers // g
    ks = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": normal_init(ks[0], (cfg.vocab, cfg.d_model), dtype=dtype),
        "final_norm": init_norm(cfg, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = normal_init(ks[1], (cfg.d_model, cfg.vocab),
                                        dtype=dtype)

    cross = cfg.n_encoder_layers > 0

    def stack(key, kind, cross_flag):
        keys = jax.random.split(key, n_groups)
        return jax.vmap(lambda k: init_block(k, cfg, kind, dtype, cross_flag)
                        )(keys)

    params["blocks"] = tuple(
        stack(jax.random.fold_in(ks[2], i), kind, cross)
        for i, kind in enumerate(kinds))

    if cross:  # encoder stack (seamless)
        enc_keys = jax.random.split(ks[3], cfg.n_encoder_layers)
        params["encoder"] = jax.vmap(
            lambda k: init_block(k, cfg, "attn_global", dtype, False)
        )(enc_keys)
        params["enc_norm"] = init_norm(cfg, cfg.d_model, dtype)
        params["frontend_proj"] = normal_init(ks[4], (cfg.d_model, cfg.d_model),
                                              dtype=dtype)
    if cfg.frontend == "vision":
        params["frontend_proj"] = normal_init(ks[4], (cfg.d_model, cfg.d_model),
                                              dtype=dtype)
    return params


def init_cache(cfg: ArchConfig, batch: int, max_len: int, cross_len: int = 0,
               dtype=jnp.bfloat16):
    kinds = layer_kinds(cfg)
    g = len(kinds)
    n_groups = cfg.n_layers // g

    def stacked(kind):
        one = init_block_cache(cfg, kind, batch, max_len, cross_len, dtype)
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (n_groups,) + a.shape), one)

    return tuple(stacked(kind) for kind in kinds)


# ----------------------------------------------------------------------
# forward passes
# ----------------------------------------------------------------------
def _embed(cfg, params, tokens, frontend=None):
    table = use_weight(params["embed"].astype(jnp.bfloat16), ("model", None))
    x = table[tokens]
    x = shard_activations(x)
    if cfg.frontend == "vision" and frontend is not None:
        fp = frontend.astype(x.dtype) @ params["frontend_proj"].astype(x.dtype)
        x = jax.lax.dynamic_update_slice(x, fp, (0, 0, 0))
    return x


def _encode(cfg, params, frames):
    """Seamless encoder: frames (B, S_enc, d) from the audio-frontend stub."""
    x = frames.astype(jnp.bfloat16) @ params["frontend_proj"].astype(jnp.bfloat16)
    positions = jnp.arange(x.shape[1])[None, :]
    aux = (jnp.float32(0), jnp.float32(0))

    def body(carry, blk):
        x, aux = carry
        x, aux, _ = apply_block_seq(cfg, "attn_global", blk, x, positions, aux,
                                    bidirectional=True)
        return (x, aux), None

    (x, _), _ = jax.lax.scan(body, (x, aux), params["encoder"])
    return apply_norm(cfg, params["enc_norm"], x)


def _logits(cfg, params, x):
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    head = use_weight(head.astype(x.dtype), (None, "model"))
    return (x @ head).astype(jnp.float32)


def forward_train(cfg: ArchConfig, params, batch, remat: bool = False,
                  seq_shard: bool = False):
    """batch: dict(tokens (B,S) int32, + optional frames/patches).
    Returns (logits_f32 (B,S,V), aux dict).  ``remat=True`` checkpoints each
    scanned layer group (recompute in backward) to bound activation memory.
    """
    tokens = batch["tokens"]
    enc_out = None
    if cfg.n_encoder_layers:
        enc_out = _encode(cfg, params, batch["frames"])
    x = _embed(cfg, params, tokens, batch.get("patches"))
    positions = jnp.arange(tokens.shape[1])[None, :]
    aux = (jnp.float32(0), jnp.float32(0))
    kinds = layer_kinds(cfg)
    # Megatron-style sequence parallelism: the residual stream (and hence
    # the remat-saved layer inputs) is sharded over "model" along the
    # sequence dim between blocks; GSPMD inserts the gather at attention.
    seq_ax = "model" if seq_shard else None
    if seq_shard:
        x = shard_activations(x, seq_axis=seq_ax)

    def body(carry, blk_params):
        x, aux = carry
        for i, kind in enumerate(kinds):
            p_i = jax.tree_util.tree_map(lambda a: a, blk_params[i])
            x, aux, _ = apply_block_seq(cfg, kind, p_i, x, positions, aux,
                                        enc_out=enc_out)
            if seq_shard:
                x = shard_activations(x, seq_axis=seq_ax)
        return (x, aux), None

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(body, (x, aux), params["blocks"])
    x = apply_norm(cfg, params["final_norm"], x)
    return _logits(cfg, params, x), {"moe_aux": aux[0], "moe_z": aux[1]}


def forward_prefill(cfg: ArchConfig, params, batch, cache):
    """Full-sequence prefill that fills the caches.
    Returns (last-token logits (B,V), new_cache)."""
    tokens = batch["tokens"]
    enc_out = None
    if cfg.n_encoder_layers:
        enc_out = _encode(cfg, params, batch["frames"])
    x = _embed(cfg, params, tokens, batch.get("patches"))
    positions = jnp.arange(tokens.shape[1])[None, :]
    aux = (jnp.float32(0), jnp.float32(0))
    kinds = layer_kinds(cfg)

    # the cache rides in the scan CARRY and is updated slice-by-slice in
    # place: xs/ys caches would be double-buffered by XLA (2x cache HBM)
    def body(carry, xs):
        x, aux, cache_full = carry
        blk_params, g = xs
        new_caches = []
        for i, kind in enumerate(kinds):
            cache_g = jax.tree_util.tree_map(lambda c: c[g], cache_full[i])
            x, aux, nc = apply_block_seq(cfg, kind, blk_params[i], x,
                                         positions, aux, cache=cache_g,
                                         enc_out=enc_out)
            new_caches.append(jax.tree_util.tree_map(
                lambda c, n: jax.lax.dynamic_update_index_in_dim(
                    c, n.astype(c.dtype), g, 0), cache_full[i], nc))
        return (x, aux, tuple(new_caches)), None

    n_groups = cfg.n_layers // len(kinds)
    (x, _, new_cache), _ = jax.lax.scan(
        body, (x, aux, cache), (params["blocks"], jnp.arange(n_groups)))
    x = apply_norm(cfg, params["final_norm"], x[:, -1:, :])
    return _logits(cfg, params, x)[:, 0], new_cache


def forward_decode(cfg: ArchConfig, params, tokens, cache, pos):
    """tokens: (B, 1); pos: scalar int32 index of the new token.
    Returns (logits (B, V), new_cache)."""
    x = _embed(cfg, params, tokens)
    aux = (jnp.float32(0), jnp.float32(0))
    kinds = layer_kinds(cfg)

    def body(carry, xs):
        x, aux, cache_full = carry
        blk_params, g = xs
        new_caches = []
        for i, kind in enumerate(kinds):
            cache_g = jax.tree_util.tree_map(lambda c: c[g], cache_full[i])
            x, aux, nc = apply_block_decode(cfg, kind, blk_params[i], x,
                                            cache_g, pos, aux)
            new_caches.append(jax.tree_util.tree_map(
                lambda c, n: jax.lax.dynamic_update_index_in_dim(
                    c, n.astype(c.dtype), g, 0), cache_full[i], nc))
        return (x, aux, tuple(new_caches)), None

    n_groups = cfg.n_layers // len(kinds)
    (x, _, new_cache), _ = jax.lax.scan(
        body, (x, aux, cache), (params["blocks"], jnp.arange(n_groups)))
    x = apply_norm(cfg, params["final_norm"], x)
    return _logits(cfg, params, x)[:, 0], new_cache


# ----------------------------------------------------------------------
def cross_entropy_loss(logits, labels, z_loss: float = 1e-4):
    """logits (B,S,V) f32; labels (B,S) int32; returns scalar mean loss.

    The gold logit is picked with a one-hot reduction rather than
    ``take_along_axis``: a vocab-dim gather would force GSPMD to all-gather
    the (B,S,V) logits across the TP axis, while the compare-and-reduce
    stays sharded (verified in the dry-run collective table).
    """
    lse = jax.nn.logsumexp(logits, axis=-1)
    vocab = logits.shape[-1]
    onehot = labels[..., None] == jnp.arange(vocab, dtype=labels.dtype)
    gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    loss = jnp.mean(lse - gold)
    if z_loss:
        loss = loss + z_loss * jnp.mean(lse ** 2)
    return loss
