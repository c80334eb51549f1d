"""Plain reference of AI21-Jamba2-3B as the program implements it, in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
and the benchmark's weights.

It imports nothing of ``repro``.  The layer equations are the Jamba model
type's (arXiv:2403.19887; ``config.json`` of ai21labs/AI21-Jamba2-3B):
pre-norm residual blocks, each a mixer then a SwiGLU MLP (``num_experts``
1, so every layer's MLP is dense).  Layer i mixes with attention iff
i % ``attn_period`` == ``attn_offset`` (layers 7 and 21 of 28): grouped
query attention, causal over every earlier key, with no positional
encoding.  Every other layer mixes with Mamba-1: ``in_proj`` to (u, z); a
depthwise causal conv with bias, then SiLU; ``x_proj`` to (dt, B, C) of
widths (dt_rank, N, N), each RMS-normed; Δ = softplus(``dt_proj``(dt) +
bias); the selective scan h_t = exp(Δ_t A) h_{t-1} + Δ_t u_t B_t,
y_t = C_t · h_t + D u_t; then y · SiLU(z) and ``out_proj``.  The vocabulary
head is the embedding, transposed.

Departures from the published model: none in the equations.  The weights
are random from the seed, not the published checkpoint.  The forward pass
is the whole teacher-forced sequence: attention over the full key range in
blocks of queries, and a sequential scan over time, one step at a time,
with no cache, chunking or kernel.

``make_params`` is the benchmark's weight generator: one jitted call from
the seed, on the device, in bfloat16 and in the layout the serving engine
takes.  ``logits_at`` upcasts one layer's weights at a time, so that the
check at 8192 + 64 tokens fits the chip beside the bfloat16 weights.
"""
from __future__ import annotations

import functools
import json
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32


# ----------------------------------------------------------------------
# Weights
# ----------------------------------------------------------------------

def layer_kind(s: dict, i: int) -> str:
    return "attn" if i % s["attn_period"] == s["attn_offset"] else "mamba"


def _block_specs(s: dict, kind: str) -> Dict[str, tuple]:
    d, ff, L = s["d_model"], s["d_ff"], s["n_layers"]
    sc = s["init"]
    w, w_out = sc["weight"], sc["weight"] / np.sqrt(2 * L)
    out = {
        "ln1/scale": ((d,), ("ones",)),
        "ln2/scale": ((d,), ("ones",)),
        "ffn/w_in": ((d, ff), w),
        "ffn/w_gate": ((d, ff), w),
        "ffn/w_out": ((ff, d), w_out),
    }
    if kind == "attn":
        H, Hk, hd = s["n_heads"], s["n_kv_heads"], s["head_dim"]
        out.update({
            "attn/wq": ((d, H * hd), w),
            "attn/wk": ((d, Hk * hd), w),
            "attn/wv": ((d, Hk * hd), w),
            "attn/wo": ((H * hd, d), w),
        })
        return out
    N, k, R = s["ssm_state"], s["ssm_conv"], s["dt_rank"]
    inner = s["ssm_expand"] * d
    out.update({
        "mamba/w_in": ((d, 2 * inner), w),
        "mamba/conv_w": ((k, inner), sc["conv"]),
        "mamba/conv_b": ((inner,), ("zeros",)),
        "mamba/x_proj": ((inner, R + 2 * N), w),
        "mamba/dt_norm": ((R,), ("ones",)),
        "mamba/B_norm": ((N,), ("ones",)),
        "mamba/C_norm": ((N,), ("ones",)),
        "mamba/dt_proj": ((R, inner), sc["dt"]),
        "mamba/dt_bias": ((inner,), ("dt_bias",)),
        "mamba/A_log": ((inner, N), ("alog",)),
        "mamba/D": ((inner,), ("ones",)),
        "mamba/w_out": ((inner, d), w),
    })
    return out


def leaf_specs(s: dict) -> Dict[str, tuple]:
    """path -> (shape, init) for every weight.  ``init`` is a normal's scale,
    or a fill: ("ones",), ("zeros",), ("alog",) = log(1..N) per channel,
    ("dt_bias",) = softplus⁻¹ of step sizes spread log-uniformly over
    [1e-3, 1e-1] across the channels (Mamba's own initialisation).
    Block leaves are ``blocks/<position in the period>/...`` with a leading
    axis over the periods, as the engine scans them."""
    P = s["attn_period"]
    groups = s["n_layers"] // P
    out = {"embed": ((s["vocab"], s["d_model"]), s["init"]["weight"]),
           "final_norm/scale": ((s["d_model"],), ("ones",))}
    for j in range(P):
        for path, (shape, how) in _block_specs(s, layer_kind(s, j)).items():
            out[f"blocks/{j}/{path}"] = ((groups,) + shape, how)
    return out


def _nest(flat: Dict[str, jnp.ndarray], period: int) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    out["blocks"] = tuple(out["blocks"][str(j)] for j in range(period))
    return out


def key_of(seed: int) -> jax.Array:
    """A key from any whole seed, including ones past 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed % 2 ** 32)
    return jax.random.fold_in(key, (seed // 2 ** 32) % 2 ** 31)


def _fill(how, shape):
    if how == ("ones",):
        return jnp.ones(shape, F32)
    if how == ("zeros",):
        return jnp.zeros(shape, F32)
    if how == ("alog",):
        return jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[-1] + 1, dtype=F32)), shape)
    if how == ("dt_bias",):
        dt = jnp.exp(jnp.linspace(np.log(1e-3), np.log(1e-1), shape[-1],
                                  dtype=F32))
        return jnp.broadcast_to(dt + jnp.log(-jnp.expm1(-dt)), shape)
    raise KeyError(how)


def make_params(seed: int, s: dict, dtype=jnp.bfloat16) -> dict:
    """All weights from the seed, on the default device, in one jitted call."""
    specs = leaf_specs(s)

    @jax.jit
    def init(key):
        keys = jax.random.split(key, len(specs))
        flat = {}
        for kk, (path, (shape, how)) in zip(keys, sorted(specs.items())):
            v = (jax.random.normal(kk, shape, F32) * how
                 if isinstance(how, float) else _fill(how, shape))
            flat[path] = v.astype(dtype)
        return _nest(flat, s["attn_period"])

    return init(key_of(seed))


# ----------------------------------------------------------------------
# Forward pass
# ----------------------------------------------------------------------

Quant = Optional[Callable[[jnp.ndarray], jnp.ndarray]]


def fp8(x):
    """Per-tensor scaled float8 (e4m3) rounding: the precision below the
    bfloat16 the configuration states, for the control."""
    x = x.astype(F32)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(a, w, q: Quant):
    a, w = a.astype(F32), w.astype(F32)
    if q is not None:
        a, w = q(a), q(w)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _attention(h, p, s, q: Quant, block: int = 256):
    """Causal attention over every earlier key, no positional encoding."""
    B, S, _ = h.shape
    H, Hk, hd = s["n_heads"], s["n_kv_heads"], s["head_dim"]
    g = H // Hk
    qh = _mm(h, p["wq"], q).reshape(B, S, H, hd)
    kh = _mm(h, p["wk"], q).reshape(B, S, Hk, hd)
    vh = _mm(h, p["wv"], q).reshape(B, S, Hk, hd)
    outs = []
    for a in range(0, S, block):
        b = min(S, a + block)
        qb = qh[:, a:b].reshape(B, b - a, Hk, g, hd)
        kb, vb = kh[:, :b], vh[:, :b]
        if q is not None:
            qb, kb, vb = q(qb), q(kb), q(vb)
        sc = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb,
                        precision=HIGHEST) / np.sqrt(hd)
        mask = jnp.arange(b)[None, :] <= jnp.arange(a, b)[:, None]
        w = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        if q is not None:
            w = q(w)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", w, vb, precision=HIGHEST)
        outs.append(o.reshape(B, b - a, H * hd))
    return _mm(jnp.concatenate(outs, axis=1), p["wo"], q)


def _mamba(h, p, s, q: Quant):
    B, S, d = h.shape
    inner, k = s["ssm_expand"] * d, s["ssm_conv"]
    R, N, eps = s["dt_rank"], s["ssm_state"], s["norm_eps"]
    uz = _mm(h, p["w_in"], q)
    u_raw, z = uz[..., :inner], uz[..., inner:]
    cw = p["conv_w"].astype(F32)
    up = jnp.pad(u_raw, ((0, 0), (k - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(up[:, i:i + S] * cw[i] for i in range(k))
                    + p["conv_b"].astype(F32))
    proj = _mm(u, p["x_proj"], q)
    dt = _rms(proj[..., :R], p["dt_norm"], eps)
    Bm = _rms(proj[..., R:R + N], p["B_norm"], eps)
    Cm = _rms(proj[..., R + N:], p["C_norm"], eps)
    dt = jax.nn.softplus(_mm(dt, p["dt_proj"], q) + p["dt_bias"].astype(F32))
    A = -jnp.exp(p["A_log"].astype(F32))                       # (inner, N)

    def step(hs, xs):
        dt_t, u_t, b_t, c_t = xs
        hs = jnp.exp(dt_t[:, :, None] * A) * hs \
            + (dt_t * u_t)[:, :, None] * b_t[:, None, :]
        return hs, jnp.einsum("bin,bn->bi", hs, c_t, precision=HIGHEST)

    h0 = jnp.zeros((B, inner, N), F32)
    _, ys = jax.lax.scan(step, h0, (dt.swapaxes(0, 1), u.swapaxes(0, 1),
                                    Bm.swapaxes(0, 1), Cm.swapaxes(0, 1)))
    y = (ys.swapaxes(0, 1) + u * p["D"].astype(F32)) * jax.nn.silu(z)
    return _mm(y, p["w_out"], q)


def _layer(x, p, s, kind: str, q: Quant):
    eps = s["norm_eps"]
    h = _rms(x, p["ln1"]["scale"], eps)
    if kind == "attn":
        x = x + _attention(h, p["attn"], s, q)
    else:
        x = x + _mamba(h, p["mamba"], s, q)
    h2 = _rms(x, p["ln2"]["scale"], eps)
    f = p["ffn"]
    y = jax.nn.silu(_mm(h2, f["w_gate"], q)) * _mm(h2, f["w_in"], q)
    return x + _mm(y, f["w_out"], q)


@functools.lru_cache(maxsize=None)
def _programs(skey: str, q: Quant):
    """The jitted layers (one per kind) and head for one set of sizes."""
    s = json.loads(skey)
    layers = {kind: jax.jit(functools.partial(_layer, s=s, kind=kind, q=q))
              for kind in ("attn", "mamba")}

    @jax.jit
    def head(x, pos, scale, w):
        xs = jnp.take_along_axis(x, pos[:, :, None], axis=1)
        return _mm(_rms(xs, scale, s["norm_eps"]), w.T, q)

    return layers, head


def logits_at(params: dict, tokens: np.ndarray, positions: np.ndarray,
              s: dict, q: Quant = None) -> np.ndarray:
    """Logits (float32) of ``tokens`` (B, S) at ``positions`` (B, n): the
    teacher-forced forward pass, one layer at a time."""
    with jax.default_matmul_precision("highest"):
        layers, head = _programs(json.dumps(s, sort_keys=True), q)
        x = params["embed"].astype(F32)[jnp.asarray(tokens)]
        P = s["attn_period"]
        for i in range(s["n_layers"]):
            g, j = divmod(i, P)
            p = jax.tree_util.tree_map(lambda a: a[g], params["blocks"][j])
            x = layers[layer_kind(s, i)](x, p)
        out = head(x, jnp.asarray(positions), params["final_norm"]["scale"],
                   params["embed"])
    return np.asarray(out, np.float32)
