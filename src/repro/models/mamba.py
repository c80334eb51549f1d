"""Mamba-1 selective SSM mixer: Hymba's SSM heads (arXiv:2411.13676) and
Jamba's Mamba layers (arXiv:2403.19887).

Diagonal selective scan (S6): per channel c and state n
    h_t = exp(Δ_t A) ⊙ h_{t-1} + Δ_t B_t u_t
    y_t = C_t · h_t + D u_t
with Δ, B, C input-dependent.  ``cfg.ssm`` says how they are formed:
Hymba's mixer takes Δ as an elementwise per-channel projection of the conv
output and B, C from two matrices; Jamba's (``dt_rank > 0``) is Mamba-1 as
published: one ``x_proj`` to (dt_rank + 2N), RMSNorm of the dt, B and C
activations (``inner_norms``), then ``dt_proj`` dt_rank -> inner with a
bias, and a bias on the depthwise conv (``conv_bias``).

Sequence mode forms each step's decay and drive from that step's Δ, u and
B, so no (batch, seq, inner, state) array exists at any length.  On a TPU,
outside a sharding mesh and at a width of whole 128-lane tiles
(``scan_kernel_engaged``), the recurrence is the Pallas kernel
``kernels/selective_scan``: the state stays in VMEM, Δ, u, B and C are read
in place and y is written once.  Elsewhere it is an XLA scan over time in
checkpointed chunks, a few steps to a loop iteration, which keeps one state
per chunk for a backward pass.  On a TPU v5e, at 4 x 8192 tokens and
Jamba's widths, the XLA scan took 17.8-20.9 ms a layer and the kernel 8.0
ms.  The kernel's backward pass is that XLA scan's.  Decode mode is an
O(1) state update.  The state is laid out (batch, state, inner), inner
minor, as the TPU tiles it.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from ..kernels.selective_scan import ops as scan_ops
from ..sharding.context import mesh_active, use_weight
from .layers import normal_init, rms_norm_heads

SCAN_CHUNK = 128         # time steps per checkpointed chunk of the scan
SCAN_UNROLL = 4          # steps per iteration of the loop inside a chunk


def init_mamba(key, cfg, dtype=jnp.float32):
    d = cfg.d_model
    s = cfg.ssm
    inner = s.expand * d
    ks = jax.random.split(key, 7)
    p = {
        "w_in": normal_init(ks[0], (d, 2 * inner), dtype=dtype),   # u, z
        "A_log": jnp.log(jnp.tile(jnp.arange(1, s.state_dim + 1,
                                             dtype=jnp.float32)[None, :],
                                  (inner, 1))).astype(dtype),
        "D": jnp.ones((inner,), dtype),
        "conv_w": normal_init(ks[4], (s.conv_dim, inner), scale=0.2,
                              dtype=dtype),
        "w_out": normal_init(ks[5], (inner, d), dtype=dtype),
        "dt_bias": jnp.zeros((inner,), dtype),
    }
    if s.dt_rank:
        p["x_proj"] = normal_init(ks[1], (inner, s.dt_rank + 2 * s.state_dim),
                                  dtype=dtype)
        p["dt_proj"] = normal_init(ks[2], (s.dt_rank, inner), scale=0.1,
                                   dtype=dtype)
    else:
        p["w_dt"] = normal_init(ks[1], (inner, 1), scale=0.1, dtype=dtype)
        p["w_B"] = normal_init(ks[2], (inner, s.state_dim), dtype=dtype)
        p["w_C"] = normal_init(ks[3], (inner, s.state_dim), dtype=dtype)
    if s.inner_norms:
        p["dt_norm"] = jnp.ones((s.dt_rank,), dtype)
        p["B_norm"] = jnp.ones((s.state_dim,), dtype)
        p["C_norm"] = jnp.ones((s.state_dim,), dtype)
    if s.conv_bias:
        p["conv_b"] = jnp.zeros((inner,), dtype)
    return p


def init_mamba_state(cfg, batch: int, dtype=jnp.float32):
    s = cfg.ssm
    inner = s.expand * cfg.d_model
    return {"h": jnp.zeros((batch, s.state_dim, inner), jnp.float32),
            "conv": jnp.zeros((batch, s.conv_dim - 1, inner), dtype)}


def _features(cfg, p, u_conv):
    """Input-dependent SSM parameters (Δ, B, C) from the conv'd activation."""
    s = cfg.ssm
    dt_ = u_conv.dtype
    if not s.dt_rank:
        dt = jax.nn.softplus(u_conv * p["w_dt"].astype(dt_)[:, 0]
                             + p["dt_bias"].astype(dt_))
        return dt, u_conv @ p["w_B"].astype(dt_), u_conv @ p["w_C"].astype(dt_)
    proj = u_conv @ p["x_proj"].astype(dt_)
    R, N = s.dt_rank, s.state_dim
    dt_r, B, C = proj[..., :R], proj[..., R:R + N], proj[..., R + N:]
    if s.inner_norms:
        dt_r = rms_norm_heads(dt_r, p["dt_norm"])
        B = rms_norm_heads(B, p["B_norm"])
        C = rms_norm_heads(C, p["C_norm"])
    dt = jax.nn.softplus(dt_r @ p["dt_proj"].astype(dt_)
                         + p["dt_bias"].astype(dt_))
    return dt, B, C


def _conv_out(p, acc):
    if "conv_b" in p:
        acc = acc + p["conv_b"].astype(acc.dtype)
    return jax.nn.silu(acc)


def _causal_conv_seq(p, u, conv_state):
    """Depthwise causal conv over time. u: (B,S,inner)."""
    k = p["conv_w"].shape[0]
    pad = jnp.concatenate([conv_state.astype(u.dtype), u], axis=1)        # (B, S+k-1, inner)
    out = jnp.zeros_like(u)
    cw = p["conv_w"].astype(u.dtype)
    for i in range(k):
        out = out + pad[:, i:i + u.shape[1], :] * cw[i][None, None, :]
    return _conv_out(p, out), pad[:, -(k - 1):, :]


def selective_scan(dt, u, Bm, Cm, A_T, h0):
    """The S6 recurrence over time, in float32.

    dt, u: (B, S, inner); Bm, Cm: (B, S, N); A_T: (N, inner), the negative
    state matrix; h0: (B, N, inner).  Returns y (B, S, inner) and the last
    state.  Time is padded to whole chunks with Δ = 0, which
    leaves the state as it is; each step's decay exp(Δ_t A) and drive
    Δ_t u_t B_t are (B, N, inner) values inside the step."""
    Bsz, S, inner = dt.shape
    chunk = min(SCAN_CHUNK, S)
    pad = (-S) % chunk
    if pad:
        widths = ((0, 0), (0, pad), (0, 0))
        dt, u, Bm, Cm = (jnp.pad(a, widths) for a in (dt, u, Bm, Cm))
    n = (S + pad) // chunk

    def time_major(a):          # (B, S, k) -> (chunks, chunk, B, k)
        return a.swapaxes(0, 1).reshape(n, chunk, Bsz, a.shape[-1])

    def step(h, xs):
        d, x, b, c = xs                                    # (B, inner|N)
        h = jnp.exp(d[:, None, :] * A_T) * h \
            + (d * x)[:, None, :] * b[:, :, None]
        return h, jnp.sum(c[:, :, None] * h, axis=1)

    def chunk_body(h, xs):
        return jax.lax.scan(step, h, xs, unroll=SCAN_UNROLL)

    chunk_body = jax.checkpoint(chunk_body, prevent_cse=False)
    h_last, ys = jax.lax.scan(chunk_body, h0,
                              tuple(time_major(a) for a in (dt, u, Bm, Cm)))
    y = ys.reshape(n * chunk, Bsz, inner).swapaxes(0, 1)[:, :S]
    return y, h_last


def _xla_scan(dt, u, Bm, Cm, A_T, h0):
    """``selective_scan`` on the model's activations: float32 inside, y
    back in u.dtype."""
    f32 = jnp.float32
    y, h_last = selective_scan(dt.astype(f32), u.astype(f32), Bm.astype(f32),
                               Cm.astype(f32), A_T, h0)
    return y.astype(u.dtype), h_last


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _kernel_scan(dt, u, Bm, Cm, A_T, h0, interpret):
    """The Pallas scan forward; its gradient is ``_xla_scan``'s."""
    return scan_ops.selective_scan(dt, u, Bm, Cm, A_T, h0,
                                   interpret=interpret)


def _kernel_scan_fwd(dt, u, Bm, Cm, A_T, h0, interpret):
    return (_kernel_scan(dt, u, Bm, Cm, A_T, h0, interpret),
            (dt, u, Bm, Cm, A_T, h0))


def _kernel_scan_bwd(interpret, res, g):
    return jax.vjp(_xla_scan, *res)[1](g)


_kernel_scan.defvjp(_kernel_scan_fwd, _kernel_scan_bwd)


def scan_kernel_engaged(cfg) -> bool:
    """Whether ``apply_mamba_seq`` runs the Pallas scan: on a TPU, outside
    a sharding mesh, and at an inner width of whole 128-lane tiles."""
    return (jax.default_backend() == "tpu" and not mesh_active()
            and (cfg.ssm.expand * cfg.d_model) % 128 == 0)


def apply_mamba_seq(cfg, p, x, state) -> Tuple[jnp.ndarray, dict]:
    """x: (B, S, d). Sequential scan over time in fp32."""
    B, S, d = x.shape
    s = cfg.ssm
    inner = s.expand * d
    uz = x @ use_weight(p["w_in"].astype(x.dtype), (None, "model"))
    u, z = uz[..., :inner], uz[..., inner:]
    u, conv_state = _causal_conv_seq(p, u, state["conv"])
    dt, Bm, Cm = _features(cfg, p, u)
    A_T = -jnp.exp(p["A_log"].astype(jnp.float32)).T                # (N,inner)
    if scan_kernel_engaged(cfg):
        y, h_last = _kernel_scan(dt, u, Bm, Cm, A_T, state["h"],
                                 jax.default_backend() != "tpu")
    else:
        y, h_last = _xla_scan(dt, u, Bm, Cm, A_T, state["h"])
    y = y + u * p["D"].astype(x.dtype)[None, None]
    y = y * jax.nn.silu(z)
    out = y @ use_weight(p["w_out"].astype(x.dtype), ("model", None))
    return out, {"h": h_last, "conv": conv_state.astype(jnp.float32)}


def apply_mamba_step(cfg, p, x, state) -> Tuple[jnp.ndarray, dict]:
    """x: (B, 1, d) decode — O(1) update."""
    B, _, d = x.shape
    s = cfg.ssm
    inner = s.expand * d
    uz = x[:, 0] @ use_weight(p["w_in"].astype(x.dtype), (None, "model"))
    u_raw, z = uz[..., :inner], uz[..., inner:]
    window = jnp.concatenate([state["conv"].astype(x.dtype), u_raw[:, None, :]], axis=1)
    u = _conv_out(p, jnp.einsum("bki,ki->bi", window,
                                p["conv_w"].astype(x.dtype)))
    dt, Bm, Cm = _features(cfg, p, u)
    A_T = -jnp.exp(p["A_log"].astype(jnp.float32)).T
    dt32 = dt.astype(jnp.float32)
    h = jnp.exp(dt32[:, None, :] * A_T) * state["h"] \
        + (dt32 * u.astype(jnp.float32))[:, None, :] \
        * Bm.astype(jnp.float32)[:, :, None]
    y = jnp.einsum("bni,bn->bi", h, Cm.astype(jnp.float32)).astype(x.dtype)
    y = y + u * p["D"].astype(x.dtype)[None]
    y = y * jax.nn.silu(z)
    out = (y @ use_weight(p["w_out"].astype(x.dtype), ("model", None)))[:, None, :]
    return out, {"h": h, "conv": window[:, 1:, :].astype(jnp.float32)}
