"""Use-site logical sharding constraints (ZeRO-3 materialization policy).

Parameters are *stored* FSDP-sharded (fp32 masters spread over the data
axes — see rules.py).  If a matmul consumed them directly, GSPMD would see a
contracted dimension sharded over ``data`` and often lowers that to huge
fp32 partial-sum all-reduces of activations.  Instead, every layer wraps its
weights in ``use_weight(w, *candidate_specs)``: the bf16 copy is constrained
to a TP-only layout, so GSPMD materializes a **bf16 all-gather of the
weight** (half the wire bytes of fp32) right before use and a reduce-scatter
of the gradient in the backward — textbook ZeRO-3 with mixed-precision
gathers.

Outside a mesh context (CPU tests, single device) everything is a no-op.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

_STATE = threading.local()


def _mesh_axes():
    return getattr(_STATE, "axes", None)


def _mesh():
    return getattr(_STATE, "mesh", None)


def mesh_active() -> bool:
    """Whether use-site constraints are active: inside ``sharding_rules``."""
    return _mesh_axes() is not None


def _named(spec: P):
    return NamedSharding(_mesh(), spec)


def _dp_axes():
    axes = _mesh_axes()
    if not axes:
        return None
    dp = tuple(a for a in ("pod", "data") if a in axes)
    return dp if len(dp) > 1 else (dp[0] if dp else None)


@contextmanager
def sharding_rules(mesh):
    """Activate use-site constraints for lowering under ``mesh``."""
    _STATE.axes = dict(mesh.shape)
    _STATE.mesh = mesh
    try:
        yield
    finally:
        _STATE.axes = None
        _STATE.mesh = None


def _fit(spec: Sequence, shape: Tuple[int, ...]) -> Optional[P]:
    axes = _mesh_axes()
    out = []
    ok = False
    for dim, ax in zip(shape, spec):
        if ax is None:
            out.append(None)
            continue
        size = axes.get(ax, 0) if not isinstance(ax, tuple) else 0
        if isinstance(ax, tuple):
            size = 1
            for a in ax:
                size *= axes.get(a, 0)
        if size and size > 0 and dim % size == 0:
            out.append(ax)
            ok = True
        else:
            out.append(None)
    return P(*out) if ok else P(*([None] * len(shape)))


def use_weight(w, *candidate_specs):
    """Constrain a weight (already cast to compute dtype) to the first
    candidate TP layout that divides evenly; no-op outside a mesh context.

    (Refuted experiment note, kept for the §Perf log: pre-pinning the bf16
    copy to a storage-like layout did NOT stop GSPMD from gathering f32 —
    the fix that works is the bf16 working copy cast once per step in
    make_train_step.)"""
    if _mesh_axes() is None or not candidate_specs:
        return w
    for spec in candidate_specs:
        p = _fit(spec, w.shape)
        if any(a is not None for a in p):
            return jax.lax.with_sharding_constraint(w, _named(p))
    return jax.lax.with_sharding_constraint(
        w, _named(P(*([None] * w.ndim))))


def shard_activations(x, *, seq_axis=None):
    """Constrain token activations (B, S, d) to batch-over-DP."""
    if _mesh_axes() is None:
        return x
    dp = _dp_axes()
    if dp is None:
        return x
    spec = [dp] + [None] * (x.ndim - 1)
    if seq_axis is not None and x.ndim >= 2:
        spec[1] = seq_axis
    return jax.lax.with_sharding_constraint(x, _named(_fit(spec, x.shape)))


def shard_heads(x):
    """Constrain (B, S, H, hd) attention tensors: batch over DP, heads over
    "model" when divisible, and — critically — head_dim explicitly
    REPLICATED.  Without this GSPMD may shard the contracted hd dim (e.g.
    propagating through hymba's 25-head reshape), turning every blocked
    score matmul into a partial-sum all-reduce (~6 TiB/step at 32k)."""
    if _mesh_axes() is None or x.ndim != 4:
        return x
    dp = _dp_axes()
    tp = _mesh_axes().get("model", 1)
    head_ax = "model" if (tp > 1 and x.shape[2] % tp == 0) else None
    spec = P(dp, None, head_ax, None)
    return jax.lax.with_sharding_constraint(x, _named(spec))


def pin_attention_blocks(qg, kb, vb):
    """Pin the blocked-attention scan inputs: (nq|nk, B, chunk, Hkv[, g],
    hd) — batch over DP, kv-heads over "model" when divisible, and hd/chunk
    dims REPLICATED so the score matmul never contracts a sharded dim."""
    if _mesh_axes() is None:
        return qg, kb, vb
    dp = _dp_axes()
    tp = _mesh_axes().get("model", 1)
    hkv = kb.shape[3]
    h_ax = "model" if (tp > 1 and hkv % tp == 0) else None
    qspec = P(None, dp, None, h_ax, None, None)
    kspec = P(None, dp, None, h_ax, None)
    qg = jax.lax.with_sharding_constraint(qg, _named(qspec))
    kb = jax.lax.with_sharding_constraint(kb, _named(kspec))
    vb = jax.lax.with_sharding_constraint(vb, _named(kspec))
    return qg, kb, vb


def constrain_like_params(tree):
    """Constrain a parameter-shaped tree (e.g. gradients / accumulators) to
    the FSDP *storage* sharding — turns data-parallel gradient all-reduces
    into reduce-scatters and keeps the fp32 accumulator sharded."""
    mesh = _mesh()
    if mesh is None:
        return tree
    from .rules import param_sharding
    shardings = param_sharding(tree, mesh)
    return jax.tree_util.tree_map(
        lambda x, sh: jax.lax.with_sharding_constraint(x, sh),
        tree, shardings)
