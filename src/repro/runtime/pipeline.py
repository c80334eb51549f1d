"""Pipeline parallelism over the ``pod`` axis (GPipe-style).

The multi-pod mesh's slowest links are the inter-pod ones; instead of pure
DP over ``pod`` (per-step gradient reduce-scatter across pods), the layer
stack can be split into one *stage per pod* and microbatches streamed
through with ``ppermute`` handoffs — inter-pod traffic becomes one
activation tensor per microbatch instead of the full gradient set.

Implementation: ``shard_map`` over the pipeline axis; every rank runs the
same program on its own stage parameters (stacked with a leading
``n_stages`` axis sharded over the pipeline axis).  The classic GPipe
schedule is expressed as a ``lax.scan`` over ``n_micro + n_stages - 1``
ticks: each tick computes the local stage on the activation received last
tick and ppermutes the result to the next rank.  Bubble fraction =
(S-1)/(T+S-1), recovered in §Perf napkin math.

Used by tests/test_pipeline.py (fake 8-device mesh) and exposed as a
building block; the 40-cell dry-run keeps DP over ``pod`` as its default
(better for the assigned global-batch shapes), with PP available via this
module for deeper-than-HBM models.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, mesh: Mesh, axis: str = "pod"):
    """Build a pipelined forward: ``f(stage_params, x_micro) -> y_micro``.

    * ``stage_params``: pytree whose leaves have a leading ``n_stages`` axis,
      sharded over ``axis`` (one stage per rank group).
    * ``x_micro``: (n_micro, micro_batch, ...) — replicated along ``axis``.
    * ``stage_fn(params_stage, x) -> x`` applies one stage.

    Returns outputs (n_micro, micro_batch, ...) valid on the LAST stage
    (other ranks return garbage of the right shape; callers psum-select).
    """
    n_stages = mesh.shape[axis]

    def ranked(params, xs):
        rank = jax.lax.axis_index(axis)
        params = jax.tree_util.tree_map(lambda a: a[0], params)  # local stage
        n_micro = xs.shape[0]
        ticks = n_micro + n_stages - 1
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages - 1)]

        def tick(carry, t):
            inflight, outputs = carry
            # which microbatch enters the pipe this tick (stage 0 only)
            enter = jnp.where(t < n_micro, t, 0)
            x_in = jnp.where(rank == 0, xs[enter], inflight)
            y = stage_fn(params, x_in)
            # hand off to the next stage
            handed = jax.lax.ppermute(y, axis, fwd) if n_stages > 1 else y
            # last stage commits an output for microbatch t-(S-1)
            out_idx = t - (n_stages - 1)
            commit = (rank == n_stages - 1) & (out_idx >= 0)
            outputs = jax.lax.cond(
                commit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(out_idx, 0), 0),
                lambda o: o, outputs)
            return (handed, outputs), None

        inflight0 = jnp.zeros_like(xs[0])
        outputs0 = jnp.zeros_like(xs)
        (_, outputs), _ = jax.lax.scan(tick, (inflight0, outputs0),
                                       jnp.arange(ticks))
        # broadcast the last stage's outputs to every rank
        outputs = jax.lax.psum(
            jnp.where(rank == n_stages - 1, outputs, 0.0), axis)
        return outputs

    # P(axis) acts as a prefix spec for the whole parameter pytree: every
    # leaf is sharded on its leading (stage) dim; activations replicated.
    return jax.shard_map(ranked, mesh=mesh, in_specs=(P(axis), P()),
                         out_specs=P(), check_vma=False)


def pipeline_loss_fn(stage_fn: Callable, loss_tail: Callable, mesh: Mesh,
                     axis: str = "pod"):
    """Differentiable pipelined loss: mean over microbatches of
    ``loss_tail(last_stage_output, labels)``.  jax.grad flows through the
    ppermute schedule (GPipe's recompute-free backward)."""
    fwd = pipeline_apply(stage_fn, mesh, axis)

    def loss(stage_params, xs, ys):
        outs = fwd(stage_params, xs)
        return loss_tail(outs, ys)

    return loss
