"""Jittable train / prefill / decode steps used by the launcher, the
dry-run, and the examples.

``make_train_step`` builds the full production step: gradient-accumulation
scan over microbatches (bounds activation memory for the 340B config),
per-layer remat, AdamW (optionally 8-bit state), MoE aux losses.  Donated
buffers and shardings are applied by the caller (launch/dryrun.py).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..models import (cross_entropy_loss, forward_decode, forward_prefill,
                      forward_train)
from ..models.config import ArchConfig
from ..optim import AdamW, AdamWState
from ..sharding.context import constrain_like_params


class TrainState(NamedTuple):
    params: Any
    opt: AdamWState


def make_loss_fn(cfg: ArchConfig, remat: bool = True,
                 seq_shard: bool = False):
    def loss_fn(params, micro_batch):
        logits, aux = forward_train(cfg, params, micro_batch, remat=remat,
                                    seq_shard=seq_shard)
        loss = cross_entropy_loss(logits, micro_batch["labels"])
        total = loss + aux["moe_aux"] + aux["moe_z"]
        return total, {"ce_loss": loss, **aux}
    return loss_fn


def make_train_step(cfg: ArchConfig, optimizer: AdamW, *,
                    remat: bool = True, seq_shard: bool = False):
    """Returns train_step(state, batch) -> (state, metrics).

    ``batch`` leaves are shaped (accum, micro_batch, ...): the step scans
    over the leading accumulation axis, accumulating f32 gradients, then
    applies one optimizer update.
    """
    loss_fn = make_loss_fn(cfg, remat, seq_shard)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        accum = jax.tree_util.tree_leaves(batch)[0].shape[0]

        def micro(carry, mb):
            gacc, lacc = carry
            (_, metrics), grads = grad_fn(state.params, mb)
            # keep per-micro grads + the accumulator in FSDP storage
            # sharding: DP sync becomes a reduce-scatter, not an all-reduce
            grads = constrain_like_params(grads)
            gacc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), gacc, grads)
            gacc = constrain_like_params(gacc)
            return (gacc, lacc + metrics["ce_loss"]), None

        zeros = constrain_like_params(jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state.params))
        (grads, loss_sum), _ = jax.lax.scan(micro, (zeros, jnp.float32(0)),
                                            batch)
        grads = jax.tree_util.tree_map(lambda g: g / accum, grads)
        params, opt, metrics = optimizer.update(grads, state.opt, state.params)
        metrics["loss"] = loss_sum / accum
        return TrainState(params, opt), metrics

    return train_step


def make_prefill_step(cfg: ArchConfig):
    def prefill_step(params, batch, cache):
        return forward_prefill(cfg, params, batch, cache)
    return prefill_step


def make_decode_step(cfg: ArchConfig):
    def serve_step(params, tokens, cache, pos):
        """One new token for the whole batch against the KV/state cache."""
        logits, cache = forward_decode(cfg, params, tokens, cache, pos)
        next_tokens = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        return next_tokens, logits, cache
    return serve_step
