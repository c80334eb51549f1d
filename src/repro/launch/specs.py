"""ShapeDtypeStruct stand-ins + shardings for every (arch x shape) cell.

Nothing here allocates: model/optimizer/cache shapes come from
``jax.eval_shape`` over the real init functions, so the dry-run lowers the
exact production step against the exact production state.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..data.pipeline import batch_specs
from ..models import init_cache, init_lm
from ..models.config import ArchConfig, ShapeCell
from ..optim import AdamW, Q8State
from ..runtime.steps import TrainState, make_decode_step, make_prefill_step, \
    make_train_step
from ..sharding.context import sharding_rules
from ..sharding.rules import batch_spec, cache_sharding, dp_axes, fit_spec, \
    param_sharding


def _with_rules(fn, mesh):
    """Activate use-site sharding constraints during tracing."""
    def wrapped(*args):
        with sharding_rules(mesh):
            return fn(*args)
    return wrapped


MICRO_TOKENS_BUDGET = 1 << 16     # ~64k tokens per microbatch (grad accum)


def plan_accum(cell: ShapeCell, mesh) -> Tuple[int, int]:
    """(accum, micro_batch): micro divisible by dp, tokens/micro bounded."""
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.shape:
            dp *= mesh.shape[a]
    micro = min(cell.global_batch, max(dp, MICRO_TOKENS_BUDGET // cell.seq_len))
    micro -= micro % dp
    micro = max(micro, min(dp, cell.global_batch))
    while cell.global_batch % micro:
        micro -= dp
    accum = cell.global_batch // micro
    return accum, micro


def eval_shapes(fn, *args, **kw):
    return jax.eval_shape(fn, *args, **kw)


# ----------------------------------------------------------------------
def state_shapes(cfg: ArchConfig, optimizer: AdamW) -> TrainState:
    def init():
        params = init_lm(jax.random.PRNGKey(0), cfg)
        return TrainState(params, optimizer.init(params))
    return jax.eval_shape(init)


def opt_leaf_sharding(mesh, param_shard):
    """m/v moments mirror the parameter sharding; Q8 blocks keep the
    parameter's leading-axis sharding and leave the (nb, BLOCK) trailing
    axes unsharded."""

    def _axis_len(ax):
        if ax is None:
            return 1
        axes = ax if isinstance(ax, tuple) else (ax,)
        n = 1
        for a in axes:
            n *= mesh.shape.get(a, 1)
        return n

    def one(leaf, ps):
        if isinstance(leaf, Q8State):
            base = tuple(ps.spec) if ps is not None else ()
            lead = base[:-1] if base else ()
            last = base[-1] if base else None
            nb = leaf.codes.shape[-2]
            # ladder: full dp tuple -> each sub-axis -> unsharded, so a
            # non-dividing nb (e.g. 144 vs pod*data=32) still gets the
            # largest usable ZeRO degree instead of replication
            if isinstance(last, tuple):
                subs = sorted(last, key=_axis_len, reverse=True)
                candidates = [last] + subs
            else:
                candidates = [last]
            pick = None
            for c in candidates:
                if c is not None and nb % _axis_len(c) == 0:
                    pick = c
                    break
            codes = NamedSharding(mesh, fit_spec(lead + (pick, None),
                                                 leaf.codes.shape, mesh))
            scales = NamedSharding(mesh, fit_spec(lead + (pick,),
                                                  leaf.scales.shape, mesh))
            return Q8State(codes, scales)
        return ps

    return one


def train_state_sharding(mesh, cfg: ArchConfig, st_shapes: TrainState):
    ps = param_sharding(st_shapes.params, mesh)
    one = opt_leaf_sharding(mesh, None)
    is_q8 = lambda x: isinstance(x, Q8State)
    m_sh = jax.tree_util.tree_map(lambda leaf, p: one(leaf, p),
                                  st_shapes.opt.m, ps, is_leaf=is_q8)
    v_sh = jax.tree_util.tree_map(lambda leaf, p: one(leaf, p),
                                  st_shapes.opt.v, ps, is_leaf=is_q8)
    step_sh = NamedSharding(mesh, P())
    OptState = type(st_shapes.opt)
    return TrainState(ps, OptState(step=step_sh, m=m_sh, v=v_sh))


def replicated(mesh, tree):
    return jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), tree)


# ----------------------------------------------------------------------
class CellLowering(NamedTuple):
    fn: Any                        # the jittable step function
    arg_specs: tuple               # ShapeDtypeStructs
    in_shardings: tuple
    out_shardings: Any
    donate_argnums: tuple
    meta: dict


def make_optimizer(cfg: ArchConfig) -> AdamW:
    # 8-bit optimizer state for the very large dense configs:
    # fp32 moments for <=200B params fit the pod; 340B needs int8 moments.
    quantized = cfg.param_count() > 200e9
    return AdamW(lr=3e-4, quantized=quantized)


def build_cell(cfg: ArchConfig, cell: ShapeCell, mesh, *,
               remat: bool = True,
               seq_shard: Optional[bool] = None) -> CellLowering:
    """Lowerable artifact for one (arch x shape x mesh) cell."""
    optimizer = make_optimizer(cfg)
    dp = dp_axes(mesh)
    if seq_shard is None:
        # Megatron-SP by default for training — except recurrent families,
        # whose token-shift ops slice the sequence dim every layer
        seq_shard = (cell.kind == "train"
                     and cfg.family not in ("ssm", "hybrid"))

    if cell.kind == "train":
        accum, micro = plan_accum(cell, mesh)
        st = state_shapes(cfg, optimizer)
        st_sh = train_state_sharding(mesh, cfg, st)
        b_specs = batch_specs(cfg, cell.seq_len, micro * accum, accum)
        bs = batch_spec(mesh, seq_shard=False)
        b_sh = {k: NamedSharding(mesh,
                                 P(*((None,) + tuple(bs[k]))))
                for k in b_specs}
        step = _with_rules(make_train_step(cfg, optimizer,
                                           remat=remat,
                                           seq_shard=seq_shard), mesh)
        metrics_shapes = jax.eval_shape(step, st, b_specs)[1]
        out_sh = (st_sh, replicated(mesh, metrics_shapes))
        return CellLowering(step, (st, b_specs), (st_sh, b_sh), out_sh,
                            donate_argnums=(0,),
                            meta={"accum": accum, "micro": micro,
                                  "quantized_opt": optimizer.quantized})

    # inference cells: bf16 weights (serving precision) -------------------
    import jax.numpy as jnp
    params = jax.eval_shape(
        lambda: init_lm(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    p_sh = param_sharding(params, mesh)
    B, S = cell.global_batch, cell.seq_len
    cross = S // 4 if cfg.n_encoder_layers else 0
    cache = jax.eval_shape(lambda: init_cache(cfg, B, S, cross_len=cross))
    c_sh = cache_sharding(cache, mesh)

    if cell.kind == "prefill":
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), np.int32)}
        b_sh = {"tokens": NamedSharding(mesh, P(dp, "model" if seq_shard
                                                else None))}
        if cfg.n_encoder_layers:
            batch["frames"] = jax.ShapeDtypeStruct((B, cross, cfg.d_model),
                                                   np.float32)
            b_sh["frames"] = NamedSharding(mesh, P(dp, None, None))
        if cfg.frontend == "vision":
            batch["patches"] = jax.ShapeDtypeStruct(
                (B, cfg.n_frontend_tokens, cfg.d_model), np.float32)
            b_sh["patches"] = NamedSharding(mesh, P(dp, None, None))
        step = _with_rules(make_prefill_step(cfg), mesh)
        logits_sh = NamedSharding(mesh, fit_spec((dp, "model"),
                                                 (B, cfg.vocab), mesh))
        return CellLowering(step, (params, batch, cache),
                            (p_sh, b_sh, c_sh), (logits_sh, c_sh),
                            donate_argnums=(2,), meta={})

    # decode -------------------------------------------------------------
    tokens = jax.ShapeDtypeStruct((B, 1), np.int32)
    pos = jax.ShapeDtypeStruct((), np.int32)
    t_sh = NamedSharding(mesh, fit_spec((dp, None), (B, 1), mesh))
    pos_sh = NamedSharding(mesh, P())
    step = _with_rules(make_decode_step(cfg), mesh)
    logits_sh = NamedSharding(mesh, fit_spec((dp, "model"),
                                             (B, cfg.vocab), mesh))
    return CellLowering(step, (params, tokens, cache, pos),
                        (p_sh, t_sh, c_sh, pos_sh),
                        (t_sh, logits_sh, c_sh),
                        donate_argnums=(2,), meta={})
