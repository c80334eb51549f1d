"""Production training launcher.

Builds the device mesh (all local devices, or the production 16x16 /
2x16x16 meshes on a real pod), shards the train state per sharding/rules,
and drives the step loop with fault-tolerant checkpointing and exact
resume.  On this CPU container use ``--reduced`` (the full configs only
lower via dryrun.py).

    PYTHONPATH=src python -m repro.launch.train --arch qwen3_32b --reduced \
        --steps 20 --ckpt /tmp/ckpt
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.checkpoint import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.data import SyntheticTokenStream
from repro.launch.mesh import make_mesh_for, make_production_mesh
from repro.models import init_lm
from repro.optim import AdamW
from repro.runtime.steps import TrainState, make_train_step
from repro.sharding.context import sharding_rules
from repro.sharding.rules import batch_spec, param_sharding
from jax.sharding import NamedSharding, PartitionSpec as P


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_32b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--production-mesh", action="store_true",
                    help="16x16 (or 2x16x16 with --multi-pod) mesh")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--model-parallel", type=int, default=2)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    if args.production_mesh:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    else:
        mesh = make_mesh_for(len(jax.devices()), args.model_parallel)
    print(f"mesh: {dict(mesh.shape)} | params(full-cfg) "
          f"{cfg.param_count()/1e6:.1f}M")

    optimizer = AdamW(lr=3e-4, warmup=20, total_steps=max(100, args.steps))
    params = init_lm(jax.random.PRNGKey(0), cfg)
    state = TrainState(params, optimizer.init(params))
    p_sh = param_sharding(params, mesh)
    state = TrainState(jax.device_put(params, p_sh), state.opt)

    stream = SyntheticTokenStream(cfg, args.seq, args.global_batch,
                                  accum=args.accum)
    bspec = batch_spec(mesh)
    sample = stream.batch(0)
    b_sh = {k: NamedSharding(mesh, P(*((None,) + tuple(bspec.get(
        k, P(None, None))))))
            for k in sample}

    def wrapped(st, batch):
        with sharding_rules(mesh):
            return make_train_step(cfg, optimizer)(st, batch)

    with mesh:
        step_fn = jax.jit(wrapped, in_shardings=(None, b_sh),
                          donate_argnums=(0,))

        ckpt = CheckpointManager(args.ckpt) if args.ckpt else None
        start = 0
        if ckpt:
            # (step, state) resolved atomically: resuming the loop from a
            # different step than the restored state breaks exact resume.
            ck_step, ck_state, extra = ckpt.restore_latest(like=state)
            if ck_step is not None:
                saved_seed = extra.get("stream_seed")
                if saved_seed is not None and saved_seed != stream.seed:
                    raise ValueError(
                        f"checkpoint was trained with stream seed "
                        f"{saved_seed}, this run has {stream.seed}: "
                        f"resume would not be exact")
                start, state = ck_step, ck_state
                print(f"resumed from step {start}")

        t0 = time.time()
        for step in range(start, args.steps):
            batch = {k: jax.device_put(v, b_sh[k])
                     for k, v in stream.batch(step).items()}
            state, metrics = step_fn(state, batch)
            if (step + 1) % 5 == 0 or step == args.steps - 1:
                print(f"step {step+1}: loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"lr={float(metrics['lr']):.2e}")
            if ckpt and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, state,
                          extra={"stream_seed": stream.seed})
        if ckpt:
            ckpt.wait()
        dt = time.time() - t0
        toks = (args.steps - start) * args.global_batch * args.seq
        print(f"done: {dt:.1f}s, {toks/dt:.0f} tok/s")


if __name__ == "__main__":
    main()
