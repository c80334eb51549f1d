"""Production serving launcher: prefill + batched greedy decode on a
sharded mesh (bf16 weights, sharded KV cache).

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3_12b --reduced \
        --batch 2 --prompt-len 32 --new-tokens 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.launch.mesh import make_mesh_for
from repro.models import init_cache, init_lm
from repro.runtime.steps import make_decode_step, make_prefill_step
from repro.sharding.context import sharding_rules
from repro.sharding.rules import cache_sharding, param_sharding


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3_12b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--model-parallel", type=int, default=2)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    mesh = make_mesh_for(len(jax.devices()), args.model_parallel)
    params = init_lm(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    params = jax.device_put(params, param_sharding(params, mesh))
    max_len = args.prompt_len + args.new_tokens
    cross = args.prompt_len // 4 if cfg.n_encoder_layers else 0
    cache = init_cache(cfg, args.batch, max_len, cross_len=cross)
    cache = jax.device_put(cache, cache_sharding(cache, mesh))

    def wrap(fn):
        def inner(*a):
            with sharding_rules(mesh):
                return fn(*a)
        return inner

    rng = np.random.RandomState(0)
    batch = {"tokens": rng.randint(
        0, cfg.vocab, (args.batch, args.prompt_len)).astype(np.int32)}
    if cfg.n_encoder_layers:
        batch["frames"] = rng.randn(args.batch, cross,
                                    cfg.d_model).astype(np.float32)
    if cfg.frontend == "vision":
        batch["patches"] = rng.randn(args.batch, cfg.n_frontend_tokens,
                                     cfg.d_model).astype(np.float32) * 0.02

    with mesh:
        prefill = jax.jit(wrap(make_prefill_step(cfg)), donate_argnums=(2,))
        decode = jax.jit(wrap(make_decode_step(cfg)), donate_argnums=(2,))
        t0 = time.time()
        logits, cache = prefill(params, batch, cache)
        nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        generated = [np.asarray(nxt)]
        for i in range(args.new_tokens - 1):
            nxt, _, cache = decode(params, nxt, cache,
                                   jnp.int32(args.prompt_len + i))
            generated.append(np.asarray(nxt))
        dt = time.time() - t0
    toks = args.batch * args.new_tokens
    gen = np.concatenate(generated, axis=1)
    print(f"mesh {dict(mesh.shape)} | generated {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s)")
    print("sample:", gen[0][:12])


if __name__ == "__main__":
    main()
