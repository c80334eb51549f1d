"""Serving driver: batched autoregressive decoding with prefill + KV cache,
with *space-sharing* across concurrent request batches via the GrJAX
scheduler (independent batches land on separate lanes — the paper's
multi-task overlap applied to inference).

    PYTHONPATH=src python examples/serve_lm.py --requests 4 --new-tokens 16

Without ``--full`` it serves the small float32 test version of the config;
``--full`` serves the registered config at its published widths with
bfloat16 weights (e.g. ``--arch hymba_1_5b --full`` on one TPU v5e).
"""
import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp

import repro.api as gr
from repro.configs import get_config
from repro.core.managed import ManagedValue
from repro.models import init_cache, init_lm
from repro.runtime import make_decode_step, make_prefill_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3_12b")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--full", action="store_true",
                    help="published widths with bfloat16 weights")
    args = ap.parse_args()

    cfg = get_config(args.arch, reduced=not args.full)
    params = init_lm(jax.random.PRNGKey(0), cfg,
                     dtype=jnp.bfloat16 if args.full else jnp.float32)
    prefill = jax.jit(make_prefill_step(cfg))
    decode = jax.jit(make_decode_step(cfg))

    sched = gr.make_scheduler("parallel")
    params_v = ManagedValue(sched, params, name="weights")
    rng = np.random.RandomState(0)
    max_len = args.prompt_len + args.new_tokens

    def kernel(p, toks, _out):
        """One request batch: prefill then greedy decode (device kernel)."""
        cache = init_cache(cfg, toks.shape[0], max_len)
        logits, cache = prefill(p, {"tokens": toks}, cache)
        nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        outs = [nxt]
        pos = toks.shape[1]
        for i in range(args.new_tokens - 1):
            nxt, _, cache = decode(p, nxt, cache, jnp.int32(pos + i))
            outs.append(nxt)
        return jnp.concatenate(outs, axis=1)

    # Declared once: const weights, const prompts, out generated tokens.
    serve = gr.function(kernel, modes=("const", "const", "out"),
                        name="serve", scheduler=sched)

    t0 = time.time()
    results = []
    for r in range(args.requests):
        toks = sched.array(
            rng.randint(0, cfg.vocab,
                        (args.batch, args.prompt_len)).astype(np.int32),
            name=f"req{r}")
        out_toks = sched.array(
            np.zeros((args.batch, args.new_tokens), np.int32),
            name=f"gen{r}")
        # independent requests share read-only weights -> separate lanes
        serve.with_options(name=f"serve_req{r}")(params_v, toks, out_toks)
        results.append(out_toks)

    texts = [np.asarray(r) for r in results]     # host reads sync per-lane
    dt = time.time() - t0
    total = args.requests * args.batch * args.new_tokens
    print(f"served {args.requests} request batches "
          f"({total} tokens) in {dt:.2f}s -> {total/dt:.1f} tok/s")
    print("lanes used:", sched.streams.lanes_created,
          "| events:", sched.streams.events_created)
    for r, t in enumerate(texts[:2]):
        print(f"req{r} sample tokens:", t[0][:8], "...")
    sched.shutdown()


if __name__ == "__main__":
    main()
