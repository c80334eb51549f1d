"""Closed-loop offline batch inference: each run is one batch job, a fixed
set of prompts pushed through the serving engine, and ends when every
answer is on the host; the next run starts when the last one returned.

Traffic parameters (``traffic/<mix>.json``): ``requests`` per job;
``prompt_lens`` and ``new_tokens``, each a list of ``[value, share]`` (the
shares of a job's requests, exact); the engine's ``batch_size``; ``jobs``
(distinct jobs made from the seed, used in turn); ``warmup_runs``;
``check_requests`` (finished requests compared with the reference, the
longest among them); ``trace_s`` (how long the traced part of a
``--trace 1`` window lasts, in whole runs).

A run submits the job's requests, issues them with ``flush(force=True)``
(one engine batch per ``batch_size`` requests of one shape, each its own
element on its own lane), reads every batch's output with ``collect()``
and collects garbage, as the DAG loop does.  ``dag_ms`` is the window over
the runs completed in it.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import List

import numpy as np

from ..trace import span
from . import serve as S


def program_config(cfg: dict):
    """The program's own configuration object, with the sizes of the file."""
    from repro.configs import get_config
    s = cfg["sizes"]
    base = get_config(cfg["arch"])
    return dataclasses.replace(
        base, n_layers=s["n_layers"], d_model=s["d_model"],
        n_heads=s["n_heads"], n_kv_heads=s["n_kv_heads"],
        head_dim=s["head_dim"], d_ff=s["d_ff"], vocab=s["vocab"],
        attn_period=s["attn_period"], attn_offset=s["attn_offset"],
        ssm=dataclasses.replace(base.ssm, state_dim=s["ssm_state"],
                                expand=s["ssm_expand"],
                                conv_dim=s["ssm_conv"],
                                dt_rank=s["dt_rank"]))


def make_job(tr: dict, rng, vocab: int) -> List[S.Arrival]:
    n = tr["requests"]
    lens = S._exact_shares(tr["prompt_lens"], n, rng)
    news = S._exact_shares(tr["new_tokens"], n, rng)
    return [S.Arrival(0.0, rng.integers(0, vocab, int(lens[i]),
                                        dtype=np.int32), int(news[i]))
            for i in range(n)]


def one_run(run, job: List[S.Arrival]) -> List[S.Arrival]:
    """Push one job through the engine; its requests with their answers."""
    eng = run.engine
    reqs = [eng.submit(a.tokens, a.new) for a in job]
    eng.flush(force=True)
    eng.collect()
    return [dataclasses.replace(a, result=r.result) for a, r in zip(job, reqs)]


# ----------------------------------------------------------------------
def setup(run) -> None:
    from repro.runtime.serving import ServingEngine

    tr, s = run.traffic, run.config["sizes"]
    run.arch = program_config(run.config)
    params = run.reference.make_params(run.seed, s)
    S._check_layout(run.arch, params)
    run.engine = ServingEngine(
        run.arch, params, batch_size=tr["batch_size"],
        max_new_tokens=max(n for n, _ in tr["new_tokens"]))
    del params
    rng = np.random.default_rng([run.seed, 7])
    run.jobs = [make_job(tr, rng, s["vocab"]) for _ in range(tr["jobs"])]
    for i in range(tr["warmup_runs"]):
        one_run(run, run.jobs[i % len(run.jobs)])
        gc.collect()


def window(run) -> None:
    tr = run.traffic
    if run.traced:
        run.start_trace()
        calib = np.random.default_rng([run.seed, 5])
        for plen, new in S.shapes(tr):
            with span(f"calibrate:{plen}:{new}"):
                S.serve_batch(run, calib, plen, new)
        win = span("window")
        win.__enter__()
    tracing = run.traced
    run.done = []
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < run.seconds:
        job = run.jobs[n % len(run.jobs)]
        run.attempted += len(job)
        try:
            with span("run"):
                run.done += one_run(run, job)
        except Exception as e:  # a run that raises fails all its requests
            run.failed += len(job)
            run.notes["error"] = repr(e)[:500]
            break
        with span("gc"):
            gc.collect()
        n += 1
        if tracing and time.perf_counter() - t0 >= tr["trace_s"]:
            win.__exit__(None, None, None)
            run.stop_trace()
            run.tally["traced_runs"] = n
            tracing = False
    elapsed = time.perf_counter() - t0
    if tracing:
        win.__exit__(None, None, None)
        run.stop_trace()
        run.tally["traced_runs"] = n
    if run.traced:
        run.tally["fingerprints"] = S.fingerprints(run)
    if n:
        run.e2e["dag_ms"] = 1e3 * elapsed / n
    run.notes["runs"] = n
    run.notes["window_s"] = elapsed


def check(run) -> None:
    """The widest gap by which a served token's logit lies below the
    reference's best, over a sample of the window's requests drawn from
    the seed, the longest among them."""
    for (plen, new), st in sorted(run.engine.stats()["serving"].items()):
        for k, v in st.items():
            run.notes[f"{k}.{plen}x{new}"] = v
    try:
        run.engine.close()
    except Exception as e:
        run.failed = max(run.failed, 1)
        run.notes.setdefault("error", repr(e)[:500])
    del run.engine
    gc.collect()
    done = [a for a in run.done if a.result is not None]
    run.failed = max(run.failed, len(run.done) - len(done))
    if not done:
        return
    limit = run.config["limits"]["serve_gap"]
    run.sample = S.pick_sample(done, run.traffic["check_requests"], run.seed)
    run.checks["serve_gap"] = (S.widest_gap(run, run.sample), limit)
