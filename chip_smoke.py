#!/usr/bin/env python3
"""Smoke run of the scheduler's real execution path on a TPU.

    python chip_smoke.py             # one chip: phases A, B and C
    python chip_smoke.py --chips 4   # four chips: the multi-device phase only

Phase A runs the paper's six multi-task programs (VEC, B&S, ML, HITS, DL,
IMG) at their published input sizes through ``ThreadLaneExecutor`` under the
serial and the parallel policy, and checks each against the program's
``run_reference`` and parallel against serial.  Phase B serves Hymba-1.5B at
its published widths (bfloat16 weights from a seed) through a
``ServingEngine`` and through a ``serve_lm`` job of an in-process daemon,
and checks both bit for bit against a plain loop of the same jitted steps.
Phase C runs the four Pallas kernels once each at model widths against
their references.  With ``--chips 4``, B&S and IMG run on four devices under
the affinity and round-robin placements, against the reference and against
one device.

Every phase prints one verdict line with its host wall time, labelled a
smoke timing: it includes compilation and is not a benchmark number.  Any
failure exits non-zero before the last line, which is exactly
``{"ok": true, "device": {...}}``.  There is no CPU fallback: without a TPU
the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

# Tolerances of tests/test_benchsuite.py: against the reference, and
# parallel against serial.
REF_TOL = dict(rtol=2e-3, atol=1e-4)
POLICY_TOL = dict(rtol=1e-5, atol=1e-6)
PROGRAMS = ("VEC", "B&S", "ML", "HITS", "DL", "IMG")


class SmokeFailure(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def smoke_time(t0):
    return (f"smoke timing {time.perf_counter() - t0:.1f} s host clock, "
            f"compile included, not a benchmark number")


def assert_close(got, ref, what, tol):
    import numpy as np
    try:
        np.testing.assert_allclose(got, ref, err_msg=what, **tol)
    except AssertionError as e:
        raise SmokeFailure(str(e)) from None


# ----------------------------------------------------------------------
# Where a program's arrays were computed
# ----------------------------------------------------------------------

def run_program(bench, data, policy, platform, **sched_kw):
    """Run one program through a real scheduler; return its outputs, the
    devices its outputs were computed on, and the scheduler's counters.

    Every ManagedArray the program creates is kept until the run is over,
    so that its device value can be inspected: an output (an array created
    without data) must have been computed as a ``jax.Array`` on a device of
    ``platform``, and so must every other array that reached a device."""
    import jax
    from repro.core import make_scheduler

    sched = make_scheduler(policy, **sched_kw)
    made = []
    new_array = sched.array

    def array(data=None, **kw):
        ma = new_array(data, **kw)
        made.append((ma, data is None))
        return ma

    sched.array = array
    try:
        got = bench.build(sched, data, iters=2)
        sched.sync()
        stats = sched.stats()
    finally:
        sched.close()
    per_device = collections.Counter()
    for ma, is_output in made:
        val = ma.device
        if val is None:
            check(not is_output, f"{bench.name}: output {ma.name} was "
                                 f"never computed on a device")
            continue
        check(isinstance(val, jax.Array),
              f"{bench.name}: {ma.name} holds {type(val).__name__}, "
              f"not a jax.Array")
        devs = val.devices()
        check(all(d.platform == platform for d in devs),
              f"{bench.name}: {ma.name} lives on {devs}, not on {platform}")
        if is_output:
            per_device.update(str(d) for d in devs)
    check(per_device, f"{bench.name}: no output reached a device")
    return got, per_device, stats


# ----------------------------------------------------------------------
# Phase A: the paper's six programs on one chip
# ----------------------------------------------------------------------

def phase_a(platform, scale=1.0, programs=PROGRAMS):
    from repro.benchsuite import BENCHMARKS

    t_phase = time.perf_counter()
    for name in programs:
        t0 = time.perf_counter()
        bench = BENCHMARKS[name]
        data = bench.make_data(scale)
        for key, arr in data.items():
            check(arr.dtype.itemsize <= 4,
                  f"{name}: input {key} is {arr.dtype}; a 64-bit array "
                  f"would be cast to 32 bits on the device")
        ref = bench.run_reference(data, iters=2)
        outs, placed = {}, None
        for policy in ("serial", "parallel"):
            got, placed, _ = run_program(bench, data, policy, platform)
            for k in ref:
                assert_close(got[k], ref[k], f"{name}/{policy}:{k}", REF_TOL)
            outs[policy] = got
        for k in outs["serial"]:
            assert_close(outs["parallel"][k], outs["serial"][k],
                         f"{name}: parallel vs serial {k}", POLICY_TOL)
        print(f"  A {name}: sizes {bench.sizes(scale)}; serial and "
              f"parallel match run_reference and each other; outputs on "
              f"{dict(placed)}; {smoke_time(t0)}", flush=True)
        del data, ref, outs
    print(f"phase A PASS: {len(programs)} programs at scale {scale} on the "
          f"real ThreadLaneExecutor, serial and parallel; "
          f"{smoke_time(t_phase)}", flush=True)


# ----------------------------------------------------------------------
# Phase B: Hymba-1.5B serving at published widths
# ----------------------------------------------------------------------

def phase_b(arch="hymba_1_5b", reduced=False, requests=8, batch=4,
            prompt_len=1024, new_tokens=32, seed=0):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.daemon import DaemonClient, DaemonServer
    from repro.models import init_cache, init_lm
    from repro.runtime.serving import ServingEngine
    from repro.runtime.steps import make_decode_step, make_prefill_step

    t_phase = time.perf_counter()
    cfg = get_config(arch, reduced=reduced)
    # The serve_lm job builds its weights and prompts the same way.
    params = init_lm(jax.random.PRNGKey(seed), cfg,
                     dtype=jnp.float32 if reduced else jnp.bfloat16)
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(0, cfg.vocab, prompt_len) for _ in range(requests)]
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))

    # Plain loop of the jitted steps, no scheduler.
    t0 = time.perf_counter()
    prefill = jax.jit(make_prefill_step(cfg))
    decode = jax.jit(make_decode_step(cfg))
    plain = []
    for i in range(0, requests, batch):
        toks = np.stack(prompts[i:i + batch]).astype(np.int32)
        cache = init_cache(cfg, toks.shape[0], prompt_len + new_tokens)
        logits, cache = prefill(params, {"tokens": toks}, cache)
        nxt = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        gen = [nxt]
        for t in range(new_tokens - 1):
            nxt, _, cache = decode(params, nxt, cache,
                                   jnp.int32(prompt_len + t))
            gen.append(nxt)
        plain.append(np.asarray(jnp.concatenate(gen, axis=1)))
    plain = np.concatenate(plain)
    t_plain = time.perf_counter() - t0
    check(plain.shape == (requests, new_tokens), f"plain loop {plain.shape}")
    check(((plain >= 0) & (plain < cfg.vocab)).all(), "token out of range")

    # ServingEngine on a real scheduler (it owns and closes it).
    t0 = time.perf_counter()
    with ServingEngine(cfg, params, batch_size=batch,
                       max_new_tokens=new_tokens) as eng:
        reqs = [eng.submit(p) for p in prompts]
        eng.flush(force=True)
        eng.collect()
        stats = eng.stats()
    engine = np.stack([r.result for r in reqs])
    t_engine = time.perf_counter() - t0
    check(stats["plan_replays"] > 0,
          f"no batch replayed the captured plan: {stats['plan_replays']}")
    check(np.array_equal(engine, plain),
          f"engine tokens differ from the plain loop in "
          f"{int((engine != plain).sum())} places")
    del params, eng

    # The same requests as one serve_lm job of an in-process daemon.
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="smoke-daemon-") as tmp:
        srv = DaemonServer(os.path.join(tmp, "d.sock"),
                           store_path=os.path.join(tmp, "jobs.jsonl"),
                           workers=1).start()
        try:
            with DaemonClient(srv.socket_path) as client:
                job = client.submit("serve_lm", {
                    "arch": arch, "reduced": reduced, "requests": requests,
                    "prompt_len": prompt_len, "new_tokens": new_tokens,
                    "batch_size": batch, "seed": seed}, error_on_shed=True)
                result = client.result(job["job_id"], timeout=900.0)
        finally:
            srv.stop()
    daemon = np.asarray(result["generations"])
    t_daemon = time.perf_counter() - t0
    check(np.array_equal(daemon, plain),
          f"daemon tokens differ from the plain loop in "
          f"{int((daemon != plain).sum())} places")

    print(f"phase B PASS: {cfg.name} ({n_params / 1e9:.3f} B params, "
          f"{'float32 reduced' if reduced else 'bfloat16 full width'}) "
          f"{requests} requests, batch {batch}, prompt {prompt_len}, "
          f"{new_tokens} new tokens; engine ({stats['plan_replays']} plan "
          f"replays), daemon serve_lm job and plain jitted loop bit-identical;"
          f" smoke timing plain {t_plain:.1f} s, engine {t_engine:.1f} s, "
          f"daemon {t_daemon:.1f} s; {smoke_time(t_phase)}", flush=True)


# ----------------------------------------------------------------------
# Phase C: the Pallas kernels on the chip
# ----------------------------------------------------------------------

def run_compiled_kernel(fn, args, kwargs):
    """Compile ``fn`` for the default device, require the Mosaic kernel in
    its HLO (no interpret mode), and run that compiled program."""
    compiled = fn.lower(*args, **kwargs).compile()
    check("tpu_custom_call" in compiled.as_text(),
          f"{fn.__name__}: no tpu_custom_call in the compiled program")
    return compiled(*args)


def phase_c():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.rmsnorm.ops import rmsnorm
    from repro.kernels.rmsnorm.ref import rmsnorm_ref
    from repro.kernels.rwkv6.ops import wkv6
    from repro.kernels.rwkv6.ref import wkv6_ref
    from repro.kernels.selective_scan.ops import selective_scan
    from repro.kernels.selective_scan.ref import selective_scan_ref

    t_phase = time.perf_counter()
    tol = dict(rtol=2e-2, atol=2e-2)         # bfloat16, as test_kernels.py
    f32 = lambda x: np.asarray(x, np.float32)
    ks = jax.random.split(jax.random.PRNGKey(0), 10)
    done = []

    # Hymba-1.5B prefill: 25 query heads over 5 KV heads, SWA 1024.
    B, S, H, Hkv, hd = 1, 2048, 25, 5, 64
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.bfloat16)
    got = run_compiled_kernel(flash_attention, (q, k, v), {"window": 1024})
    with jax.default_matmul_precision("highest"):
        ref = attention_ref(q, k, v, window=1024)
    assert_close(f32(got), f32(ref), "flash_attention", tol)
    done.append(f"flash_attention {q.shape}x{k.shape} window 1024")

    # Jamba2-3B prefill: 20 query heads over one KV head of 128, causal.
    # The reference materialises its scores, so it runs a batch row and
    # four heads at a time.
    B, S, H, Hkv, hd = 4, 8192, 20, 1, 128
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.bfloat16)
    got = f32(run_compiled_kernel(flash_attention, (q, k, v), {}))
    with jax.default_matmul_precision("highest"):
        for b in range(B):
            for h in range(0, H, 4):
                ref = attention_ref(q[b:b + 1, :, h:h + 4], k[b:b + 1],
                                    v[b:b + 1])
                assert_close(got[b:b + 1, :, h:h + 4], f32(ref),
                             f"flash_attention batch {b} heads {h}-{h + 3}",
                             tol)
    done.append(f"flash_attention {q.shape}x{k.shape} causal")

    for d in (1600, 2048):
        x = jax.random.normal(ks[3], (4096, d), jnp.bfloat16)
        scale = jax.random.normal(ks[4], (d,), jnp.bfloat16) * 0.1 + 1.0
        got = run_compiled_kernel(rmsnorm, (x, scale), {})
        assert_close(f32(got), f32(rmsnorm_ref(x, scale)), f"rmsnorm {d}",
                     tol)
        done.append(f"rmsnorm {x.shape}")

    # RWKV-6-1.6B: 32 heads of 64 channels, 1024 tokens.
    B, T, H, hd = 1, 1024, 32, 64
    r, kk, vv = (jax.random.normal(ks[5 + i], (B, T, H, hd),
                                   jnp.bfloat16) * 0.5 for i in range(3))
    w = (jax.nn.sigmoid(jax.random.normal(ks[8], (B, T, H, hd),
                                          jnp.bfloat16)) * 0.5 + 0.4)
    u = jax.random.normal(ks[9], (H, hd), jnp.bfloat16) * 0.3
    s0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    y, sT = run_compiled_kernel(wkv6, (r, kk, vv, w, u, s0), {})
    flat = lambda t: jnp.swapaxes(t, 1, 2).reshape(B * H, T, hd)
    with jax.default_matmul_precision("highest"):
        y_ref, sT_ref = wkv6_ref(flat(r), flat(kk), flat(vv), flat(w),
                                 jnp.tile(u[None], (B, 1, 1)).reshape(B * H,
                                                                      hd),
                                 s0.reshape(B * H, hd, hd))
    y_ref = jnp.swapaxes(y_ref.reshape(B, H, T, hd), 1, 2).reshape(B, T, -1)
    assert_close(f32(y), f32(y_ref), "wkv6 y", tol)
    assert_close(f32(sT).reshape(B * H, hd, hd), f32(sT_ref), "wkv6 state",
                 tol)
    done.append(f"wkv6 {r.shape}")

    # Hymba-1.5B's Mamba prefill scan: inner 3200, state 16, 2048 tokens.
    B, S, inner, N = 2, 2048, 3200, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, inner)) - 3.0)
    u = jax.random.normal(ks[1], (B, S, inner))
    Bm, Cm = (jax.random.normal(ks[i], (B, S, N)) for i in (2, 3))
    A_T = -jnp.exp(jax.random.normal(ks[4], (N, inner)) * 0.5)
    h0 = jax.random.normal(ks[5], (B, N, inner))
    args = tuple(a.astype(jnp.bfloat16) for a in (dt, u, Bm, Cm)) + (A_T, h0)
    y, hT = run_compiled_kernel(selective_scan, args, {})
    y_ref, hT_ref = selective_scan_ref(*args)
    assert_close(f32(y), f32(y_ref), "selective_scan y", tol)
    assert_close(f32(hT), f32(hT_ref), "selective_scan state",
                 dict(rtol=1e-4, atol=1e-4))
    done.append(f"selective_scan {dt.shape}")

    print(f"phase C PASS: {'; '.join(done)}; each compiled with "
          f"tpu_custom_call and matched its ref.py; {smoke_time(t_phase)}",
          flush=True)


# ----------------------------------------------------------------------
# --chips 4: multi-device placement
# ----------------------------------------------------------------------

def phase_multi(platform, n_devices=4, scale=1.0):
    from repro.benchsuite import BENCHMARKS

    t_phase = time.perf_counter()
    for name in ("B&S", "IMG"):
        t0 = time.perf_counter()
        bench = BENCHMARKS[name]
        data = bench.make_data(scale)
        ref = bench.run_reference(data, iters=2)
        one, _, _ = run_program(bench, data, "parallel", platform)
        notes = []
        for placement in ("affinity", "round-robin"):
            got, placed, stats = run_program(
                bench, data, "parallel", platform, num_devices=n_devices,
                placement=placement)
            for k in ref:
                assert_close(got[k], ref[k], f"{name}/{placement}:{k}",
                             REF_TOL)
                assert_close(got[k], one[k],
                             f"{name}/{placement} vs one device:{k}",
                             POLICY_TOL)
            # B&S's ten independent kernels spread over every device under
            # both policies.  IMG's kernels are chained: a consumer on
            # another device moves its input there (a D2D), so its outputs
            # end on fewer devices -- but never on one alone.
            want = n_devices if name == "B&S" else 2
            check(len(placed) >= want,
                  f"{name}/{placement}: outputs on {dict(placed)}, fewer "
                  f"than {want} distinct devices")
            d2d = stats["d2d_transfers"]
            if placement == "round-robin" and name == "IMG":
                check(d2d > 0, "IMG/round-robin made no D2D transfer")
            notes.append(f"{placement}: outputs on {dict(placed)}, "
                         f"{d2d} D2D")
        print(f"  multi {name}: sizes {bench.sizes(scale)}; matches "
              f"run_reference and one device; {'; '.join(notes)}; "
              f"{smoke_time(t0)}", flush=True)
        del data, ref, one
    print(f"phase multi-device PASS: B&S and IMG on {n_devices} devices "
          f"under affinity and round-robin; {smoke_time(t_phase)}",
          flush=True)


# ----------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the multi-device phase")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}); no CPU fallback",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX sees {len(devices)}", file=sys.stderr)
        return 2
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({e})", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    print(f"chip_smoke: {len(devices)} x {dev.device_kind} ({dev.platform}); "
          f"compile cache {cache}", flush=True)

    try:
        if args.chips == 4:
            phase_multi(dev.platform, n_devices=4)
        else:
            phase_a(dev.platform)
            phase_b()
            phase_c()
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
