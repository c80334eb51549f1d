#!/usr/bin/env python3
"""Device-idle time of a traced window, put down to the program span open
at each moment on any host thread, and the count and mean length of every
program span in the window.

    python3 chipbench/tools/idle_by_span.py TRACE.xplane.pb
    python3 chipbench/tools/idle_by_span.py --workload paper6-bs --seed 7 \\
        --seconds 10 [--json OUT.json]

Each idle gap of the first device is cut at every start and end of a
program or benchmark span.  A piece goes to the innermost program span open
at its midpoint, preferring device work (copies, kernels, ``gr:dispatch``,
``gr:block``) over the rest and the rest over waits (``gr:host_wait``,
``gr:lane_wait``, ``gr:sync``), written with the spans of its thread that
enclose it (``h2d>block``).  Beside it stands the benchmark's own ``cb:``
label of the piece; ``-`` marks a piece with no program span open.  Also
printed: the share of the window in which the device was idle while a copy
span was open on any thread.  With ``--workload`` the cell runs once on the
chip, traced as ``--trace 1`` traces it.  Nothing here changes a result
line.
"""
import argparse
import importlib
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def traced_cell(workload: str, seed: int, seconds: float):
    """Run ``workload`` once with the trace on: the benchmark's trace, the
    program's spans, the window and the cell's device planes."""
    from chipbench import bench
    from chipbench import program_spans as P
    from chipbench import trace as T

    class KeptRun(bench.Run):
        """A run that also reads the program's spans from its trace."""

        def stop_trace(self) -> None:
            import jax
            jax.profiler.stop_trace()
            try:
                path = T.find_xplane(self._trace_dir)
                self.trace = T.load(path)
                self.program_spans = P.load(path)
            finally:
                shutil.rmtree(self._trace_dir, ignore_errors=True)
            self.window_ns = self.trace.window()

    cell = {w["name"]: w for w in bench.benchmark()["workloads"]}[workload]
    devices = bench.find_devices(cell["chips"])
    bench.enable_cache()
    run = KeptRun(cell, seed, seconds, True, devices, time.perf_counter())
    gen = importlib.import_module(
        f"chipbench.generators.{run.traffic['generator']}")
    gen.setup(run)
    gen.window(run)
    gen.check(run)
    return run.trace, run.program_spans, run.window_ns, run.device_planes


def report(trace, spans, window, planes) -> dict:
    from chipbench import program_spans as P
    from chipbench import trace as T
    t0, t1 = window
    ops = trace.ops(planes[0])
    idle = P.idle_by_span(ops, trace, spans, t0, t1)
    return {"window_s": (t1 - t0) * 1e-9,
            "busy_s": T.busy_ns(ops, t0, t1) * 1e-9,
            "runs": len(T.spans_in(trace, "run", t0, t1)),
            "idle_in_copy_pct": 100 * P.idle_in_copy_ns(ops, spans, t0, t1)
            / (t1 - t0),
            "idle_by_span": sorted(([cb, gr, s] for (cb, gr), s in
                                    idle.items()), key=lambda r: -r[2]),
            "spans": {k: {"count": n, "mean_us": m} for k, (n, m) in
                      P.span_table(spans, t0, t1).items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane", nargs="?")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--json", help="also write the reduction here")
    args = ap.parse_args(argv)
    if (args.xplane is None) == (args.workload is None):
        ap.error("give a trace file or --workload, not both")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import program_spans as P
    from chipbench import trace as T
    if args.xplane:
        trace = T.load(args.xplane)
        out = report(trace, P.load(args.xplane), trace.window(),
                     sorted(trace.devices))
    else:
        out = report(*traced_cell(args.workload, args.seed, args.seconds))
    print(f"window {out['window_s']:.6f} s, {out['runs']} runs, device "
          f"busy {out['busy_s']:.6f} s, idle with a copy open "
          f"{out['idle_in_copy_pct']:.3f}% of the window")
    print(f"{'benchmark span':<16} {'program span':<36} {'idle s':>12}")
    for cb, gr, s in out["idle_by_span"]:
        print(f"{cb:<16} {gr:<36} {s:>12.6f}")
    print(f"\n{'program span':<16} {'count':>7} {'mean us':>12}")
    for name, v in out["spans"].items():
        print(f"{name:<16} {v['count']:>7} {v['mean_us']:>12.2f}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
