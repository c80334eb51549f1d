import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first initialization).
import argparse
import json
import sys
import time
import traceback

import jax
import numpy as np

from repro.configs import ARCH_IDS, cells, get_config
from repro.launch.hlostats import analyze_hlo
from repro.launch.mesh import (HBM_BW, HBM_BYTES, ICI_BW, PEAK_BF16_FLOPS,
                               make_production_mesh)
from repro.launch.specs import build_cell
from repro.models.config import SHAPES


def roofline_terms(stats, mem, chips: int, cfg, cell) -> dict:
    """Three-term roofline (§Roofline).  HLO stats are per-device (SPMD
    modules are per-device programs)."""
    compute_s = stats.flops / PEAK_BF16_FLOPS
    memory_s = stats.memory_bytes / HBM_BW
    collective_s = stats.wire_bytes / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    # MODEL_FLOPS: 6*N*D for training, 2*N*D forward-only (per device)
    n_params = cfg.param_count(active_only=True)
    tokens = cell.seq_len * cell.global_batch if cell.kind != "decode" \
        else cell.global_batch
    factor = 6.0 if cell.kind == "train" else 2.0
    model_flops = factor * n_params * tokens / chips
    return {
        **terms,
        "dominant": dominant,
        "model_flops_per_device": model_flops,
        "hlo_flops_per_device": stats.flops,
        "useful_flops_ratio": model_flops / stats.flops if stats.flops else 0,
        "step_time_bound_s": max(terms.values()),
        "roofline_fraction": (compute_s / max(terms.values())
                              if max(terms.values()) > 0 else 0.0),
    }


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             seq_shard=None,
             remat: bool = True, verbose: bool = True) -> dict:
    cfg = get_config(arch)
    cell = SHAPES[shape_name]
    for c, skip in cells(arch):
        if c.name == shape_name and skip:
            return {"arch": arch, "shape": shape_name, "skipped": skip}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    lowering = build_cell(cfg, cell, mesh, remat=remat,
                          seq_shard=seq_shard)
    with mesh:
        jitted = jax.jit(lowering.fn,
                         in_shardings=lowering.in_shardings,
                         out_shardings=lowering.out_shardings,
                         donate_argnums=lowering.donate_argnums)
        lowered = jitted.lower(*lowering.arg_specs)
        compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    stats = analyze_hlo(compiled.as_text())

    arg_b = getattr(mem, "argument_size_in_bytes", 0)
    out_b = getattr(mem, "output_size_in_bytes", 0)
    tmp_b = getattr(mem, "temp_size_in_bytes", 0)
    alias_b = getattr(mem, "alias_size_in_bytes", 0)
    peak_bytes = arg_b + out_b + tmp_b - alias_b
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": dict(mesh.shape), "chips": chips,
        "compile_s": round(t_compile, 1),
        "memory": {"argument_bytes": arg_b, "output_bytes": out_b,
                   "temp_bytes": tmp_b, "alias_bytes": alias_b,
                   "peak_bytes_per_device": peak_bytes,
                   "fits_hbm": bool(peak_bytes <= HBM_BYTES),
                   "hbm_fraction": peak_bytes / HBM_BYTES},
        "xla_cost_analysis": {
            "flops_once": float(ca.get("flops", 0.0)),
            "bytes_once": float(ca.get("bytes accessed", 0.0))},
        "hlo": stats.as_dict(),
        "roofline": roofline_terms(stats, mem, chips, cfg, cell),
        "meta": lowering.meta,
    }
    if verbose:
        m = result["memory"]
        r = result["roofline"]
        print(f"[{arch} x {shape_name} x {'2x16x16' if multi_pod else '16x16'}]"
              f" compile {t_compile:.0f}s | "
              f"mem/device {m['peak_bytes_per_device']/2**30:.2f} GiB "
              f"({'fits' if m['fits_hbm'] else 'OVER'}) | "
              f"compute {r['compute_s']*1e3:.2f} ms, "
              f"memory {r['memory_s']*1e3:.2f} ms, "
              f"collective {r['collective_s']*1e3:.2f} ms -> "
              f"{r['dominant']} bound, roofline {r['roofline_fraction']:.2f}")
        print(f"  memory_analysis: {mem}")
        print(f"  cost_analysis(once): flops={ca.get('flops', 0):.3e} "
              f"bytes={ca.get('bytes accessed', 0):.3e}")
        print(f"  collectives: { {k: f'{v/2**30:.2f} GiB' for k, v in stats.collective_bytes.items()} }")
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description="multi-pod dry run")
    ap.add_argument("--arch", default=None, help="arch id (default: all)")
    ap.add_argument("--shape", default=None, help="shape cell (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--seq-shard", action="store_true", default=None)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else ARCH_IDS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'multi' if mp else 'single'}"
                try:
                    res = run_cell(arch, shape, multi_pod=mp,
                                   seq_shard=args.seq_shard,
                                   remat=not args.no_remat)
                except Exception as e:
                    traceback.print_exc()
                    failures.append(tag)
                    res = {"arch": arch, "shape": shape, "multi_pod": mp,
                           "error": repr(e)}
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(res, f, indent=1)
    if failures:
        print("FAILURES:", failures, file=sys.stderr)
        sys.exit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
