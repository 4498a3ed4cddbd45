import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ruff: noqa: E402  (the two lines above MUST precede any jax import —
# jax locks the device count at first init)
"""Multi-pod dry-run: lower + compile EVERY (architecture × input shape)
cell on the production meshes, print memory/cost analysis, and emit the
roofline terms (compute / memory / collective) per cell.

Usage:
  python -m repro.launch.dryrun --arch llama3-8b --shape train_4k \
      [--multi-pod] [--fsdp/--no-fsdp] [--out results.jsonl]
  python -m repro.launch.dryrun --all [--multi-pod]   # every valid cell

One CPU core compiles these; cells are independent so the driver writes
one JSON line per cell and can resume (--skip-done).
"""
import argparse
import json
import time
import traceback

import jax

from ..configs import ASSIGNED_NAMES, SHAPES, get_config, shape_supported
from ..distributed.api import use_mesh
from ..distributed.sharding import ShardingOptions
from ..roofline.analysis import (
    HBM_BW,
    ICI_BW,
    PEAK_FLOPS_BF16,
    model_flops,
)
from ..roofline.flops import analytic_cost
from ..roofline.hlo_parse import cpu_upcast_correction, parse_module_collectives
from .mesh import make_production_mesh
from .specs import build_cell

DCN_BW = 25e9  # inter-pod (data-center network) bytes/s per chip, effective


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             opts: ShardingOptions | None = None, microbatches: int = 1,
             use_kernel: bool = False, dp_over_model: bool = False,
             zero1: bool = False, cfg_overrides: dict | None = None,
             verbose: bool = True) -> dict:
    import dataclasses

    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    ok, reason = shape_supported(cfg, shape_name)
    if not ok:
        return {"cell": f"{arch}:{shape_name}", "status": "skipped",
                "reason": reason}
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    with use_mesh(mesh, dp_over_model=dp_over_model):
        cell = build_cell(cfg, shape_name, mesh, opts,
                          microbatches=microbatches, use_kernel=use_kernel,
                          zero1=zero1)
        jitted = jax.jit(
            cell.fn, in_shardings=cell.in_shardings,
            donate_argnums=cell.donate,
        )
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    mc = parse_module_collectives(
        hlo_text, pod_size=256 if multi_pod else None
    )
    # clamp: shape-keyed estimate can exceed the true peak (buffer reuse)
    upcast = min(cpu_upcast_correction(hlo_text), mem.temp_size_in_bytes)

    shape = SHAPES[shape_name]
    an = analytic_cost(cfg, shape_name)
    flops_dev, bytes_dev = an.per_device(chips)
    coll_ici = mc.weighted_ici_bytes()
    coll_pod = mc.weighted_pod_bytes()

    t_compute = flops_dev / PEAK_FLOPS_BF16
    t_memory = bytes_dev / HBM_BW
    t_coll = coll_ici / ICI_BW + coll_pod / DCN_BW
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    bottleneck = max(terms, key=terms.get)
    mf = model_flops(cfg, shape, shape.mode)

    result = {
        "cell": f"{arch}:{shape_name}",
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "status": "ok",
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "args_bytes_per_dev": mem.argument_size_in_bytes,
            "temp_bytes_per_dev": mem.temp_size_in_bytes,
            # XLA-CPU computes bf16 dots in f32 and hoists stacked-operand
            # converts out of loops; these f32 copies would not exist on
            # TPU (native bf16 MXU). See hlo_parse.cpu_upcast_correction.
            "cpu_f32_upcast_bytes": upcast,
            "tpu_corrected_temp_bytes": mem.temp_size_in_bytes - upcast,
            "output_bytes_per_dev": mem.output_size_in_bytes,
        },
        "hlo_cost": {
            "flops_per_dev": ca.get("flops", 0.0),
            "bytes_per_dev": ca.get("bytes accessed", 0.0),
            "note": "scan bodies counted once by XLA (see roofline docs)",
        },
        "analytic": {
            "flops_per_dev": flops_dev,
            "bytes_per_dev": bytes_dev,
            "note": an.notes,
        },
        "collectives": {
            "by_kind_bytes": mc.by_kind(),
            "counts": mc.counts(),
            "ici_weighted_bytes": coll_ici,
            "pod_weighted_bytes": coll_pod,
        },
        "roofline": {
            "t_compute_s": t_compute,
            "t_memory_s": t_memory,
            "t_collective_s": t_coll,
            "bottleneck": bottleneck,
            "bound_s": max(terms.values()),
            "roofline_fraction": (
                t_compute / max(terms.values()) if max(terms.values()) else 0
            ),
            "model_flops": mf,
            "useful_flops_fraction": (
                mf / (flops_dev * chips) if flops_dev else 0
            ),
        },
    }
    if verbose:
        print(json.dumps(result))
    return result


def all_cells():
    for arch in ASSIGNED_NAMES:
        for shape in SHAPES:
            yield arch, shape


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--use-kernel", action="store_true")
    ap.add_argument("--dp-over-model", action="store_true",
                    help="pure data parallelism: batch over model axis too")
    ap.add_argument("--no-tp", action="store_true",
                    help="disable tensor/expert parallelism")
    ap.add_argument("--zero1", action="store_true",
                    help="ZeRO-1: bf16 compute params replicated over data")
    ap.add_argument("--tag", default=None, help="label for perf iterations")
    ap.add_argument("--out", default=None)
    ap.add_argument("--skip-done", action="store_true")
    args = ap.parse_args()

    opts = ShardingOptions(
        fsdp=not args.no_fsdp,
        tensor_parallel=not args.no_tp,
        expert_parallel=not args.no_tp,
    )
    mesh_name = "2x16x16" if args.multi_pod else "16x16"
    done = set()
    if args.out and args.skip_done and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    if r.get("status") in ("ok", "skipped"):
                        done.add((r.get("cell"), r.get("mesh", mesh_name)))
                except json.JSONDecodeError:
                    pass

    cells = (
        list(all_cells()) if args.all else [(args.arch, args.shape)]
    )
    results = []
    for arch, shape in cells:
        if (f"{arch}:{shape}", mesh_name) in done:
            print(f"# skip (done): {arch}:{shape}")
            continue
        try:
            r = run_cell(
                arch, shape, multi_pod=args.multi_pod, opts=opts,
                microbatches=args.microbatches, use_kernel=args.use_kernel,
                dp_over_model=args.dp_over_model, zero1=args.zero1,
            )
        except Exception as e:  # a cell failure is a bug — record it
            traceback.print_exc()
            r = {"cell": f"{arch}:{shape}", "mesh": mesh_name,
                 "status": "error", "error": f"{type(e).__name__}: {e}"}
            print(json.dumps(r))
        if args.tag:
            r["tag"] = args.tag
        results.append(r)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(r) + "\n")
    n_ok = sum(1 for r in results if r.get("status") == "ok")
    n_skip = sum(1 for r in results if r.get("status") == "skipped")
    n_err = sum(1 for r in results if r.get("status") == "error")
    print(f"# dry-run complete: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
