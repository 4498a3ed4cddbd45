#!/usr/bin/env python3
"""Readings that set the limits of a cell's correctness check, on the chip.

    python3 benchmarks/chip/calibrate.py --cell s16-128e.train \
        --seeds 11,12,... --control-seeds 21,22,23 [--out FILE]

In one process, for every seed of ``--seeds``: the program's numbers at the
cell's own size, as a run of the cell compares them (the lower readings).
For every seed of ``--control-seeds``: the control, the reference computed
in float8 put in the program's place, and for a training cell the planted
fault of half of each batch left out (the upper readings). A training
cell's readings also keep each leaf's norms on both sides, so that the
gap of every leaf can be read against its own norm. The benchmark's own
runs never run this. Prints one JSON object; ``--out`` also writes it.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import compare, ref_train, spec  # noqa: E402
from chipbench.harness import enable_compile_cache  # noqa: E402


def _with_norms(got: dict, ref: dict) -> tuple:
    """``train_numbers`` of one reading, and both sides' leaf norms."""
    numbers, detail = compare.train_numbers(got, ref)
    norms = {k: {"got": got[k], "ref": ref[k]} for k in ("grad1", "change")}
    return numbers, detail, norms


def train(cell, seeds, control_seeds) -> dict:
    from chipbench.drivers.vit_train import Program

    cfg, n = cell.config, cell.traffic["check_steps"]
    out = {"program": {}, "control": {}, "half_batch": {}}
    prog = Program(cfg, os.path.join(cell.root, ".bench", "ckpt"))
    for seed in seeds:
        box, readings, _ = prog.start(seed, n)
        box.clear()
        gc.collect()
        out["program"][seed] = _with_norms(readings,
                                           ref_train.run(cfg, seed, n))
        print(seed, out["program"][seed][0], flush=True)
    del prog
    gc.collect()
    for seed in control_seeds:
        ref = ref_train.run(cfg, seed, n)
        for name, kw in (("control", {"control": True}),
                         ("half_batch", {"half_batch": True})):
            out[name][seed] = _with_norms(
                ref_train.run(cfg, seed, n, **kw), ref)
            print(name, seed, out[name][seed][0], flush=True)
    return out


def infer(cell, seeds, control_seeds) -> dict:
    import jax
    import numpy as np
    import repro.models as models

    from chipbench import weights
    from chipbench.drivers import _vit
    from chipbench.drivers.vit_infer import (pool_batch, reference_logits,
                                             row_errors)

    cfg, tr = cell.config, cell.traffic
    batch, pool = tr["batch"], range(tr["pool"])
    mcfg = _vit.model_config(cfg)
    shapes = _vit.check_layout(mcfg, cfg)
    fwd = jax.jit(lambda p, x: models.vit_apply(
        p, mcfg, x, use_kernel=cfg["use_kernel"])[0])
    out = {"program": {}, "control": {}}

    def worst(got, refs):
        return float(max(np.asarray(row_errors(got[j], refs[j])).max()
                         for j in refs))

    for seed in seeds:
        params = weights.make_params(shapes, seed)
        got = {j: fwd(params, pool_batch(cfg, seed, j, batch)) for j in pool}
        del params
        gc.collect()
        out["program"][seed] = worst(
            got, reference_logits(cfg, seed, pool, batch))
        print(seed, out["program"][seed], flush=True)
    for seed in control_seeds:
        ctl = reference_logits(cfg, seed, pool, batch, control=True)
        out["control"][seed] = worst(
            ctl, reference_logits(cfg, seed, pool, batch))
        print("control", seed, out["control"][seed], flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(spec.BENCH_DIR))
    cell = spec.resolve(root, args.cell)
    sys.path.insert(0, os.path.join(root, "src"))
    enable_compile_cache(root)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 1
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = [int(s) for s in args.control_seeds.split(",") if s]
    t0 = time.perf_counter()
    run = {"vit_train": train, "vit_infer": infer}[cell.traffic["driver"]]
    readings = run(cell, seeds, controls)
    out = {"cell": args.cell, "readings": readings,
           "seconds": time.perf_counter() - t0}
    text = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
