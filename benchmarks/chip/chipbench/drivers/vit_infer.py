"""Closed-loop batch inference through jitted ``vit_apply``.

Set-up makes the f32 weights in one jitted call from the seed and a pool of
``pool`` input batches of ``batch`` images on the device, and compiles the
forward on one of them. The window calls the forward on the pool's batches
in turn and waits for each result. Calls whose index the seed picks (about
one in ``keep_every``, and always the window's first) keep their logits;
after the window the reference computes the same batches, and every row of
every kept call is compared.
"""
from __future__ import annotations

import functools
import gc
import sys

import jax
import jax.numpy as jnp
import numpy as np

from .. import ref_vit, weights
from ..harness import Outcome, memory_peak_bytes
from . import _vit


def kept(seed: int, keep_every: int, n: int = 1 << 16) -> np.ndarray:
    """Which window calls keep their logits, drawn from the seed (call
    ``i`` reads entry ``i % n``); the first call always does."""
    rng = np.random.default_rng([int(seed) % (1 << 63), 7])
    pick = rng.integers(0, keep_every, size=n) == 0
    pick[0] = True
    return pick


def pool_batch(cfg: dict, seed: int, j: int, batch: int):
    return jax.jit(functools.partial(
        weights.images, batch=batch, num_patches=cfg["num_patches"],
        patch_dim=cfg["patch_dim"], num_classes=cfg["num_classes"]))(
            weights.seed_key(seed, stream=2), j)["patches"]


def reference_logits(cfg: dict, seed: int, pool_ids, batch: int,
                     control: bool = False) -> dict:
    """``{pool index: reference logits}`` for the seed's weights."""
    params = weights.make_params(ref_vit.param_shapes(cfg), seed)
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, x: ref_vit.logits(p, x, cfg, control))
        return {j: fwd(params, pool_batch(cfg, seed, j, batch))
                for j in pool_ids}


@jax.jit
def row_errors(prog, ref):
    return (jnp.linalg.norm(prog.astype(jnp.float32) - ref, axis=-1)
            / jnp.linalg.norm(ref, axis=-1))


def run(ctx) -> Outcome:
    import repro.models as models

    cfg, seed, tr = ctx.config, ctx.seed, ctx.traffic
    batch, n_pool = tr["batch"], tr["pool"]
    mcfg = _vit.model_config(cfg)
    shapes = _vit.check_layout(mcfg, cfg)
    params = weights.make_params(shapes, seed)
    pool = [pool_batch(cfg, seed, j, batch) for j in range(n_pool)]
    use_kernel = cfg["use_kernel"]
    fwd = jax.jit(lambda p, x: models.vit_apply(
        p, mcfg, x, use_kernel=use_kernel)[0])
    jax.block_until_ready(fwd(params, pool[0]))
    pick = kept(seed, tr["keep_every"])
    outputs = {}

    def call(i: int) -> int:
        out = jax.block_until_ready(fwd(params, pool[i % n_pool]))
        if pick[i % pick.size]:
            outputs[i] = out
        return batch

    win = ctx.window(call)
    peak = memory_peak_bytes()
    del params, pool
    gc.collect()
    refs = reference_logits(cfg, seed, {i % n_pool for i in outputs}, batch)
    errs = [np.asarray(row_errors(out, refs[i % n_pool]))
            for i, out in outputs.items()]
    worst = float(max(e.max() for e in errs))
    print(f"infer check: {len(outputs)} calls kept, rows compared "
          f"{sum(e.size for e in errs)}", file=sys.stderr)
    return Outcome(
        metrics={"images_per_s": win.units / win.seconds},
        attempted=win.units, failed=0, numbers={"logits_gap": worst},
        memory_peak_bytes=peak,
        record={"units": win.units, "calls": win.calls, "batch": batch,
                "units_per_s": win.units / win.seconds,
                "softmoe_passes": ("fwd",)})
