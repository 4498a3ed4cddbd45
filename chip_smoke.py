#!/usr/bin/env python3
"""Smoke run of the main paths on TPU, through the entry points users call.

  python3 chip_smoke.py [--seed N]   # one chip: Soft-MoE trainer + paged server
  python3 chip_smoke.py --chips 4    # four chips: the sharded train step only

One chip:

* train: ``Trainer`` takes 5 steps on the paper's soft-moe-s/16-128e (921M
  parameters, published widths, 128 experts, 196 tokens) with
  ``vit_loss(..., use_kernel=True)``, so the fused Soft-MoE forward and
  backward Pallas kernels run compiled. On the first step's params (head
  made nonzero, see ``_init_params``) and batch, the kernel path's loss and
  every gradient leaf must be as close to an f32 reference as the jnp
  path's are (``_within_noise``), and the trainer's step 1 must be that
  kernel path's.
* serve: ``ServeEngine(backend="paged")`` answers 8 greedy requests (prompts
  of 16-512 tokens, 32 new tokens each) on qwen2-0.5b at published widths,
  first through the Pallas paged-attention decode kernel, then through the
  jnp gather path. The kernel must not have fallen back and its decode
  program must hold the kernel (``tpu_custom_call``). At every decode step
  the gather program runs on the kernel engine's pool with the same tokens
  (``_shadow_gather``), in bf16 and as an f32 reference; the kernel's
  logits may be off that reference by no more than the bf16 gather
  program's are (same rule as the train check). The free-running token
  streams may part only where the model's bf16 noise can decide between
  two tokens (``_divergence``).

Four chips (``--chips 4``), and nothing else:

* soft-moe-s/14-256e, whose f32 params and Adam moments (22 GB) exceed one
  chip, takes 3 train steps on a (data=2, model=2) mesh from
  ``make_host_mesh`` and ``state_shardings``: FSDP over data, experts over
  model. No device may hold most of the state.
* the first step of soft-moe-s/16-128e on that mesh against the same step
  on one of its devices alone, judged like the kernel path above.

Weights are random from ``--seed``. Each phase raises on any failure. The
script exits non-zero, and prints no result line, when JAX finds no TPU or
any phase fails; otherwise the last line of stdout is the JSON result.
"""
from __future__ import annotations

import argparse
import collections
import json
import logging
import math
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
# Checkpoints the trainer writes; removed again when the phase ends.
SCRATCH = os.path.join(ROOT, ".chip_smoke")
# Step 1 of a bf16 path is judged by its distance from the f32 reference
# (f32 activations and matmuls): for the loss and for every gradient leaf,
# the path under test (the fused kernels, or the sharded step) may be at
# most NOISE_FACTOR times as far from it as the plain path on one device
# (the jnp Soft-MoE), plus NOISE_FLOOR for quantities that the plain path
# happens to hit almost exactly (2**-9: half a bf16 ulp). Two correct
# paths round the same values in another order, so their errors are of
# one size; a dropped shard, a mis-scaled softmax or a wrong gradient
# term adds its own error on top and fails.
NOISE_FACTOR = 2.0
NOISE_FLOOR = 2.0 ** -9
# The trainer's step 1 against the checked kernel path: the same code
# compiled into another program, so only XLA's fusion choices differ.
RTOL = 1e-2
TRAIN_BATCH = 8  # f32 state + step fits 16 GB at 16.6e9 B (compile rehearsal)
MESH_BATCH = 8


def _say(msg: str) -> None:
    print(msg, flush=True)


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def _memory(device, key: str):
    return (device.memory_stats() or {}).get(key)  # None on the CPU


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


class CompileStats:
    """Backend compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit skips the backend compile)."""

    def __init__(self):
        self.seconds = 0.0
        self.events = collections.Counter()

    def install(self, monitoring) -> None:
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration_secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs

    def _event(self, event, **kw):
        if event.startswith("/jax/compilation_cache/"):
            self.events[event.rsplit("/", 1)[1]] += 1


def _has_kernel(lowered) -> bool:
    """Whether a lowered program holds a compiled Pallas TPU kernel."""
    return "tpu_custom_call" in lowered.as_text()


def _init_params(cfg, rng):
    """``vit_init`` with a lecun-initialised head. ``vit_init`` zeroes the
    head, so at step 1 every logit is 0, the loss is ln(1000) whatever the
    body computes, and every body leaf's gradient is exactly 0: a check on
    that step would see neither the forward nor the backward kernels."""
    import jax

    from repro.layers.common import lecun_init
    from repro.models import vit_init

    params = vit_init(rng, cfg)
    w = params["head"]["w"]
    params["head"]["w"] = lecun_init(jax.random.fold_in(rng, 1), w.shape,
                                     fan_in=w.shape[0])
    return params


def _grads(cfg, use_kernel: bool):
    import jax

    from repro.models import vit_loss

    def f(params, batch):
        (loss, _), grads = jax.value_and_grad(
            lambda p: vit_loss(p, cfg, batch, use_kernel=use_kernel),
            has_aux=True)(params)
        return loss, grads

    return f


def _reference(cfg, params, batch):
    """The loss and gradient the bf16 paths round: the jnp path with f32
    activations and f32 matmuls, on the default device."""
    import dataclasses

    import jax

    with jax.default_matmul_precision("float32"):
        return jax.jit(_grads(dataclasses.replace(cfg, dtype="float32"),
                              False))(params, batch)


def _errors(path, params, batch, ref_loss, ref_g, **jit_kw) -> dict:
    """Relative error of one path's loss and of each gradient leaf,
    ||g - g_ref|| / ||g_ref||, against the f32 reference on the same
    params and batch."""
    import jax
    import jax.numpy as jnp

    from repro.optim import global_norm

    def against(params, batch, ref_g):
        loss, g = path(params, batch)
        return loss, global_norm(g), jax.tree.map(
            lambda a, b: jnp.stack([jnp.linalg.norm(b),
                                    jnp.linalg.norm(a - b)]), g, ref_g)

    loss, norm, leaves = jax.jit(against, **jit_kw)(params, batch, ref_g)
    leaves, _ = jax.tree_util.tree_flatten_with_path(leaves)
    rel = {}
    for key, pair in leaves:
        name = jax.tree_util.keystr(key)
        ref_norm, err = map(float, pair)
        _check(ref_norm > 0, f"{name} has a zero gradient: a comparison "
               "cannot see the code that computes it")
        rel[name] = err / ref_norm
    return {"loss": _rel(float(loss), float(ref_loss)),
            "loss_value": float(loss), "grad_norm": float(norm),
            "leaves": rel}


def _within_noise(test: dict, base: dict) -> dict:
    """Holds path ``test`` to the accuracy of path ``base``: for the loss
    and for every gradient leaf, its error against the f32 reference may
    be at most NOISE_FACTOR times ``base``'s error, plus NOISE_FLOOR."""
    pairs = {"loss": (test["loss"], base["loss"]),
             **{k: (v, base["leaves"][k]) for k, v in test["leaves"].items()}}
    bound = {k: NOISE_FACTOR * b + NOISE_FLOOR for k, (_, b) in pairs.items()}
    worst = max(pairs, key=lambda k: pairs[k][0] / bound[k])
    out = {"loss_err": list(pairs["loss"]),
           "grad_norm": [test["grad_norm"], base["grad_norm"]],
           "leaves": len(pairs) - 1,
           "worst": {"name": worst, "err": list(pairs[worst]),
                     "bound": bound[worst]},
           "moe_leaf_err": {k: v for k, v in pairs.items()
                            if "moe" in k}}
    _check(pairs[worst][0] <= bound[worst],
           f"error beyond {NOISE_FACTOR} x the base path's + "
           f"{NOISE_FLOOR}: {out}")
    return out


def _images(cfg, batch_size: int, seed: int):
    from repro.data import SyntheticImages

    return SyntheticImages(num_patches=cfg.frontend.num_embeds,
                           patch_dim=cfg.frontend.embed_dim,
                           batch_size=batch_size, seed=seed)


def _opt_cfg():
    from repro.optim import OptimizerConfig

    return OptimizerConfig(peak_lr=1e-3, schedule="constant",
                           warmup_steps=0, total_steps=10**9,
                           cooldown_steps=1)


def train_phase(cfg, batch_size: int, steps: int, seed: int) -> dict:
    """``Trainer`` steps with the fused Soft-MoE kernels; step 1's loss and
    gradient checked leaf by leaf against the jnp path on the same params
    and batch."""
    import jax

    from repro.models import vit_init, vit_loss
    from repro.train import Trainer, TrainerConfig
    from repro.train.step import init_train_state

    rng = jax.random.PRNGKey(seed)
    data = _images(cfg, batch_size, seed)
    params = _init_params(cfg, rng)
    batch = data.batch(0)
    ref = _reference(cfg, params, batch)
    jnp_err = _errors(_grads(cfg, False), params, batch, *ref)
    kernel_err = _errors(_grads(cfg, True), params, batch, *ref)
    del ref
    step1 = _within_noise(kernel_err, jnp_err)
    shutil.rmtree(SCRATCH, ignore_errors=True)  # never resume a stale run
    trainer = Trainer(
        TrainerConfig(total_steps=steps, checkpoint_every=steps + 1,
                      checkpoint_dir=os.path.join(SCRATCH, "ckpt"),
                      log_every=1),
        lambda p, b: vit_loss(p, cfg, b, use_kernel=True),
        lambda r: vit_init(r, cfg), _opt_cfg(), data)
    try:
        # the trainer donates this state: `params` is not read again
        state = trainer.run(rng, start_state=init_train_state(
            rng, lambda _: params))
        del params, state
    finally:
        shutil.rmtree(SCRATCH, ignore_errors=True)
    abstract = jax.eval_shape(
        lambda r: init_train_state(r, lambda q: vit_init(q, cfg)), rng)
    kernel_in_step = _has_kernel(trainer.train_step.lower(
        abstract, data.batch(0)))
    hist = trainer.metrics_history
    losses = [h["total_loss"] for h in hist]
    _check(len(losses) == steps, f"{len(losses)} of {steps} steps logged")
    _check(all(math.isfinite(x) for x in losses), f"losses {losses}")
    # the trainer's own step 1 against the jnp path's loss and grad norm
    t_loss, t_norm = hist[0]["total_loss"], hist[0]["grad_norm"]
    out = {
        "train_step_hlo_tpu_custom_call": kernel_in_step,
        "losses": losses,
        "peak_bytes_in_use": _memory(jax.devices()[0], "peak_bytes_in_use"),
        "step1_kernel_vs_jnp": step1,
        "trainer_step1_vs_kernel_path": {
            "loss": [t_loss, kernel_err["loss_value"]],
            "grad_norm": [t_norm, kernel_err["grad_norm"]]},
    }
    _check(kernel_in_step, "the trainer's step holds no tpu_custom_call")
    _check(_rel(t_loss, kernel_err["loss_value"]) <= RTOL
           and _rel(t_norm, kernel_err["grad_norm"]) <= RTOL,
           f"the trainer's step 1 is not the checked kernel path's: {out}")
    return out


def _logits_at(cfg):
    """Jitted full causal forward (no cache, no kernel): the logits that
    predict the token after position ``i`` of one right-padded row."""
    import jax
    import jax.numpy as jnp

    from repro.models import lm_apply

    return jax.jit(lambda p, toks, i: lm_apply(p, cfg, toks)[0][0, i]
                   .astype(jnp.float32))


def _row(toks, max_len: int):
    import jax.numpy as jnp

    return jnp.asarray([list(toks) + [0] * (max_len - len(toks))], jnp.int32)


def _bf16_noise(fwd32, fwd16, params, toks, max_len: int) -> float:
    """The largest logit change the model's own bf16 arithmetic makes at
    the last position of ``toks``, against its f32 forward."""
    import jax.numpy as jnp

    row = _row(toks, max_len)
    return float(jnp.max(jnp.abs(fwd16(params, row, len(toks) - 1)
                                 - fwd32(params, row, len(toks) - 1))))


def _divergence(fwd32, fwd16, params, prompt, out_a, out_b, max_len: int):
    """Where two greedy streams part, the f32 reference forward's logit
    margin between the two tokens, and the model's bf16 noise at that
    position. The streams may part only where the margin is inside that
    noise: there the token is decided by rounding, not by the attention
    path."""
    step = next(i for i, (a, b) in enumerate(zip(out_a, out_b)) if a != b)
    toks = list(prompt) + list(out_a[:step])
    l32 = fwd32(params, _row(toks, max_len), len(toks) - 1)
    a, b = out_a[step], out_b[step]
    margin = float(abs(l32[a] - l32[b]))
    noise = _bf16_noise(fwd32, fwd16, params, toks, max_len)
    return {"step": step, "tokens": [a, b], "margin": margin,
            "bf16_noise": noise, "tie": margin <= noise}


def _shadow_gather(backend, cfg) -> dict:
    """Teacher-forced check of the kernel: before each decode step of the
    kernel ``backend``, the gather decode program runs on the same pool,
    tables, tokens and positions twice: in bf16 as served, and in f32 (f32
    activations and matmuls on the pool's values) as the reference.
    Nothing is donated, so the pool is left as it was. Returns the lists
    that collect, per step, each live row's relative logit error
    ||x - ref|| / ||ref|| of the kernel and of the bf16 gather program, and
    the largest |kernel - gather| logit difference (a diagnostic)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.serve.programs import make_decode_step_paged

    gather = make_decode_step_paged(cfg, use_kernel=False)
    gather32 = make_decode_step_paged(
        dataclasses.replace(cfg, dtype="float32"), use_kernel=False)

    def upcast(cache):
        return jax.tree.map(lambda a: a.astype(jnp.float32)
                            if jnp.issubdtype(a.dtype, jnp.floating)
                            else a, cache)

    @jax.jit
    def before(params, toks, pos, tables, cache):
        with jax.default_matmul_precision("float32"):
            ref = gather32(params, toks, pos, tables, upcast(cache))[0]
        bf16 = gather(params, toks, pos, tables, cache)[0]
        return ref[:, 0].astype(jnp.float32), bf16[:, 0].astype(jnp.float32)

    @jax.jit
    def errors(ref, bf16, logits, pos):
        live = pos >= 0
        logits = logits[:, 0].astype(jnp.float32)

        def rel(x):
            err = jnp.linalg.norm(x - ref, axis=-1) / jnp.linalg.norm(
                ref, axis=-1)
            return jnp.where(live, err, 0.0)

        gap = jnp.where(live[:, None], jnp.abs(logits - bf16), 0.0)
        return rel(logits), rel(bf16), jnp.max(gap)

    kernel_decode = backend.decode
    out = {"kernel": [], "gather": [], "kernel_vs_gather": []}

    def decode(params, toks, pos):
        # the kernel program donates the pool: shadow it first
        ref, bf16 = before(params, toks, pos, jnp.asarray(backend.tables),
                           backend.cache)
        logits = kernel_decode(params, toks, pos)
        for key, val in zip(out, errors(ref, bf16, logits, pos)):
            out[key].append(val)
        return logits

    backend.decode = decode
    return out


def _serve(cfg, params, prompts, max_new: int, max_len: int, block: int,
           use_kernel: bool) -> dict:
    """Greedy requests through a paged ``ServeEngine``; with the kernel,
    each decode step is shadowed by the gather program."""
    import jax.numpy as jnp
    import numpy as np

    from repro.serve import Request, ServeEngine

    eng = ServeEngine(cfg, params, batch_size=len(prompts), max_len=max_len,
                      backend="paged", block_size=block,
                      use_kernel=use_kernel)
    be = eng.backend
    shadow = _shadow_gather(be, cfg) if use_kernel else {}
    reqs = [Request(prompt=p, max_new_tokens=max_new) for p in prompts]
    for r in reqs:
        eng.submit(r)
    eng.run()
    _check(all(len(r.out) == max_new for r in reqs),
           f"lengths {[len(r.out) for r in reqs]}")
    decode = be._decode.lower(
        params, jnp.zeros((len(prompts), 1), jnp.int32),
        jnp.full((len(prompts),), -1, jnp.int32),
        jnp.asarray(be.tables), be.cache)
    return {"out": [r.out for r in reqs], "fallbacks": be.kernel_fallbacks,
            "custom_call": _has_kernel(decode),
            "shadow": {k: np.asarray(v) for k, v in shadow.items()}}


def serve_phase(cfg, prompt_lens, max_new: int, seed: int) -> dict:
    """Paged engine through the Pallas decode kernel, each step shadowed
    by the gather program on the same pool (teacher-forced); then the
    gather engine on the same greedy requests."""
    import dataclasses

    import jax
    import numpy as np

    from repro.models import lm_init

    params = lm_init(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, n).tolist()
               for n in prompt_lens]
    block = 16
    max_len = -(-(max(prompt_lens) + max_new) // block) * block

    k = _serve(cfg, params, prompts, max_new, max_len, block, True)
    _say(f"serve kernel: kernel_fallbacks={k['fallbacks']} "
         f"decode_hlo_tpu_custom_call={k['custom_call']}")
    _check(k["fallbacks"] == 0, "the paged kernel fell back to the gather "
           "path; its error is logged above")
    _check(k["custom_call"], "the kernel engine's decode program holds no "
           "tpu_custom_call")
    # The kernel and the gather program compute the same attention in
    # another order: against the f32 reference on the same pool, the
    # kernel's logits may be at most NOISE_FACTOR times as far off as the
    # gather program's, plus NOISE_FLOOR, at the worst (step, row) of each.
    # Attention that drops or mis-weights keys adds its own error on top.
    sh = k["shadow"]
    _check(len(sh["kernel"]) >= max_new - 1,
           f"{len(sh['kernel'])} decode steps shadowed")
    k_err, g_err = float(sh["kernel"].max()), float(sh["gather"].max())
    bound = NOISE_FACTOR * g_err + NOISE_FLOOR
    live = sh["gather"] > 0
    shadow = {"steps": len(sh["kernel"]), "kernel_err": k_err,
              "gather_err": g_err, "bound": bound,
              "paired_ratio_max": float(
                  (sh["kernel"][live] / sh["gather"][live]).max()),
              "kernel_vs_gather_max_abs": float(
                  sh["kernel_vs_gather"].max())}
    _check(k_err <= bound, "kernel decode logits beyond "
           f"{NOISE_FACTOR} x the gather program's error + {NOISE_FLOOR} "
           f"against the f32 reference: {shadow}")
    fwd32 = _logits_at(dataclasses.replace(cfg, dtype="float32"))
    fwd16 = _logits_at(cfg)
    g = _serve(cfg, params, prompts, max_new, max_len, block, False)
    _say(f"serve gather: decode_hlo_tpu_custom_call={g['custom_call']}")
    k_out, g_out = k["out"], g["out"]
    same = sum(a == b for ka, ga in zip(k_out, g_out)
               for a, b in zip(ka, ga))
    parts = [dict(request=i, prompt_len=len(prompts[i]),
                  **_divergence(fwd32, fwd16, params, prompts[i], k_out[i],
                                g_out[i], max_len))
             for i in range(len(prompts)) if k_out[i] != g_out[i]]
    out = {"prompt_lens": list(prompt_lens),
           "shadow": shadow,
           "streams_identical": len(prompts) - len(parts),
           "tokens_agree": same, "tokens": len(prompts) * max_new,
           "divergences": parts}
    # diagnostic: free-running streams may part only at bf16 ties
    _check(all(p["tie"] for p in parts),
           f"kernel and gather streams part outside bf16 noise: {parts}")
    return out


def mesh_phase(big_cfg, cmp_cfg, batch_size: int, steps: int,
               seed: int) -> dict:
    """The sharded train step on a (data=2, model=2) mesh, and its first
    step against one device alone."""
    import gc

    import jax

    from repro.distributed import (ShardingOptions, batch_sharding,
                                   tree_shardings, use_mesh)
    from repro.launch.mesh import make_host_mesh
    from repro.models import vit_init, vit_loss
    from repro.train.step import (init_train_state, make_train_step,
                                  state_shardings)

    mesh = make_host_mesh(model=2)
    devices = list(mesh.devices.flat)
    rng = jax.random.PRNGKey(seed)
    out = {"mesh": dict(mesh.shape)}

    def batch_sh(batch):
        return {k: batch_sharding(mesh, v.ndim) for k, v in batch.items()}

    with use_mesh(mesh):
        data = _images(big_cfg, batch_size, seed)
        abstract = jax.eval_shape(
            lambda r: init_train_state(r, lambda q: vit_init(q, big_cfg)),
            rng)
        st_sh = state_shardings(mesh, abstract, ShardingOptions())
        # params and Adam moments are made on their shards: made on one
        # device, the params (7.3 GB) and the stacking of their layers
        # exceed one chip
        state = jax.jit(lambda r: init_train_state(
            r, lambda q: _init_params(big_cfg, q)), out_shardings=st_sh)(rng)
        step = jax.jit(
            make_train_step(lambda p, b: vit_loss(p, big_cfg, b), _opt_cfg()),
            in_shardings=(st_sh, batch_sh(data.batch(0))),
            out_shardings=(st_sh, None), donate_argnums=(0,))
        losses = []
        for i in range(steps):
            state, metrics = step(state, data.batch(i))
            losses.append(float(metrics["total_loss"]))
        total = sum(x.nbytes for x in jax.tree_util.tree_leaves(state))
        held = collections.Counter()
        for leaf in jax.tree_util.tree_leaves(state):
            for shard in leaf.addressable_shards:
                held[shard.device.id] += shard.data.nbytes
        out["big"] = {
            "arch": big_cfg.name, "losses": losses, "state_bytes": total,
            "state_bytes_per_device": [held[d.id] for d in devices],
            "bytes_in_use_per_device": [
                _memory(d, "bytes_in_use") for d in devices],
        }
        _check(all(math.isfinite(x) for x in losses), f"losses {losses}")
        _check(max(held.values()) < total / 2,
               f"one device holds most of the state: {out['big']}")
        del state, metrics
    gc.collect()

    # one device: outside the mesh context, whose constraints would
    # spread the activations over all four
    params = _init_params(cmp_cfg, rng)
    batch = _images(cmp_cfg, batch_size, seed).batch(0)
    ref = _reference(cmp_cfg, params, batch)
    one_err = _errors(_grads(cmp_cfg, False), params, batch, *ref)
    with use_mesh(mesh):
        p_sh = tree_shardings(mesh, params, ShardingOptions())
        b_sh = batch_sh(batch)
        mesh_err = _errors(
            _grads(cmp_cfg, False), jax.device_put(params, p_sh),
            jax.device_put(batch, b_sh), ref[0], jax.device_put(ref[1], p_sh),
            in_shardings=(p_sh, b_sh, p_sh))
    out["step1_mesh_vs_one_device"] = dict(
        arch=cmp_cfg.name, **_within_noise(mesh_err, one_err))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded train step on four chips")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and images")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("chip_smoke: src/repro is not next to this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))

    from repro.launch.cache import enable_compile_cache

    cache_dir = enable_compile_cache(ROOT)

    import jax
    from jax import monitoring

    stats = CompileStats()
    stats.install(monitoring)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU; JAX found {dev.platform} devices",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devices)} devices", file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.kernels.tuning import KernelConfig

    _check(not KernelConfig().resolve_interpret(),
           "Pallas kernels would run in interpret mode on this device")
    # the paged backend logs a kernel failure before falling back
    logging.basicConfig(level=logging.WARNING)
    _say(f"device platform={dev.platform} kind={dev.device_kind} "
         f"count={len(devices)} jax={jax.__version__} "
         f"compile_cache={cache_dir}")

    if args.chips == 4:
        phases = [("mesh", lambda: mesh_phase(
            get_config("soft-moe-s/14-256e"),
            get_config("soft-moe-s/16-128e"), MESH_BATCH, 3, args.seed))]
    else:
        phases = [
            ("train", lambda: train_phase(
                get_config("soft-moe-s/16-128e"), TRAIN_BATCH, 5,
                args.seed)),
            ("serve", lambda: serve_phase(
                get_config("qwen2-0.5b"),
                [16, 86, 157, 228, 299, 370, 441, 512], 32, args.seed)),
        ]
    for name, run in phases:
        t0, c0 = time.perf_counter(), stats.seconds
        result = run()
        _say(f"phase {name} ok: " + json.dumps(result))
        _say(f"phase {name} wall_s={time.perf_counter() - t0} "
             f"backend_compile_s={stats.seconds - c0}")
    _say("compile_cache " + json.dumps(dict(stats.events)))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
