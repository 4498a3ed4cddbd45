"""Jitted train step: value_and_grad + microbatch accumulation + AdamW.

The step is built once per (config, mesh) and jitted with explicit
in/out shardings; gradient accumulation scans over microbatches so the
activation memory is that of ONE microbatch (the standard fit-large-batch
trick); remat inside the model bounds per-layer activations.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..optim import OptimizerConfig, adamw_init, adamw_update


def init_train_state(rng, init_fn, zero1: bool = False):
    """Default (ZeRO-3/FSDP): fp32 params double as the master copy and
    are sharded over (data × model); every use all-gathers them.

    zero1=True (ZeRO-1/2): bf16 compute params replicated over data
    (sharded over model only) + fp32 master/moments sharded over
    (data × model). Trades +params_bf16/data_shards memory for removing
    the per-layer per-pass FSDP all-gathers — at qwen2-72b:train_4k those
    are ~914GB/device/step, 2.4× the roofline compute time."""
    params = init_fn(rng)
    if not zero1:
        return {"params": params, "opt": adamw_init(params)}

    def to_bf16(p):
        if jnp.issubdtype(p.dtype, jnp.floating):
            return p.astype(jnp.bfloat16)
        return p

    return {
        "params": jax.tree_util.tree_map(to_bf16, params),
        "master": params,
        "opt": adamw_init(params),
    }


def make_train_step(loss_fn: Callable, opt_cfg: OptimizerConfig,
                    microbatches: int = 1):
    """loss_fn(params, batch) -> (loss, metrics). Returns step(state, batch).

    A telemetry-enabled loss (``lm_loss(..., telemetry=True)``) nests the
    model-interior stats pytree under ``metrics["telemetry"]``; it rides
    the same microbatch aggregation below (``max_*`` leaves take the step
    max, the rest the mean) and the Trainer flattens it at log time —
    nothing here special-cases it."""

    def grads_of(params, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch
        )
        return loss, metrics, grads

    def accumulate(params, batch):
        def reshape(x):
            return x.reshape((microbatches, x.shape[0] // microbatches)
                             + x.shape[1:])

        mbs = jax.tree_util.tree_map(reshape, batch)

        def body(carry, mb):
            loss_acc, grads_acc = carry
            loss, metrics, grads = grads_of(params, mb)
            grads_acc = jax.tree_util.tree_map(jnp.add, grads_acc, grads)
            return (loss_acc + loss, grads_acc), metrics

        zero_grads = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params
        )
        (loss_sum, grads), metrics = jax.lax.scan(
            body, (jnp.zeros(()), zero_grads), mbs
        )
        grads = jax.tree_util.tree_map(lambda g: g / microbatches, grads)
        # Aggregate per-microbatch metrics over the scan axis: max-type
        # inspection stats (e.g. the Soft-MoE `max_combine` softmax-collapse
        # probe) take the step max, everything else the mean — keeping only
        # the last microbatch (the old behavior) under-reports both.
        # Keyed per LEAF path so nested metric pytrees aggregate correctly.
        def agg(path, v):
            leaf = path[-1] if path else None
            name = str(getattr(leaf, "key", getattr(leaf, "name", "")))
            return v.max(axis=0) if name.startswith("max_") else v.mean(axis=0)

        metrics = jax.tree_util.tree_map_with_path(agg, metrics)
        return loss_sum / microbatches, metrics, grads

    def train_step(state, batch):
        if microbatches > 1:
            loss, metrics, grads = accumulate(state["params"], batch)
        else:
            loss, metrics, grads = grads_of(state["params"], batch)
        with jax.named_scope("optimizer"):
            if "master" in state:  # ZeRO-1: update the sharded fp32
                # master, then re-broadcast bf16 compute params. The
                # grads->master resharding lowers to a reduce-scatter; the
                # cast-back to the replicated layout lowers to one
                # all-gather per step (vs one per layer per pass under
                # FSDP).
                grads = _match_sharding(grads, state["master"])
                new_master, new_opt, opt_metrics = adamw_update(
                    grads, state["opt"], state["master"], opt_cfg
                )
                new_params = jax.tree_util.tree_map(
                    lambda m, p: m.astype(p.dtype), new_master,
                    state["params"]
                )
                new_state = {"params": new_params, "master": new_master,
                             "opt": new_opt}
            else:
                new_params, new_opt, opt_metrics = adamw_update(
                    grads, state["opt"], state["params"], opt_cfg
                )
                new_state = {"params": new_params, "opt": new_opt}
        metrics = dict(metrics)
        metrics.update(opt_metrics)
        metrics["total_loss"] = loss
        return new_state, metrics

    return train_step


def _match_sharding(grads, master):
    """Pin grads to the master's (data×model)-sharded layout — under jit
    the cross-data grad sync then lowers as a reduce-scatter rather than
    an all-reduce (each data shard only needs its slice)."""
    from ..distributed.api import current_mesh
    from ..distributed.sharding import tree_shardings

    mesh = current_mesh()
    if mesh is None:
        return grads
    sh = tree_shardings(mesh, master)
    return jax.tree_util.tree_map(
        lambda g, s: jax.lax.with_sharding_constraint(g, s), grads, sh
    )


def jit_train_step(train_step, mesh, state_shardings, batch_shardings):
    """Pin state/batch shardings; donate the state buffer (in-place update
    on device — required to fit two copies of a 72B state)."""
    return jax.jit(
        train_step,
        in_shardings=(state_shardings, batch_shardings),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,),
    )


def state_shardings(mesh, state, opts=None):
    """Shard optimizer moments exactly like their params (FSDP included).
    ZeRO-1 states ('master' present): compute params shard over model
    only; master + moments keep the full (data × model) sharding."""
    import dataclasses

    from ..distributed.sharding import ShardingOptions, tree_shardings
    from jax.sharding import NamedSharding, PartitionSpec as P

    opts = opts or ShardingOptions()
    if "master" in state:
        compute_sh = tree_shardings(
            mesh, state["params"], dataclasses.replace(opts, fsdp=False)
        )
        master_sh = tree_shardings(mesh, state["master"], opts)
        return {
            "params": compute_sh,
            "master": master_sh,
            "opt": {
                "mu": master_sh,
                "nu": master_sh,
                "step": NamedSharding(mesh, P()),
            },
        }
    param_sh = tree_shardings(mesh, state["params"], opts)
    return {
        "params": param_sh,
        "opt": {
            "mu": param_sh,
            "nu": param_sh,
            "step": NamedSharding(mesh, P()),
        },
    }
