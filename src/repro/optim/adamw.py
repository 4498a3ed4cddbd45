"""AdamW with fp32 master params and global-norm clipping — pure pytrees
(no optax in this environment). Moments inherit the parameter shardings, so
under FSDP they are sharded over (data × model) like the master params.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class OptimizerConfig:
    peak_lr: float = 1e-3
    schedule: str = "rsqrt"  # "rsqrt" | "cosine" | "linear" | "constant"
    warmup_steps: int = 10_000
    total_steps: int = 300_000
    cooldown_steps: int = 50_000  # paper: linear cooldown tail
    timescale: float = 1e5  # rsqrt timescale (paper App. E)
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 1e-4
    grad_clip_norm: float = 1.0


def adamw_init(params):
    zeros = lambda p: jnp.zeros_like(p)  # noqa: E731
    return {
        "mu": jax.tree_util.tree_map(zeros, params),
        "nu": jax.tree_util.tree_map(zeros, params),
        "step": jnp.zeros((), jnp.int32),
    }


def global_norm(tree):
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x.astype(jnp.float32))) for x in leaves)
    )


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree_util.tree_map(lambda g: g * scale, grads), norm


def adamw_update(grads, opt_state, params, cfg: OptimizerConfig,
                 lr=None):
    step = opt_state["step"] + 1
    lr = schedule_lr(cfg, step) if lr is None else lr
    with jax.named_scope("clip"):
        grads, gnorm = clip_by_global_norm(grads, cfg.grad_clip_norm)
    b1, b2 = cfg.b1, cfg.b2

    def upd(g, m, v, p):
        g = g.astype(jnp.float32)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        mhat = m / (1 - b1 ** step.astype(jnp.float32))
        vhat = v / (1 - b2 ** step.astype(jnp.float32))
        delta = mhat / (jnp.sqrt(vhat) + cfg.eps) + cfg.weight_decay * p
        return p - lr * delta, m, v

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(opt_state["mu"])
    flat_v = treedef.flatten_up_to(opt_state["nu"])
    with jax.named_scope("adamw"):
        out = [upd(g, m, v, p)
               for g, m, v, p in zip(flat_g, flat_m, flat_v, flat_p)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    new_state = {"mu": new_m, "nu": new_v, "step": step}
    return new_p, new_state, {"grad_norm": gnorm, "lr": lr}


def schedule_lr(cfg: OptimizerConfig, step):
    """Paper setup: inverse-sqrt decay with linear warmup and a linear
    cooldown tail (§3.3 / App. E); cosine/linear/constant also provided."""
    s = step.astype(jnp.float32)
    warm = jnp.minimum(s / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    if cfg.schedule == "rsqrt":
        base = jnp.sqrt(cfg.timescale) / jnp.sqrt(jnp.maximum(s, cfg.timescale))
    elif cfg.schedule == "cosine":
        frac = jnp.clip(s / cfg.total_steps, 0.0, 1.0)
        base = 0.5 * (1 + jnp.cos(jnp.pi * frac))
    elif cfg.schedule == "linear":
        base = 1.0 - jnp.clip(s / cfg.total_steps, 0.0, 1.0)
    else:
        base = jnp.ones(())
    # linear cooldown tail to zero over the last `cooldown_steps`
    tail = jnp.clip(
        (cfg.total_steps - s) / jnp.maximum(cfg.cooldown_steps, 1), 0.0, 1.0
    )
    return cfg.peak_lr * warm * base * tail
