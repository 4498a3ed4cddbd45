"""The reference's first training steps, and the readings both sides give.

The reference takes the seed's weights and batches, computes the loss and
gradient with ``ref_vit`` in float32 at ``highest`` precision, clips by the
global norm and applies a plain AdamW, all as the configuration file's
``trainer.optimizer`` states. Its state is donated from step to step, so it
holds one copy of parameters, moments and gradient at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import ref_vit, weights


def leaf_norms(tree) -> dict:
    """``{leaf path: ||leaf||}`` (f32 norms, read on the host)."""
    norms = jax.jit(lambda t: jax.tree.map(
        lambda a: jnp.linalg.norm(a.astype(jnp.float32).ravel()), t))(tree)
    return {k: float(v) for k, v in weights.leaf_dict(norms).items()}


@functools.partial(jax.jit, static_argnums=(3,))
def _change_norm(p, key, h, scale):
    p0 = weights.leaf_value(key, h, p.shape, scale)
    return jnp.linalg.norm((p.astype(jnp.float32) - p0).ravel())


def change_norms(params, seed: int) -> dict:
    """``{leaf path: ||p - p0||}``, with each leaf's initial value made
    again from the seed one leaf at a time."""
    key = weights.seed_key(seed)
    return {k: float(_change_norm(v, key, np.uint32(weights.path_hash(k)),
                                  weights.is_scale(k)))
            for k, v in weights.leaf_dict(params).items()}


def make_step(cfg: dict, control: bool = False, half_batch: bool = False):
    """The reference's jitted step ``(p, mu, nu, t, batch) -> (p, mu, nu,
    loss, clipped gradient norms)``; ``p``, ``mu``, ``nu`` are donated.
    ``control`` computes it in float8 (``ref_vit``); ``half_batch`` takes
    the loss over the first half of every batch, a planted fault."""
    opt = cfg["trainer"]["optimizer"]
    b1, b2 = opt["b1"], opt["b2"]

    def loss_fn(p, batch):
        if half_batch:
            batch = jax.tree.map(lambda a: a[: a.shape[0] // 2], batch)
        return ref_vit.loss(p, batch, cfg, control)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, mu, nu, t, batch):
        loss, g = jax.value_and_grad(loss_fn)(p, batch)
        leaves = jax.tree.leaves(g)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in leaves))
        g = jax.tree.map(lambda x: x * jnp.minimum(
            1.0, opt["grad_clip_norm"] / jnp.maximum(gnorm, 1e-9)), g)
        norms = jax.tree.map(lambda x: jnp.linalg.norm(x.ravel()), g)
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        p = jax.tree.map(
            lambda w, m, v: w - opt["peak_lr"] * (
                (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
                + opt["weight_decay"] * w), p, mu, nu)
        return p, mu, nu, loss, norms

    return step


def run(cfg: dict, seed: int, steps: int, control: bool = False,
        half_batch: bool = False) -> dict:
    """The reference's readings over the first ``steps`` steps (see
    ``make_step`` for the options)."""
    tr = cfg["trainer"]
    shapes = ref_vit.param_shapes(cfg)
    image_key = weights.seed_key(seed, stream=1)
    make_batch = jax.jit(functools.partial(
        weights.images, batch=tr["batch"], num_patches=cfg["num_patches"],
        patch_dim=cfg["patch_dim"], num_classes=cfg["num_classes"]))
    step = make_step(cfg, control, half_batch)
    with jax.default_matmul_precision("highest"):
        p = weights.make_params(shapes, seed)
        zeros = jax.jit(lambda q: jax.tree.map(jnp.zeros_like, q))
        mu, nu = zeros(p), zeros(p)
        losses, grad1 = [], None
        for i in range(steps):
            p, mu, nu, loss, norms = step(
                p, mu, nu, jnp.float32(i + 1), make_batch(image_key, i))
            losses.append(float(loss))
            if i == 0:
                grad1 = {k: float(v) for k, v in
                         weights.leaf_dict(norms).items()}
        del mu, nu
        change = change_norms(p, seed)
    return {"losses": losses, "grad1": grad1, "change": change}
