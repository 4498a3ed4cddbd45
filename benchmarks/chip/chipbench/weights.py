"""Weights and inputs made from ``--seed``, on the device.

Every leaf has its own key, folded from the seed and the leaf's path, so
one leaf can be made again alone (the parameter-change check regenerates
the initial value of one leaf at a time) and the whole tree is made in
one jitted call, the same program for every seed. Seeds are whole numbers of any size: both 32-bit halves
of the low 64 bits go into the key.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

STD = 0.02  # matrices, biases and embeddings; norm scales are 1 + 0.1 N


def seed_key(seed: int, stream: int = 0):
    s = int(seed) % (1 << 64)
    k = jax.random.PRNGKey(stream)
    k = jax.random.fold_in(k, np.uint32(s >> 32))
    return jax.random.fold_in(k, np.uint32(s & 0xFFFFFFFF))


def path_hash(path: str) -> int:
    return zlib.crc32(path.encode())


def is_scale(path: str) -> bool:
    return path.rsplit("/", 1)[-1] == "scale"


def leaf_value(key, h, shape: tuple, scale: bool):
    """The initial value of one leaf; ``h`` is its ``path_hash`` (may be
    traced, so leaves of one shape share a program)."""
    z = jax.random.normal(jax.random.fold_in(key, h), shape, jnp.float32)
    return 1.0 + 0.1 * z if scale else STD * z


def _build(shapes, key, prefix: str = ""):
    if isinstance(shapes, dict):
        return {k: _build(v, key, f"{prefix}/{k}" if prefix else k)
                for k, v in shapes.items()}
    if isinstance(shapes, list):
        return [_build(v, key, f"{prefix}/{i}" if prefix else str(i))
                for i, v in enumerate(shapes)]
    return leaf_value(key, np.uint32(path_hash(prefix)), tuple(shapes),
                      is_scale(prefix))


def make_params(shapes, seed: int, then=None):
    """The seed's whole f32 parameter tree, made on the device in one
    jitted call (passed through ``then`` inside it, if given). The key is
    the program's argument, so every seed runs the same compiled
    program."""
    then = then or (lambda p: p)
    return jax.jit(lambda k: then(_build(shapes, k)))(seed_key(seed))


def images(key, step, batch: int, num_patches: int, patch_dim: int,
           num_classes: int) -> dict:
    """Batch ``step`` (may be traced) of the image stream of ``key``
    (``seed_key(seed, stream=1)``): standard normal patches and uniform
    labels, all rows distinct."""
    k = jax.random.fold_in(key, step)
    kp, kl = jax.random.split(k)
    return {"patches": jax.random.normal(
                kp, (batch, num_patches, patch_dim), jnp.float32),
            "labels": jax.random.randint(kl, (batch,), 0, num_classes,
                                         jnp.int32)}


def path_str(keypath) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in keypath)


def leaf_dict(tree) -> dict:
    """``{leaf path: leaf}``, paths written as ``make_params`` names them."""
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {path_str(p): v for p, v in leaves}
