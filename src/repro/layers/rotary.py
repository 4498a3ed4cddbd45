"""Rotary position embeddings (interleaved-free, llama-style half-rotation)."""
from __future__ import annotations

import jax.numpy as jnp


def rope_freqs(head_dim: int, theta: float):
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponents)  # (head_dim/2,)


def rope_cos_sin(positions, head_dim: int, theta: float):
    """(cos, sin) of each position's angles: (..., seq, head_dim/2) f32."""
    inv = rope_freqs(head_dim, theta)  # (hd/2,)
    ang = positions[..., :, None].astype(jnp.float32) * inv  # (..., seq, hd/2)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq)."""
    cos, sin = rope_cos_sin(positions, x.shape[-1], theta)
    cos = cos[..., :, None, :]  # (..., seq, 1, hd/2)
    sin = sin[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)
