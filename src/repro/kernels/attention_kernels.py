"""Fused full-sequence attention Pallas TPU kernel: RoPE, QKᵀ, softmax
and P·V in one pass, for the cache-less, non-causal, window-less case
(the ViT encoder's self-attention).

Why a kernel: ``layers.attention._attend`` materialises the logits as a
``[batch, heads, S, S]`` f32 tensor in HBM, then softmax and the P·V
einsum read and write it pass after pass. At ViT-S/16 inference (batch
512, 6 heads, 196 tokens) that is 472 MB a layer, about 630 MB once the
TPU pads 196 to 256 lanes. Here the whole key axis of one (image, head)
fits in one VMEM tile, so the kernel needs no online softmax: each grid
step computes its scores, their row max, exp and row sum, and P·V, and
writes only the output.

Layout: ``qkv`` is ``[b, S, 3 * heads * head_dim]``, q, k and v side by
side as one projection gives them, read through three index maps; the
output is ``[b, S, heads * head_dim]``, as the output projection reads
it. A ``[b, h, S, head_dim]`` layout would pad head_dim 64 to 128 lanes
in HBM and make XLA relayout and pad every operand before the call.
Grid ``(batch blocks, head blocks)``: a step holds ``block_batch``
images and one 128-lane block of heads (two heads of 64). Each head of
the block takes its scores against the whole block with the other
heads' query lanes zeroed, which leaves its dot product exact, and keeps
its own lanes of P·V. The key axis stays at S (196): Mosaic pads it to
256 lanes inside the tile and keeps the padding out of the row max, the
row sum and the P·V contraction, so every real key is attended and no
padded one is.

RoPE is applied to the q and k tiles in VMEM, in f32 as ``apply_rope``
computes it, from (S, lanes) cos/sin tables: outside, its half swap made
XLA relayout q and k twice.

Precision: QKᵀ and P·V take operands in the input dtype (bf16 for the
ViT configurations) with f32 accumulation; row max, exp and row sum are
f32, and the output is divided by the f32 row sum before its cast to the
input dtype.

Forward only: ``layers.attention._attend_full`` differentiates a call
through the plain path the kernel replaces. ``fits`` says which shapes
the kernel takes. On the CPU it runs in interpret mode (the lazy
``KernelConfig.resolve_interpret`` policy) against ``_attend``;
tests/test_tpu_compile.py compiles it for a described v5e.
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tuning import KernelConfig

# Bytes of f32 scores one grid step may hold: with their exp, its bf16
# copy and the double-buffered q/k/v/out tiles that stays well inside the
# 16 MiB of VMEM a v5e kernel gets by default.
SCORE_BUDGET = 2 * 1024 * 1024
_MAX_BLOCK_BATCH = 8


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _score_bytes(s: int) -> int:
    """VMEM bytes of one (image, head)'s f32 scores in (8, 128) tiles."""
    return _round_up(s, 8) * _round_up(s, 128) * 4


def _lanes(head_dim: int) -> int:
    """Lanes of one grid step's block of heads: 128 (two heads of 64),
    or one head whose width is a multiple of 128; 0 if neither."""
    if 128 % head_dim == 0:
        return 128
    return head_dim if head_dim % 128 == 0 else 0


def fits(s: int, num_heads: int, head_dim: int) -> bool:
    """Whether the kernel takes this self-attention: its blocks of heads
    tile the width in whole 128-lane multiples, and one (image, head)'s
    f32 scores fit ``SCORE_BUDGET``."""
    lanes = _lanes(head_dim)
    return (lanes > 0 and num_heads * head_dim % lanes == 0
            and _score_bytes(s) <= SCORE_BUDGET)


def block_batch(b: int, s: int) -> int:
    """Images per grid step: the largest divisor of ``b`` up to 8 whose
    scores fit ``SCORE_BUDGET`` (a divisor, so no block is partial)."""
    most = min(_MAX_BLOCK_BATCH, SCORE_BUDGET // _score_bytes(s))
    return next(c for c in range(most, 0, -1) if b % c == 0)


def _rope(x, cos, sin, half: int):
    """``apply_rope`` on an f32 tile of whole heads along its last axis:
    x·cos + rot(x)·sin, where rot turns each head's halves (x1, x2) into
    (−x2, x1) and the tables repeat each head's angles in both halves."""
    n, ax = x.shape[-1], x.ndim - 1
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, ax)
    rot = jnp.where(lane % (2 * half) < half, -pltpu.roll(x, n - half, ax),
                    pltpu.roll(x, half, ax))
    return x * cos + rot * sin


def _tables(cos, sin, heads: int):
    """(S, d/2) angle tables -> (S, heads·d), one head's pattern a head."""
    return tuple(jnp.tile(jnp.concatenate([t, t], -1), (1, heads))
                 for t in (cos, sin))


def _mha_fwd_kernel(q_ref, k_ref, v_ref, cos_ref, sin_ref, o_ref, *,
                    head_dim, scale):
    cos, sin = cos_ref[...], sin_ref[...]  # (S, lanes) f32

    def rope(ref):
        x = _rope(ref[...].astype(jnp.float32), cos, sin, head_dim // 2)
        return x.astype(ref.dtype)

    q, k, v = rope(q_ref), rope(k_ref), v_ref[...]  # (bb, S, lanes)
    head = jax.lax.broadcasted_iota(jnp.int32, q.shape, 2) // head_dim
    out = jnp.zeros(q.shape, jnp.float32)
    for hh in range(q.shape[-1] // head_dim):
        mine = head == hh
        # (bb, S, S): batch over images, contract this head's lanes.
        s = scale * jax.lax.dot_general(
            jnp.where(mine, q, 0), k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        p = jnp.exp(s - s.max(axis=-1, keepdims=True))
        o = jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)  # (bb, S, lanes)
        out = jnp.where(mine, o / p.sum(axis=-1, keepdims=True), out)
    o_ref[...] = out.astype(o_ref.dtype)


def mha_full(qkv, cos, sin, *, num_heads: int):
    """Full-sequence self-attention through ``mha_full_fwd``, RoPE
    included; forward only (``layers.attention._attend_full``
    differentiates a call through the plain path it replaces).

    qkv: (b, S, 3·h·d), q, k and v side by side as one projection gives
    them, before RoPE; cos, sin: (S, d/2), ``layers.rotary.rope_cos_sin``
    of the positions. No mask: every key is a real token (the caller's
    contract). Returns (b, S, h·d) in qkv.dtype."""
    b, s, width = qkv.shape[0], qkv.shape[1], qkv.shape[2] // 3
    d = width // num_heads
    assert fits(s, num_heads, d), (qkv.shape, num_heads)
    lanes = _lanes(d)
    nb = width // lanes
    bb = block_batch(b, s)

    def part(n):  # block j of q (n = 0), k (1) or v (2)
        return pl.BlockSpec((bb, s, lanes), lambda i, j: (i, 0, n * nb + j))

    table = pl.BlockSpec((s, lanes), lambda i, j: (0, 0))
    return pl.pallas_call(
        functools.partial(_mha_fwd_kernel, head_dim=d,
                          scale=1.0 / float(d) ** 0.5),
        grid=(b // bb, nb),
        in_specs=[part(0), part(1), part(2), table, table],
        out_specs=part(0),
        out_shape=jax.ShapeDtypeStruct((b, s, width), qkv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="mha_full_fwd",
        interpret=KernelConfig().resolve_interpret(),
    )(qkv, qkv, qkv, *_tables(cos, sin, lanes // d))
