"""Flash-style Pallas decode-attention kernel over the paged KV pool.

Why a kernel: the jnp paged decode path (`layers.attention._paged_view`)
materializes a per-row (B, blocks_per_row * block_size, ...) KV view in
HBM on EVERY decode step — gather-write the view, then read it all back
in the attend — before masking throws most of it away. That is the same
HBM-traffic sin the PR 1 routing kernels eliminated for Soft-MoE
dispatch/combine, and at serving scale decode attention is pure
bandwidth: the row view triples the bytes touched per step (gather read
+ view write + attend read vs streaming the pool tiles once).

This kernel consumes the block pool **in place**. The grid is
``(batch_row, kv_tile)`` and the block tables ride in as a scalar-
prefetch operand (``pltpu.PrefetchScalarGridSpec``), so the KV
BlockSpec's index map dereferences ``tables[b, tile]`` and the pipeline
DMAs exactly one physical (block_size, kv_heads, head_dim) tile from the
(num_blocks, block_size, ...) pool into VMEM per step — logical order,
no intermediate row view anywhere. Per row the kernel keeps online
softmax state — running (max, denom) per head plus an (heads, v_dim)
accumulator — exactly the flash-attention decode recurrence, and every
masking rule of the gather path is applied *inside* the tile:

  * ``pos < 0`` pool entries (never written / invalidated) drop — the
    reserved null block 0 contributes nothing however often a sparse
    table points at it;
  * causality (``pos <= q_pos``) and the sliding-window term
    ``(pos > q_pos - window) | is_global`` match ``make_mask``;
  * inactive rows (``q_pos < 0``) mask every key; the safe-divide
    emits zeros for them (the engine ignores those logits).

GQA grouping is native: q is viewed as (kv_groups, rep, head_dim) and
both dots batch over the group axis, so K/V tiles are fetched once per
row regardless of the query-head fan-out. MLA decode and chunked-prefill
calls keep the gather fallback (`attention.py` routes only GQA s==1
decode here); the latent-cache kernel is a recorded follow-up.

Tiling: one grid step consumes ``paged_block_kv`` rows of a pool block
(``tuning.paged_config`` — whole block by default, subdivided when
``block_size`` exceeds the VMEM-friendly 128). The last dim of a KV tile
is ``head_dim`` (< 128 on most configs), so lanes are padded on real
TPUs — acceptable for a bandwidth-bound decode kernel whose tiles are
resident for exactly one recurrence step. On the CPU the tests run it in
interpret mode against the gather path (the lazy
``KernelConfig.resolve_interpret`` policy, same as the routing kernels);
tests/test_tpu_compile.py compiles it for a described v5e, and
chip_smoke.py serves through it on one.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tuning import KernelConfig, paged_config

_NEG = -1e30


def _paged_decode_kernel(tables_ref, qpos_ref, q_ref, k_ref, v_ref, pos_ref,
                         out_ref, acc, mx, den, *, groups, rep, causal,
                         window, is_global, scale, dt):
    """One grid step: fold KV tile ``tables[b, jt]`` into row b's online
    softmax state. Grid (batch, kv_tiles); scratch persists across the
    inner kv_tile axis and re-initializes at tile 0 of each row."""
    b, jt = pl.program_id(0), pl.program_id(1)
    nt = pl.num_programs(1)

    @pl.when(jt == 0)
    def _init_row():
        acc[...] = jnp.zeros_like(acc)
        mx[...] = jnp.full_like(mx, _NEG)
        den[...] = jnp.zeros_like(den)

    q = q_ref[0].astype(dt)      # (H, Dk)
    k = k_ref[0].astype(dt)      # (bkv, G, Dk)
    v = v_ref[0].astype(dt)      # (bkv, G, Dv)
    kp = pos_ref[0, 0]           # (bkv,) int32; -1 = invalid
    qp = qpos_ref[b]             # scalar; < 0 = inactive row

    d = q.shape[-1]
    qg = q.reshape(groups, rep, d)
    # logits: (G, rep, bkv) — batch over kv groups, contract head_dim.
    s = scale * jax.lax.dot_general(
        qg, k, (((2,), (2,)), ((0,), (1,))), preferred_element_type=dt
    )
    ok = kp >= 0
    if causal:
        ok = ok & (kp <= qp)
    if window is not None:
        ok = ok & ((kp > qp - window) | is_global)
    s = jnp.where(ok[None, None, :], s, _NEG)

    m_old = mx[...]
    m_new = jnp.maximum(m_old, s.max(axis=-1))
    corr = jnp.exp(m_old - m_new)
    # Zero masked lanes explicitly: while no valid key has been seen the
    # running max is still _NEG and exp(_NEG - _NEG) would count masked
    # keys as weight 1 — fully-masked (inactive) rows must end with
    # denom 0, not a uniform average.
    p = jnp.where(ok[None, None, :], jnp.exp(s - m_new[..., None]), 0.0)
    den[...] = den[...] * corr + p.sum(axis=-1)
    mx[...] = m_new
    # (G, rep, Dv) += p @ v-tile, batched over groups.
    acc[...] = acc[...] * corr[..., None] + jax.lax.dot_general(
        p, v, (((2,), (0,)), ((0,), (1,))), preferred_element_type=dt
    )

    @pl.when(jt == nt - 1)
    def _finish_row():
        # Fully-masked rows (q_pos < 0, or an all-null table) have
        # denom 0: the safe divide returns zeros, never NaN.
        out = acc[...] / jnp.maximum(den[...], 1e-30)[..., None]
        out_ref[0] = out.reshape(groups * rep, -1).astype(out_ref.dtype)


def paged_decode_attend(q, k_pool, v_pool, pos_pool, tables, q_pos, *,
                        causal: bool = True, window: Optional[int] = None,
                        is_global: bool = True,
                        scale: Optional[float] = None,
                        cfg: Optional[KernelConfig] = None):
    """Decode attention straight off the paged pool.

    q: (B, H, Dk) one query per row; k_pool/v_pool:
    (num_blocks, block_size, G, D*) shared physical pool; pos_pool:
    (num_blocks, block_size) int32 positions (-1 invalid); tables:
    (B, blocks_per_row) int32 physical block ids (0 = null block);
    q_pos: (B,) int32 absolute positions (-1 = inactive row).
    Returns (B, H, Dv) in q.dtype. Numerics match gathering the row view
    and running the dense masked softmax (checked in
    tests/test_paged_attention_kernel.py).
    """
    b, h, d = q.shape
    _, block_size, groups, dk = k_pool.shape
    dv = v_pool.shape[-1]
    nb = tables.shape[1]
    assert h % groups == 0, (h, groups)
    rep = h // groups
    scale = scale if scale is not None else 1.0 / float(d) ** 0.5
    cfg = cfg if cfg is not None else paged_config(block_size)
    bkv = cfg.paged_block_kv or block_size
    assert block_size % bkv == 0, (block_size, bkv)
    sub = block_size // bkv
    dt = cfg.acc()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # tables, q_pos feed the index maps
        grid=(b, nb * sub),
        in_specs=[
            pl.BlockSpec((1, h, dk), lambda b, jt, tables, qpos: (b, 0, 0)),
            pl.BlockSpec(
                (1, bkv, groups, dk),
                lambda b, jt, tables, qpos: (tables[b, jt // sub],
                                             jt % sub, 0, 0),
            ),
            pl.BlockSpec(
                (1, bkv, groups, dv),
                lambda b, jt, tables, qpos: (tables[b, jt // sub],
                                             jt % sub, 0, 0),
            ),
            # positions ride as (num_blocks, 1, block_size) rows: Mosaic
            # tiles the last two block dims by (8, 128), which a (1, bkv)
            # block of the 2-D pool fails.
            pl.BlockSpec(
                (1, 1, bkv),
                lambda b, jt, tables, qpos: (tables[b, jt // sub], 0,
                                             jt % sub),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, h, dv), lambda b, jt, tables, qpos: (b, 0, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((groups, rep, dv), dt),  # output accumulator
            pltpu.VMEM((groups, rep), dt),      # running max
            pltpu.VMEM((groups, rep), dt),      # running denom
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, groups=groups, rep=rep, causal=causal,
            window=window, is_global=is_global, scale=scale, dt=dt,
        ),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, dv), q.dtype),
        name="paged_decode_attend",
        interpret=cfg.resolve_interpret(),
    )(tables, q_pos, q, k_pool, v_pool, pos_pool[:, None, :])
