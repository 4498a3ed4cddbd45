"""Jitted device programs the serving engines run.

Each factory closes over a config and returns a pure function with ONE
fixed signature — request churn changes values (slot ids, positions,
block tables), never shapes, so each program compiles exactly once.

Contiguous programs address the cache as (num_slots, max_len) rows
(serve/cache_pool.py); paged programs address a (num_blocks, block_size)
block pool through per-row block tables (serve/block_manager.py). The
attention cache is per-layer "attn" entries; SSM recurrent state stays
slot-indexed in both layouts (it is constant-size per row — there is
nothing to page).

Every factory takes a STATIC ``telemetry`` flag. ``telemetry=True``
builds a program whose jaxpr additionally emits the ``lm_apply``
telemetry pytree (fixed-shape stop_gradient'd scalars: per-layer
routing health + logit numerics) as a trailing output — the tokens the
program produces are bit-identical to the ``telemetry=False`` build,
and because the flag is baked at build time it can never trigger a
recompile at serve time.

Every program here runs ``lm_apply`` in a serving mode ("prefill" /
"decode"), which makes MoE routing a PURE PER-ROW FUNCTION
(core/sparse_moe.py; Soft MoE is per-row by construction): a row's
outputs are identical whether it is served solo or co-batched, whether
its prompt arrived whole or in chunks, and whether its tokens rode a
(B, 1) decode step or a (B, k+1) speculative verify lane. The batch-
variance probe (serve/telemetry.py) and the chunked-prefill/spec parity
tests are the enforcement of this contract.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..layers.attention import (
    copy_kv_blocks,
    invalidate_kv_positions,
    invalidate_paged_positions,
    reset_block_pos,
)
from ..layers.ssm import reset_ssm_rows
from ..models import lm_apply
from .cache_pool import pool_row, pool_write_row


# ---------------------------------------------------------------------------
# contiguous (slot-row) programs
# ---------------------------------------------------------------------------


def make_prefill_step(cfg, max_len: int):
    """Whole-prompt prefill: (params, tokens(B,S), cache) ->
    (logits(B,1,V), cache). Shared positions arange(S) — the wave path and
    the dry-run's prefill cells."""

    def prefill(params, tokens, cache):
        s = tokens.shape[1]
        logits, cache, _ = lm_apply(
            params, cfg, tokens, positions=jnp.arange(s), cache=cache,
            mode="prefill", last_only=True,
        )
        return logits, cache

    return prefill


def make_decode_step(cfg, telemetry: bool = False):
    """(params, tokens(B,1), pos(B,), cache) -> (logits(B,1,V), cache
    [, telem]). Per-row positions; rows with pos<0 are inactive no-ops."""

    def decode(params, tokens, pos, cache):
        out = lm_apply(
            params, cfg, tokens, positions=pos[:, None], cache=cache,
            mode="decode", telemetry=telemetry,
        )
        if telemetry:
            return out[0], out[1], out[3]
        return out[0], out[1]

    return decode


def make_prefill_chunk_step(cfg, telemetry: bool = False):
    """Chunked prefill into one pool slot: (params, pool_cache, logits_buf,
    slot, tokens(1,C), positions(1,C)) -> (pool_cache, logits_buf).

    mode="decode" with S>1 makes attention read prior chunks back out of
    the cache (and the SSM paths continue from their recurrent state), so
    chunks compose exactly; left-pad tokens carry position -1 and touch
    nothing."""

    def prefill_chunk(params, cache, buf, slot, tokens, positions):
        row = pool_row(cache, slot)
        out = lm_apply(
            params, cfg, tokens, positions=positions, cache=row,
            mode="decode", last_only=True, telemetry=telemetry,
        )
        logits, row = out[0], out[1]
        cache = pool_write_row(cache, slot, row)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, logits[:, -1].astype(buf.dtype), slot, axis=0
        )
        if telemetry:
            return cache, buf, out[3]
        return cache, buf

    return prefill_chunk


def make_verify_step(cfg, telemetry: bool = False):
    """Speculative-decoding verify: (params, tokens(B,S), pos(B,S), cache)
    -> (logits(B,S,V), cache). A multi-token decode continuation over the
    contiguous pool (chunked-prefill semantics: this call's KV is written
    first, each lane attends everything causally at or before it), with
    logits at EVERY lane — lane j's logits are the target distribution
    for the token after lane j, which the accept/resample step
    (sampling.spec_accept_tokens) scores the drafts against. Lanes with
    pos < 0 (inactive rows, unused draft lanes) are exact no-ops. One
    fixed (B, k+1) signature: request churn and per-row draft counts
    change values, never shapes."""

    def verify(params, tokens, pos, cache):
        out = lm_apply(
            params, cfg, tokens, positions=pos, cache=cache, mode="decode",
            telemetry=telemetry,
        )
        if telemetry:
            return out[0], out[1], out[3]
        return out[0], out[1]

    return verify


def invalidate_positions_program(cache, positions):
    """Speculative rollback (contiguous): pos -> -1 for a (B, W) batch of
    absolute positions in every attention layer (lanes < 0 drop). Leaves
    the cache equal to never having written the rejected draft lanes."""
    out = []
    for layer in cache:
        c = dict(layer)
        if "attn" in c:
            c["attn"] = invalidate_kv_positions(c["attn"], positions)
        out.append(c)
    return out


# ---------------------------------------------------------------------------
# paged (block-pool) programs
# ---------------------------------------------------------------------------


def _ssm_row_view(cache, slot):
    """Batch-1 view of one slot: attention block pools pass through whole
    (they are row-independent — addressing goes through the table), SSM
    leaves are sliced to the slot's row."""
    view = []
    for layer in cache:
        c = {}
        if "attn" in layer:
            c["attn"] = layer["attn"]
        if "ssm" in layer:
            c["ssm"] = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=0),
                layer["ssm"],
            )
        view.append(c)
    return view


def _ssm_row_merge(cache, new_view, slot):
    """Inverse of `_ssm_row_view`: adopt updated attention pools wholesale,
    scatter the batch-1 SSM rows back into the slot."""
    out = []
    for layer, nl in zip(cache, new_view):
        c = dict(layer)
        if "attn" in c:
            c["attn"] = nl["attn"]
        if "ssm" in c:
            c["ssm"] = jax.tree_util.tree_map(
                lambda a, r: jax.lax.dynamic_update_slice_in_dim(
                    a, r.astype(a.dtype), slot, axis=0
                ),
                layer["ssm"], nl["ssm"],
            )
        out.append(c)
    return out


def make_prefill_chunk_paged(cfg, telemetry: bool = False):
    """Chunked prefill through a block table: (params, cache, logits_buf,
    slot, table(1,nb), tokens(1,C), positions(1,C)) -> (cache, buf
    [, telem]). Attention writes scatter into the slot's table blocks;
    SSM state lives in the slot row as in the contiguous path."""

    def prefill_chunk(params, cache, buf, slot, table, tokens, positions):
        view = _ssm_row_view(cache, slot)
        out = lm_apply(
            params, cfg, tokens, positions=positions, cache=view,
            mode="decode", last_only=True, block_tables=table,
            telemetry=telemetry,
        )
        logits, view = out[0], out[1]
        cache = _ssm_row_merge(cache, view, slot)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, logits[:, -1].astype(buf.dtype), slot, axis=0
        )
        if telemetry:
            return cache, buf, out[3]
        return cache, buf

    return prefill_chunk


def make_decode_step_paged(cfg, use_kernel: bool = False,
                           telemetry: bool = False):
    """(params, tokens(B,1), pos(B,), tables(B,nb), cache) ->
    (logits(B,1,V), cache). Rows with pos<0 are inactive; their (all-null)
    table rows contribute only masked-out keys.

    ``use_kernel`` routes GQA attention through the Pallas
    paged-attention kernel (kernels/paged_attention_kernels.py), which
    streams pool tiles in place — no per-step (B, blocks_per_row *
    block_size, ...) row-view gather in the decode jaxpr (proved by
    ``benchmarks.bench_kernels.check_paged_materialization``). The
    default jnp gather path is the oracle it is tested against."""

    def decode(params, tokens, pos, tables, cache):
        out = lm_apply(
            params, cfg, tokens, positions=pos[:, None], cache=cache,
            mode="decode", block_tables=tables, paged_kernel=use_kernel,
            telemetry=telemetry,
        )
        if telemetry:
            return out[0], out[1], out[3]
        return out[0], out[1]

    return decode


def make_verify_step_paged(cfg, use_kernel: bool = False,
                           telemetry: bool = False):
    """Paged speculative verify: (params, tokens(B,S), pos(B,S),
    tables(B,nb), cache) -> (logits(B,S,V), cache). Same contract as
    `make_verify_step` through the block tables. ``use_kernel`` is
    accepted for signature parity with the decode program, but S > 1
    always takes the jnp gather route (see layers/attention.py — the
    Pallas kernel is single-query)."""

    def verify(params, tokens, pos, tables, cache):
        out = lm_apply(
            params, cfg, tokens, positions=pos, cache=cache,
            mode="decode", block_tables=tables, paged_kernel=use_kernel,
            telemetry=telemetry,
        )
        if telemetry:
            return out[0], out[1], out[3]
        return out[0], out[1]

    return verify


def invalidate_positions_paged_program(cache, positions, tables):
    """Speculative rollback (paged): pos -> -1 through the block tables
    for a (B, W) batch of absolute positions in every attention layer."""
    out = []
    for layer in cache:
        c = dict(layer)
        if "attn" in c:
            c["attn"] = invalidate_paged_positions(
                c["attn"], positions, tables
            )
        out.append(c)
    return out


def clear_blocks_program(cache, blocks):
    """Invalidate a (W,) padded batch of physical blocks across every
    attention layer (pos -> -1) and return the cache. Freed blocks are
    cleared lazily at their next allocation, exactly like contiguous slot
    rows. Jit-safe."""
    out = []
    for layer in cache:
        c = dict(layer)
        if "attn" in c:
            c["attn"] = reset_block_pos(c["attn"], blocks)
        out.append(c)
    return out


def copy_blocks_program(cache, src, dst):
    """Copy physical blocks src[i] -> dst[i] in every attention layer
    (copy-on-write fork). Padded lanes carry out-of-range ids and drop."""
    out = []
    for layer in cache:
        c = dict(layer)
        if "attn" in c:
            c["attn"] = copy_kv_blocks(c["attn"], src, dst)
        out.append(c)
    return out


def clear_ssm_slot_program(cache, slot):
    """Zero one slot's SSM rows (paged acquire — attention needs no clear
    here because block invalidation happens per block at allocation)."""
    out = []
    for layer in cache:
        c = dict(layer)
        if "ssm" in c:
            c["ssm"] = reset_ssm_rows(c["ssm"], slot)
        out.append(c)
    return out
