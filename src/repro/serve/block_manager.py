"""Paged serving memory: fixed-size KV token blocks + per-request tables.

``BlockManager`` is pure host-side bookkeeping (testable without jax):
physical blocks carry refcounts so a block can back several requests at
once — the radix prefix cache (serve/prefix_cache.py) and request forks
share blocks instead of copying them, and a write to a shared block goes
through copy-on-write.

``PagedBackend`` owns the device side: per-layer block pools
(``init_paged_kv_cache`` — leading dim indexes physical blocks, not
rows), slot-indexed SSM state, the per-slot block tables, and the jitted
paged prefill/decode/clear/copy programs. It implements the same
``CacheBackend`` interface as the contiguous pool (serve/cache_pool.py),
so ``ServeEngine`` drives either interchangeably and the contiguous
engine stays the bit-exact correctness oracle.

Memory math (docs/serving.md): the contiguous pool is
``num_slots x max_len`` token positions whatever the traffic; the paged
pool holds ``num_blocks x block_size`` and a request pins only
``ceil(len / block_size)`` blocks, so peak usage tracks tokens actually
resident — the high-water mark is tracked and reported per engine.
"""
from __future__ import annotations

import logging
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..layers.attention import init_paged_kv_cache
from ..layers.ssm import init_ssm_cache
from .cache_pool import CacheBackend
from .prefix_cache import RadixPrefixCache

log = logging.getLogger(__name__)

# Fixed width of the jitted block clear/copy programs: ids are padded to a
# multiple of this with out-of-range sentinels (dropped scatters), so any
# allocation count runs through one compiled signature.
_ID_BATCH = 8


class BlockManager:
    """Free-list allocator with refcounts over `num_blocks` physical
    blocks. Block 0 is the reserved NULL block (never allocated; its pool
    `pos` stays -1, so table entries of 0 mean "nothing here").

    Refcount protocol: alloc() returns blocks at refcount 1 (the owning
    request). Sharing — a prefix-cache node adopting a block, or a fork
    duplicating a table — increfs. decref() frees at zero. A writer must
    hold the ONLY reference; `needs_cow` says whether a write has to
    copy first.
    """

    def __init__(self, num_blocks: int):
        assert num_blocks >= 2, "need at least the null block + one real"
        self.num_blocks = num_blocks
        self.ref = np.zeros((num_blocks,), np.int32)
        self.ref[0] = 1  # null block: pinned forever
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self.high_water = 0  # max blocks simultaneously allocated

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_used(self) -> int:
        return self.num_blocks - 1 - len(self._free)

    def can_alloc(self, n: int) -> bool:
        return len(self._free) >= n

    def alloc(self, n: int) -> List[int]:
        assert self.can_alloc(n), f"alloc({n}) with {len(self._free)} free"
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            assert self.ref[b] == 0
            self.ref[b] = 1
        self.high_water = max(self.high_water, self.num_used)
        return out

    def incref(self, block: int):
        assert block != 0 and self.ref[block] > 0
        self.ref[block] += 1

    def decref(self, block: int) -> bool:
        """Drop one reference; returns True if the block was freed."""
        assert block != 0 and self.ref[block] > 0, f"bad decref({block})"
        self.ref[block] -= 1
        if self.ref[block] == 0:
            self._free.append(block)
            return True
        return False

    def needs_cow(self, block: int) -> bool:
        return self.ref[block] > 1

    def fork_table(self, table: Sequence[int]) -> List[int]:
        """Share every block of a table with a second owner (copy-on-write
        fork): increfs each real block, returns the copied table."""
        out = list(table)
        for b in out:
            if b != 0:
                self.incref(b)
        return out


def init_paged_cache(cfg, num_blocks: int, block_size: int, num_slots: int,
                     dtype=jnp.bfloat16):
    """Per-layer cache list for the paged backend: attention layers get a
    (num_blocks, block_size, ...) block pool SHARED by all rows; SSM
    layers keep (num_slots, ...) per-row recurrent state (constant-size —
    nothing to page)."""
    caches = []
    for _ in range(cfg.num_layers):
        c = {}
        if cfg.has_attention():
            c["attn"] = init_paged_kv_cache(cfg, num_blocks, block_size,
                                            dtype)
        if cfg.has_ssm():
            c["ssm"] = init_ssm_cache(cfg, num_slots, dtype)
        caches.append(c)
    return caches


def _pad_ids(ids: Sequence[int], sentinel: int) -> np.ndarray:
    """Pad to the next _ID_BATCH multiple with out-of-range ids."""
    n = max(_ID_BATCH, -(-len(ids) // _ID_BATCH) * _ID_BATCH)
    out = np.full((n,), sentinel, np.int32)
    out[: len(ids)] = ids
    return out


class PagedBackend(CacheBackend):
    """Paged cache backend: block-table addressing + radix prefix cache.

    Admission needs a free slot (decode batch row + SSM state) AND enough
    free blocks for the uncached part of the prompt — NOT a whole
    max_len reservation. Decode allocates one block at a time as a row
    crosses block boundaries; when the free list runs dry the prefix
    cache evicts LRU-first, and if that is not enough the engine preempts
    the row (requeues it) rather than corrupting memory.
    """

    def __init__(self, cfg, num_slots: int, max_len: int,
                 dtype=jnp.bfloat16, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 prefix_cache: bool = True, use_kernel: bool = True,
                 cache_generated: bool = False, telemetry: bool = False):
        from .programs import (
            clear_blocks_program,
            clear_ssm_slot_program,
            copy_blocks_program,
            invalidate_positions_paged_program,
            make_decode_step_paged,
            make_prefill_chunk_paged,
            make_verify_step_paged,
        )

        self.cfg = cfg
        self.num_slots = num_slots
        self.max_len = max_len
        self.block_size = block_size
        self.blocks_per_row = -(-max_len // block_size)
        if num_blocks is None:
            # capacity parity with the contiguous pool by default; callers
            # size it down to get prompt-proportional memory
            num_blocks = num_slots * self.blocks_per_row + 1
        self.num_blocks = num_blocks
        self.cache = init_paged_cache(cfg, num_blocks, block_size,
                                      num_slots, dtype)
        self.mgr = BlockManager(num_blocks)
        # Prefix reuse splices cached KV under a *new* request, which is
        # only sound when all cross-token state lives in the cache —
        # recurrent SSM state is not block-addressable, so hybrid/SSM
        # archs run paged but uncached.
        self.prefix: Optional[RadixPrefixCache] = (
            RadixPrefixCache(block_size)
            if prefix_cache and not cfg.has_ssm() else None
        )
        # cache_finished() publishes a retired request's prompt+OUTPUT
        # block chain into the radix tree, so a follow-up request whose
        # prompt extends a completed conversation gets prefix hits past
        # the original prompt boundary (multi-turn reuse). Opt-in: the
        # tree then retains generation blocks until LRU eviction, which
        # trades pool headroom for hits.
        self.cache_generated = cache_generated and self.prefix is not None
        self.tables = np.zeros((num_slots, self.blocks_per_row), np.int32)
        self._tables_dev = None  # rebuilt lazily when tables change
        self._free_slots: List[int] = list(range(num_slots - 1, -1, -1))
        # Peak blocks pinned by LIVE request tables. Unlike
        # mgr.high_water (pool usage, which the radix tree's retained-
        # but-evictable blocks push toward capacity in any sustained
        # run), this measures actual request footprint — the number the
        # memory-proportionality claim is about.
        self.live_block_hw = 0

        # Decode runs the Pallas paged-attention kernel by default (tiles
        # streamed from the pool in place); use_kernel=False keeps the jnp
        # row-view gather — the oracle the kernel is tested against (same
        # math, another f32 summation order). Chunked prefill always takes
        # the gather path (S > 1).
        # A kernel failure at dispatch degrades PERMANENTLY to the gather
        # oracle (`_kernel_fallback`) instead of taking serving down; the
        # exception is logged and `kernel_fallbacks` counts the
        # degradations for the metrics surface.
        self.use_kernel = use_kernel
        self.kernel_fallbacks = 0
        # Telemetry variants of the programs (see serve/programs.py):
        # every call stashes its telemetry pytree on `last_telemetry` as
        # (phase, pytree) for the engine to drain.
        self.telemetry = telemetry
        self.last_telemetry = None
        self._prefill_chunk = jax.jit(
            make_prefill_chunk_paged(cfg, telemetry=telemetry),
            donate_argnums=(1, 2)
        )
        self._decode = jax.jit(
            make_decode_step_paged(cfg, use_kernel=use_kernel,
                                   telemetry=telemetry),
            donate_argnums=(4,),
        )
        # Speculative-decoding programs (compiled lazily at first use).
        self._verify = jax.jit(
            make_verify_step_paged(cfg, use_kernel=use_kernel,
                                   telemetry=telemetry),
            donate_argnums=(4,),
        )
        self._invalidate = jax.jit(
            invalidate_positions_paged_program, donate_argnums=(0,)
        )
        self._clear_blocks = jax.jit(
            clear_blocks_program, donate_argnums=(0,)
        )
        self._copy_blocks = jax.jit(copy_blocks_program, donate_argnums=(0,))
        self._clear_ssm = (
            jax.jit(clear_ssm_slot_program, donate_argnums=(0,))
            if cfg.has_ssm() else None
        )

    # -- CacheBackend ------------------------------------------------------

    @property
    def num_free_slots(self) -> int:
        return len(self._free_slots)

    @property
    def max_chunk(self) -> int:
        # One chunk's positions are distinct, so distinct (block, offset)
        # slots — no scatter-order hazard at any chunk size.
        return self.max_len

    def accepts(self, prompt_len: int, max_new: int) -> bool:
        worst = -(-(prompt_len + max_new) // self.block_size)
        return worst <= self.num_blocks - 1

    def try_admit(self, req) -> Optional[Tuple[int, int]]:
        if not self._free_slots:
            return None
        prompt = req.prompt
        cached: List[int] = []
        if self.prefix is not None and not req.no_prefix_cache:
            cached = self.prefix.match(prompt)
        cached_len = len(cached) * self.block_size
        # blocks covering the uncached prompt tail plus the first decode
        # token. Clamp at max_len: a prompt that fills the window exactly
        # (max_new_tokens == 0) retires on cache_full before any decode
        # write, so position max_len never needs a block — and without
        # the clamp n_logical would exceed blocks_per_row.
        n_logical = -(-min(len(prompt) + 1, self.max_len)
                      // self.block_size)
        need = n_logical - len(cached)
        # pin the matched chain before eviction can run
        for b in cached:
            self.mgr.incref(b)
        if not self._reserve(need):
            for b in cached:
                self.mgr.decref(b)
            return None
        fresh = self.mgr.alloc(need)
        self._invalidate_blocks(fresh)
        slot = self._free_slots.pop()
        if self._clear_ssm is not None:
            self.cache = self._clear_ssm(self.cache, jnp.int32(slot))
        row = self.tables[slot]
        row[:] = 0
        row[: len(cached)] = cached
        row[len(cached): n_logical] = fresh
        self._tables_dev = None
        self._touch_live_hw()
        if self.prefix is not None and not req.no_prefix_cache:
            self.prefix.record_lookup(len(cached))
        return slot, cached_len

    def prefill_chunk(self, params, buf, slot: int, toks, poss):
        table = jnp.asarray(self.tables[slot: slot + 1])
        out = self._prefill_chunk(
            params, self.cache, buf, jnp.int32(slot), table,
            jnp.asarray([toks], jnp.int32), jnp.asarray([poss], jnp.int32),
        )
        self.cache, buf = out[0], out[1]
        if self.telemetry:
            self.last_telemetry = ("prefill", out[2])
        return buf

    def prefill_finished(self, entry):
        """Publish the request's full prompt blocks into the radix tree
        the moment prefill completes — later requests with the same
        system prompt share them immediately, not at retirement."""
        if self.prefix is None:
            return
        prompt = entry.req.prompt
        row = self.tables[entry.slot]
        n_full = len(prompt) // self.block_size
        self.prefix.insert(prompt[: n_full * self.block_size],
                           list(row[:n_full]), self.mgr)

    def ensure_decode_block(self, slot: int, pos: int) -> bool:
        """Make position `pos` writable for `slot`: allocate the logical
        block if the table has none (evicting prefix LRU under pressure),
        copy-on-write if it is shared. False = out of memory (preempt)."""
        return self._ensure_logical_block(slot, pos // self.block_size)

    def reserve_burst(self, slot: int, start: int, n: int) -> int:
        """Make positions [start, start+n) writable for a speculative
        burst: secure (alloc/COW) every logical block in range, in order,
        evicting prefix LRU under pressure. Returns the number of leading
        positions covered — a partial reservation shrinks the burst
        rather than failing it, and 0 means even the pending token's
        position could not be secured (the engine preempts)."""
        bs = self.block_size
        end = min(start + n, self.max_len)
        covered = 0
        for lb in range(start // bs, -(-end // bs)):
            if not self._ensure_logical_block(slot, lb):
                break
            covered = min(end, (lb + 1) * bs) - start
        return max(0, min(covered, n))

    def rollback_burst(self, slot: int, next_pos: int):
        """Un-reserve blocks that exist only to hold rejected draft
        positions beyond ``next_pos`` (the row's next write position).
        Afterwards the table and refcounts are exactly the
        never-having-drafted state: blocks cover positions <= next_pos,
        the same footprint `ensure_decode_block(slot, next_pos)` leaves
        on the non-speculative path."""
        row = self.tables[slot]
        changed = False
        for lb in range(next_pos // self.block_size + 1,
                        self.blocks_per_row):
            blk = int(row[lb])
            if blk != 0:
                self.mgr.decref(blk)
                row[lb] = 0
                changed = True
        if changed:
            self._tables_dev = None

    def _ensure_logical_block(self, slot: int, lb: int) -> bool:
        blk = int(self.tables[slot, lb])
        if blk == 0:
            if not self._reserve(1):
                return False
            (fresh,) = self.mgr.alloc(1)
            self._invalidate_blocks([fresh])
            self.tables[slot, lb] = fresh
            self._tables_dev = None
            self._touch_live_hw()
        elif self.mgr.needs_cow(blk):
            if not self._reserve(1):
                return False
            (fresh,) = self.mgr.alloc(1)
            self.copy_blocks([blk], [fresh])
            self.mgr.decref(blk)
            self.tables[slot, lb] = fresh
            self._tables_dev = None
            self._touch_live_hw()  # divergence: one more unique block
        return True

    def _kernel_fallback(self, err: Exception):
        """Graceful degradation: a Pallas kernel failure (compile or
        dispatch) rebuilds BOTH multi-token programs on the jnp gather
        oracle and turns the kernel off for the backend's lifetime. The
        gather path computes the same attention in another f32 summation
        order, so serving continues (greedy streams can part only where
        two tokens tie within bf16 rounding) — the decode HBM saving is
        lost. Safe to invoke at trace/compile
        failure time: the cache pytree is only replaced on a successful
        call, and buffer donation cannot have consumed it before the
        program ever ran. ``err`` is logged with its traceback: a kernel
        the device's compiler refuses must not pass silently as a
        slower gather path."""
        from .programs import make_decode_step_paged, make_verify_step_paged

        assert self.use_kernel, "fallback with the kernel already off"
        log.error("paged-attention kernel program failed; falling back to "
                  "the jnp gather path: %s: %s", type(err).__name__, err,
                  exc_info=err)
        self.use_kernel = False
        self.kernel_fallbacks += 1
        # the rebuilt programs must keep the telemetry flag: losing it
        # would change the program arity mid-serve
        self._decode = jax.jit(
            make_decode_step_paged(self.cfg, use_kernel=False,
                                   telemetry=self.telemetry),
            donate_argnums=(4,),
        )
        self._verify = jax.jit(
            make_verify_step_paged(self.cfg, use_kernel=False,
                                   telemetry=self.telemetry),
            donate_argnums=(4,),
        )

    def decode(self, params, toks, pos):
        if self._tables_dev is None:
            self._tables_dev = jnp.asarray(self.tables)
        try:
            out = self._decode(
                params, toks, pos, self._tables_dev, self.cache
            )
        except Exception as err:
            if not self.use_kernel:
                raise
            self._kernel_fallback(err)
            out = self._decode(
                params, toks, pos, self._tables_dev, self.cache
            )
        logits, self.cache = out[0], out[1]
        if self.telemetry:
            self.last_telemetry = ("decode", out[2])
        return logits

    def verify(self, params, toks, poss):
        if self._tables_dev is None:
            self._tables_dev = jnp.asarray(self.tables)
        try:
            out = self._verify(
                params, toks, poss, self._tables_dev, self.cache
            )
        except Exception as err:
            if not self.use_kernel:
                raise
            self._kernel_fallback(err)
            out = self._verify(
                params, toks, poss, self._tables_dev, self.cache
            )
        logits, self.cache = out[0], out[1]
        if self.telemetry:
            self.last_telemetry = ("verify", out[2])
        return logits

    def invalidate_positions(self, positions):
        if self._tables_dev is None:
            self._tables_dev = jnp.asarray(self.tables)
        self.cache = self._invalidate(
            self.cache, positions, self._tables_dev
        )

    def cache_finished(self, entry):
        """Publish the retiring request's prompt+output chain into the
        radix tree (``cache_generated``): a repeat or multi-turn
        continuation then gets prefix hits past the original prompt
        boundary. Only full blocks are insertable, and the last emitted
        token is excluded — it was sampled but never fed, so its KV slot
        is unwritten (on every path: EOS, budget, ceiling, speculative
        truncation)."""
        if not self.cache_generated or entry.req.no_prefix_cache:
            return
        toks = list(entry.req.prompt) + list(entry.req.out[:-1])
        n_full = len(toks) // self.block_size
        if n_full == 0:
            return
        row = self.tables[entry.slot]
        self.prefix.insert(
            toks[: n_full * self.block_size],
            [int(b) for b in row[:n_full]], self.mgr,
        )

    def retire(self, slot: int):
        row = self.tables[slot]
        for b in row:
            if b != 0:
                self.mgr.decref(int(b))
        row[:] = 0
        self._tables_dev = None
        assert slot not in self._free_slots, f"double retire of slot {slot}"
        self._free_slots.append(slot)

    def jit_cache_sizes(self) -> tuple:
        sizes = (self._decode._cache_size(),
                 self._prefill_chunk._cache_size(),
                 self._clear_blocks._cache_size(),
                 self._copy_blocks._cache_size(),
                 self._verify._cache_size(),
                 self._invalidate._cache_size())
        if self._clear_ssm is not None:
            sizes += (self._clear_ssm._cache_size(),)
        return sizes

    def token_capacity(self) -> int:
        return (self.num_blocks - 1) * self.block_size

    def tokens_free(self) -> int:
        """Token positions admission control can still promise: the free
        list plus tree-retained blocks no live table references (those
        are reclaimable via LRU eviction — counting them stops the
        server shedding everything once the radix tree has warmed up to
        pool capacity, which it does in any sustained run)."""
        live = np.unique(self.tables[self.tables != 0]).size
        reclaimable = max(0, self.mgr.num_used - int(live))
        return (self.mgr.num_free + reclaimable) * self.block_size

    def bytes_per_block(self) -> int:
        per = 0
        for layer in self.cache:
            if "attn" in layer:
                for leaf in layer["attn"].values():
                    per += leaf.nbytes // self.num_blocks
        return per

    def ssm_bytes(self) -> int:
        per = 0
        for layer in self.cache:
            if "ssm" in layer:
                per += sum(leaf.nbytes for leaf in
                           jax.tree_util.tree_leaves(layer["ssm"]))
        return per

    def peak_cache_bytes(self) -> int:
        """Peak live-request block footprint x bytes/block (+ the
        constant SSM rows) — what a right-sized pool would have needed
        for the traffic, the number the bench compares against
        num_slots x max_len. Tree-retained (evictable) blocks are
        excluded: they are reclaimable cache, and counting them would
        just report the configured pool size in any sustained run."""
        return self.live_block_hw * self.bytes_per_block() + self.ssm_bytes()

    def occupancy(self) -> dict:
        live = int(np.unique(self.tables[self.tables != 0]).size)
        return {
            "blocks_free": self.mgr.num_free,
            "blocks_used": self.mgr.num_used,
            "blocks_live": live,
            "slots_free": len(self._free_slots),
            "slots_total": self.num_slots,
        }

    def _touch_live_hw(self):
        # unique physical blocks: a prefix-shared block backing several
        # table rows is ONE resident block, not one per row
        used = self.tables[self.tables != 0]
        self.live_block_hw = max(self.live_block_hw,
                                 int(np.unique(used).size))

    # -- internals ---------------------------------------------------------

    def _reserve(self, n: int) -> bool:
        """Ensure `n` free blocks, evicting prefix-cache LRU leaves as
        needed; False if physically impossible right now."""
        while not self.mgr.can_alloc(n):
            if self.prefix is None or not self.prefix.evict_one(self.mgr):
                return False
        return True

    def _invalidate_blocks(self, blocks: List[int]):
        """pos -> -1 for freshly allocated blocks: stale entries from the
        previous owner must not alias the new request's positions (the
        paged analogue of the contiguous pool's acquire-time row clear)."""
        if not blocks:
            return
        ids = _pad_ids(blocks, self.num_blocks)
        for i in range(0, len(ids), _ID_BATCH):
            self.cache = self._clear_blocks(
                self.cache, jnp.asarray(ids[i: i + _ID_BATCH])
            )

    def copy_blocks(self, src: List[int], dst: List[int]):
        """Device copy src[i] -> dst[i] (COW / fork). Fixed-width padded
        batches: zero recompiles whatever the count."""
        assert len(src) == len(dst)
        if not src:
            return
        s = _pad_ids(src, 0)  # src pad: clamped read, dropped by dst pad
        d = _pad_ids(dst, self.num_blocks)
        for i in range(0, len(s), _ID_BATCH):
            self.cache = self._copy_blocks(
                self.cache, jnp.asarray(s[i: i + _ID_BATCH]),
                jnp.asarray(d[i: i + _ID_BATCH]),
            )

    def fork_slot(self, src_slot: int) -> Optional[int]:
        """Fork a live row into a fresh slot sharing ALL its blocks
        (copy-on-write): the clone diverges block-by-block as either row
        writes. Returns the new slot or None (no slot free). SSM state is
        copied by value (it is per-slot, not shared)."""
        if not self._free_slots:
            return None
        slot = self._free_slots.pop()
        self.tables[slot] = self.mgr.fork_table(self.tables[src_slot])
        self._tables_dev = None
        self._touch_live_hw()
        if self._clear_ssm is not None:
            # slot-state copy: roundtrip through host is fine (fork is a
            # control-plane operation, not a per-token one)
            for layer in self.cache:
                if "ssm" in layer:
                    for name, leaf in layer["ssm"].items():
                        layer["ssm"][name] = leaf.at[slot].set(
                            leaf[src_slot]
                        )
        return slot
