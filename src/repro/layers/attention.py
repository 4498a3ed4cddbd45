"""Attention variants: GQA (optional QKV bias, sliding window) and MLA
(DeepSeek-V2 multi-head latent attention with compressed KV cache).

Three execution modes share one code path:
  * train/eval: full sequence, no cache.
  * prefill:    full sequence, cache written for subsequent decoding.
  * decode:     q_len==1, attends over the cache (ring buffer for
                sliding-window layers, absorbed-matmul form for MLA).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..kernels import attention_kernels
from ..kernels.paged_attention_kernels import paged_decode_attend
from .common import lecun_init, split_rngs
from .rotary import apply_rope, rope_cos_sin

NEG_INF = -2.0**30

# Above this (Sq * Sk) product, attention switches to the flash-style
# chunked path — never materializes (Sq, Sk) logits. At train_4k the dense
# path would hold a (b, h, 4096, 4096) f32 logits tensor per device
# (~15GB/dev for qwen2-0.5b, whose 14 heads can't shard over a 16-way
# model axis); the chunked path keeps one (Sq, block) tile live instead.
_CHUNKED_THRESHOLD = 2048 * 4096


# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def gqa_init(rng, cfg):
    a = cfg.attention
    d = cfg.d_model
    r_q, r_k, r_v, r_o = split_rngs(rng, 4)
    p = {
        "wq": lecun_init(r_q, (d, a.num_heads, a.head_dim), fan_in=d),
        "wk": lecun_init(r_k, (d, a.num_kv_heads, a.head_dim), fan_in=d),
        "wv": lecun_init(r_v, (d, a.num_kv_heads, a.head_dim), fan_in=d),
        "wo": lecun_init(
            r_o, (a.num_heads, a.head_dim, d), fan_in=a.num_heads * a.head_dim
        ),
    }
    if a.qkv_bias:
        p["bq"] = jnp.zeros((a.num_heads, a.head_dim))
        p["bk"] = jnp.zeros((a.num_kv_heads, a.head_dim))
        p["bv"] = jnp.zeros((a.num_kv_heads, a.head_dim))
    return p


def init_kv_cache(cfg, batch: int, length: int, is_global: bool,
                  dtype=jnp.bfloat16):
    """Cache for one layer. Sliding-window layers use a ring buffer of the
    window size; global layers allocate the full length.

    ``pos`` is PER ROW — (batch, length) — so each row advances through its
    ring independently: continuous-batching serving admits/retires rows at
    arbitrary decode steps (serve/cache_pool.py). An entry with pos < 0 is
    invalid and masked out of attention."""
    a = cfg.attention
    if a.sliding_window is not None and not is_global:
        length = min(length, a.sliding_window)
    if a.kind == "mla":
        return {
            "ckv": jnp.zeros((batch, length, a.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, length, a.qk_rope_head_dim), dtype),
            "pos": jnp.full((batch, length), -1, jnp.int32),
        }
    return {
        "k": jnp.zeros((batch, length, a.num_kv_heads, a.head_dim), dtype),
        "v": jnp.zeros((batch, length, a.num_kv_heads, a.head_dim), dtype),
        "pos": jnp.full((batch, length), -1, jnp.int32),
    }


def init_paged_kv_cache(cfg, num_blocks: int, block_size: int,
                        dtype=jnp.bfloat16):
    """Paged cache for one layer: one pool of ``num_blocks`` fixed-size
    token blocks shared by ALL rows (serve/block_manager.py hands blocks
    out). Leading dim indexes physical blocks, not batch rows — a request
    reaches its tokens through a per-row block table.

    Unlike the contiguous cache there is no ring: sliding-window layers
    store every position and rely on the window term of `make_mask`
    (paging already bounds memory by tokens actually written, which is
    the job the ring did). Block 0 is reserved as the NULL block — its
    `pos` stays -1 forever, so unallocated table entries gather only
    masked-out keys."""
    a = cfg.attention
    if a.kind == "mla":
        return {
            "ckv": jnp.zeros((num_blocks, block_size, a.kv_lora_rank), dtype),
            "krope": jnp.zeros(
                (num_blocks, block_size, a.qk_rope_head_dim), dtype
            ),
            "pos": jnp.full((num_blocks, block_size), -1, jnp.int32),
        }
    return {
        "k": jnp.zeros(
            (num_blocks, block_size, a.num_kv_heads, a.head_dim), dtype
        ),
        "v": jnp.zeros(
            (num_blocks, block_size, a.num_kv_heads, a.head_dim), dtype
        ),
        "pos": jnp.full((num_blocks, block_size), -1, jnp.int32),
    }


def _attend(q, k, v, mask, scale: Optional[float] = None):
    """q: (B,Sq,H,Dk); k: (B,Sk,G,Dk); v: (B,Sk,G,Dv) grouped;
    mask: (B,Sq,Sk) bool or None. Dv may differ from Dk (MLA latent)."""
    b, sq, h, d = q.shape
    g, dv = k.shape[2], v.shape[-1]
    rep = h // g
    scale = scale if scale is not None else 1.0 / float(d) ** 0.5
    qg = q.reshape(b, sq, g, rep, d)
    logits = scale * jnp.einsum(
        "bsgrd,btgd->bgrst", qg.astype(jnp.float32), k.astype(jnp.float32)
    )
    if mask is not None:
        # (B,Sq,Sk) -> (B,1,1,Sq,Sk) to broadcast over (g, rep).
        logits = logits + jnp.where(mask[:, None, None], 0.0, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrst,btgd->bsgrd", w, v.astype(jnp.float32))
    return out.reshape(b, sq, h, dv).astype(q.dtype)


def _attend_chunked(q, k, v, qpos, kpos, causal: bool,
                    window: Optional[int], scale: Optional[float] = None,
                    is_global=True, block: int = 1024):
    """Flash-style online-softmax attention, scanning KV in blocks — never
    materializes the (Sq, Sk) logits or mask. Used when Sk is long (32k /
    500k shapes); numerically identical to `_attend` (checked in tests)."""
    b, sq, h, d = q.shape
    sk, g, dv = k.shape[1], k.shape[2], v.shape[-1]
    rep = h // g
    scale = scale if scale is not None else 1.0 / float(d) ** 0.5
    if kpos.ndim == 1:  # shared key positions -> per-row
        kpos = jnp.broadcast_to(kpos[None], (b, sk))
    if sk % block != 0:
        pad = (sk + block - 1) // block * block - sk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kpos = jnp.pad(kpos, ((0, 0), (0, pad)), constant_values=-1)
        sk += pad
    nb = sk // block
    qg = q.astype(jnp.float32).reshape(b, sq, g, rep, d)
    kb = k.astype(jnp.float32).reshape(b, nb, block, g, d).transpose(1, 0, 2, 3, 4)
    vb = v.astype(jnp.float32).reshape(b, nb, block, g, dv).transpose(1, 0, 2, 3, 4)
    kpb = kpos.reshape(b, nb, block).transpose(1, 0, 2)

    def body(carry, inp):
        m, l, acc = carry
        kblk, vblk, kp = inp
        s = jnp.einsum("bsgrd,btgd->bgrst", qg, kblk) * scale
        mask = make_mask(qpos, kp, causal, window, is_global)  # (b,sq,block)
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(-1)
        acc = acc * corr[..., None] + jnp.einsum("bgrst,btgd->bgrsd", p, vblk)
        return (m_new, l, acc), None

    # Without this, autodiff of the scan stores the per-block probability
    # tiles p — the full (Sq × Sk) memory the chunking exists to avoid.
    # Checkpointing the body makes backward recompute p from (q, k-block):
    # the flash-attention backward, expressed through remat.
    body = jax.checkpoint(
        body, policy=jax.checkpoint_policies.nothing_saveable,
        prevent_cse=False,
    )

    m0 = jnp.full((b, g, rep, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, g, rep, sq), jnp.float32)
    a0 = jnp.zeros((b, g, rep, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kb, vb, kpb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, dv)
    return out.astype(q.dtype)


def make_mask(q_positions, k_positions, causal: bool,
              window: Optional[int], is_global=True):
    """Boolean (B?,Sq,Sk) mask: True = attend. Positions may be (S,) or
    (B,S); invalid cache entries carry position -1. `is_global` may be a
    traced scalar bool (gemma3's local:global pattern scanned with shared
    weights): global layers ignore the window."""
    q = q_positions[..., :, None]
    k = k_positions[..., None, :]
    m = k >= 0
    if causal:
        m = m & (k <= q)
    if window is not None:
        m = m & ((k > q - window) | is_global)
    return m


def _ring_slots(cache, positions):
    """Per-row ring addressing shared by every contiguous-cache writer:
    (B?,S) absolute positions -> ((B,S) positions, (B,S) slots). Tokens
    with position < 0 scatter to the out-of-bounds slot `length`, which
    mode="drop" discards — a predicated write with no gather/select."""
    b, length = cache["pos"].shape
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions[None], (b, positions.shape[0]))
    slots = jnp.where(positions >= 0, positions % length, length)  # (B,S)
    return positions, slots


def _paged_address(cache, positions, tables):
    """Block-table addressing shared by every paged-cache writer:
    (B?,S) absolute positions -> ((B,S) positions, (B,S) physical block,
    (B,S) offset). Token at position p of row b lands in physical block
    ``tables[b, p // block_size]`` at offset ``p % block_size``. Tokens
    with position < 0 — and positions whose table entry is still the
    null block — address the out-of-bounds block `num_blocks`, which
    mode="drop" discards (the paged analogue of `_ring_slots`)."""
    nb_total, bs_blk = cache["pos"].shape
    b = tables.shape[0]
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions[None], (b, positions.shape[0]))
    logical = jnp.clip(
        jnp.where(positions >= 0, positions // bs_blk, 0),
        0, tables.shape[1] - 1,
    )
    phys = jnp.take_along_axis(tables, logical, axis=1)  # (B,S)
    ok = (positions >= 0) & (phys > 0)
    phys = jnp.where(ok, phys, nb_total)  # OOB -> dropped
    off = jnp.where(ok, positions % bs_blk, 0)
    return positions, phys, off


def _ring_update(cache, new_vals: dict, positions):
    """Write `new_vals[name]` (B,S,...) at per-row ring slots pos % length.

    positions: (S,) shared or (B,S) per row. Tokens with position < 0 are
    NO-OPS — the old cache entry survives. The serving engine relies on
    this twice: (a) inactive/prefilling rows ride through batched decode
    steps with position -1 without corrupting their cache, (b) left-pad
    tokens of a chunked-prefill chunk write nothing."""
    positions, slots = _ring_slots(cache, positions)
    bidx = jnp.arange(slots.shape[0])[:, None]
    out = dict(cache)
    for name, val in new_vals.items():
        out[name] = cache[name].at[bidx, slots].set(
            val.astype(cache[name].dtype), mode="drop"
        )
    out["pos"] = cache["pos"].at[bidx, slots].set(positions, mode="drop")
    return out


def _paged_update(cache, new_vals: dict, positions, tables):
    """Scatter `new_vals[name]` (B,S,...) into the paged pool through the
    per-row block tables (`_paged_address` has the addressing rules;
    inactive rows and left-pad tokens stay exact no-ops)."""
    positions, phys, off = _paged_address(cache, positions, tables)
    out = dict(cache)
    for name, val in new_vals.items():
        out[name] = cache[name].at[phys, off].set(
            val.astype(cache[name].dtype), mode="drop"
        )
    out["pos"] = cache["pos"].at[phys, off].set(positions, mode="drop")
    return out


def _paged_view(cache, tables):
    """Gather a per-row (B, blocks_per_row * block_size, ...) KV view out
    of the paged pool. Entries in logical-position order, so downstream
    masking/attention is identical to the contiguous layout; null-block
    entries carry pos -1 and mask out."""
    b, nb = tables.shape
    bs_blk = cache["pos"].shape[1]
    names = [n for n in cache if n != "pos"]
    vals = [
        cache[n][tables].reshape((b, nb * bs_blk) + cache[n].shape[2:])
        for n in names
    ]
    kpos = cache["pos"][tables].reshape(b, nb * bs_blk)
    return dict(zip(names, vals)), kpos


def reset_block_pos(cache, blocks):
    """Invalidate a fixed-width batch of physical blocks (pos -> -1); pad
    `blocks` with out-of-range ids (mode="drop" discards them). Jit-safe —
    `blocks` is a (W,) traced int array, so alloc-time clears of any count
    run through one compiled program."""
    return dict(cache, pos=cache["pos"].at[blocks].set(-1, mode="drop"))


def copy_kv_blocks(cache, src, dst):
    """Copy physical blocks src[i] -> dst[i] (copy-on-write fork). src/dst
    are (W,) traced int arrays padded with out-of-range ids; padded lanes
    read clamped garbage but scatter out-of-bounds, so they drop."""
    out = dict(cache)
    for name, val in cache.items():
        out[name] = val.at[dst].set(val[src], mode="drop")
    return out


def invalidate_kv_positions(cache, positions):
    """Speculative-decoding rollback for the contiguous ring: pos -> -1 at
    each row's ring slot for `positions` (B, W) absolute positions; lanes
    carrying -1 are no-ops. Rejected draft tokens' K/V entries were
    already unreachable (their positions exceed every future query until
    the row's write frontier overwrites them — causal masking), but
    invalidating them makes the cache state *equal* to never having
    drafted, which the rollback invariant tests check literally. Jit-safe
    fixed-width batch (one compiled signature per verify shape)."""
    _, slots = _ring_slots(cache, positions)
    bidx = jnp.arange(slots.shape[0])[:, None]
    return dict(
        cache, pos=cache["pos"].at[bidx, slots].set(-1, mode="drop")
    )


def invalidate_paged_positions(cache, positions, tables):
    """Paged analogue of `invalidate_kv_positions`: pos -> -1 through the
    block tables for `positions` (B, W); -1 lanes and null-block entries
    drop. Blocks that only held rejected tokens are separately un-reserved
    by BlockManager rollback — this clears rejected entries inside blocks
    the row keeps (the ones sharing a block with accepted tokens)."""
    _, phys, off = _paged_address(cache, positions, tables)
    return dict(cache, pos=cache["pos"].at[phys, off].set(-1, mode="drop"))


def reset_kv_rows(cache, row):
    """Invalidate row(s) of one layer's KV cache: pos -> -1. The stale K/V
    values stay in memory — they are unreachable because make_mask admits
    only entries with pos >= 0, and any later write overwrites both the
    value and its pos. `row` may be a traced scalar (jitted slot clear)."""
    return dict(cache, pos=cache["pos"].at[row].set(-1))


def _project_qkv(params, x, positions, theta: float):
    """q and k (roped at ``positions``) and v, each (b, s, heads,
    head_dim); the biases join when ``params`` holds them."""
    dt = x.dtype
    out = []
    for n in ("q", "k", "v"):
        y = jnp.einsum("bsd,dhk->bshk", x, params["w" + n].astype(dt))
        if "b" + n in params:
            y = y + params["b" + n].astype(dt)
        out.append(y if n == "v" else apply_rope(y, positions, theta))
    return out


def _attend_full_plain(params, x, theta: float):
    """What ``_attend_full`` computes, by the projections, ``apply_rope``
    and ``_attend``: the path a differentiated call takes."""
    return _attend(*_project_qkv(params, x, jnp.arange(x.shape[1]), theta),
                   None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _attend_full(params, x, num_heads: int, theta: float):
    """Unmasked self-attention of ``x`` (b, s, d) with the q/k/v weights
    (and biases) in ``params``: (b, s, heads, head_dim). One projection
    gives q, k and v side by side, which the fused kernel
    (kernels/attention_kernels.py) reads as they are and ropes itself:
    neither the logits nor a roped or transposed copy of q and k reaches
    HBM. Differentiated, it runs and differentiates ``_attend_full_plain``
    instead: at train batches the kernel is no faster than XLA's
    attention, and a backward from the kernel's outputs would recompute
    the logits once more."""
    dt = x.dtype
    names = ("q", "k", "v")
    w = jnp.concatenate([params["w" + n].astype(dt).reshape(x.shape[-1], -1)
                         for n in names], axis=1)
    qkv = jnp.einsum("bsd,de->bse", x, w)
    if "bq" in params:
        qkv = qkv + jnp.concatenate(
            [params["b" + n].astype(dt).reshape(-1) for n in names])
    head_dim = params["wq"].shape[-1]
    cos, sin = rope_cos_sin(jnp.arange(x.shape[1]), head_dim, theta)
    out = attention_kernels.mha_full(qkv, cos, sin, num_heads=num_heads)
    return out.reshape(*x.shape[:2], num_heads, head_dim)


def _attend_full_fwd(params, x, num_heads, theta):
    return jax.vjp(
        functools.partial(_attend_full_plain, theta=theta), params, x)


_attend_full.defvjp(_attend_full_fwd,
                    lambda num_heads, theta, vjp, g: vjp(g))


def gqa_apply(params, cfg, x, *, layer_is_global: bool = True,
              positions=None, cache=None, mode: str = "train",
              block_tables=None, paged_kernel: bool = False,
              use_kernel: bool = False):
    """Returns (out, new_cache). positions: (S,) shared or (B,S) per-row
    absolute token positions; entries < 0 are pad/inactive (no cache write,
    masked from attention). With ``block_tables`` (B, blocks_per_row) the
    cache is a paged block pool (init_paged_kv_cache) addressed through the
    tables instead of a per-row contiguous ring; ``paged_kernel`` routes
    single-token paged decode through the Pallas kernel that streams pool
    tiles in place (kernels/paged_attention_kernels.py) instead of
    gathering the per-row view — chunked prefill (S > 1) and traced
    ``layer_is_global`` flags keep the gather fallback.

    ``use_kernel`` takes the fused full-sequence kernel
    (kernels/attention_kernels.py) when nothing is masked: no cache, not
    causal, no sliding window, no positions given (so every token is a
    real one), as many kv heads as query heads, and heads and sequence
    length the kernel ``fits``. Every other call attends
    through ``_attend`` / ``_attend_chunked``."""
    a = cfg.attention
    b, s, _ = x.shape
    if (use_kernel and cache is None and positions is None
            and not cfg.causal and a.sliding_window is None
            and a.num_kv_heads == a.num_heads
            and attention_kernels.fits(s, a.num_heads, a.head_dim)):
        qkv_params = {k: v for k, v in params.items() if k != "wo"}
        out = _attend_full(qkv_params, x, a.num_heads, a.rope_theta)
        return jnp.einsum("bshk,hkd->bsd", out,
                          params["wo"].astype(x.dtype)), None
    if positions is None:
        positions = jnp.arange(s)
    q, k, v = _project_qkv(params, x, positions, a.rope_theta)

    # `layer_is_global` may be traced (scanned local:global pattern), so
    # the window is applied inside the mask rather than branched on here.
    window = a.sliding_window

    if cache is None:
        k_all, v_all, kpos = k, v, positions
    elif block_tables is not None:
        # Paged path: scatter this call's KV through the block tables
        # (write-then-read keeps chunked prefill self-attending, exactly
        # like the ring path below), then attend the pool — in place via
        # the Pallas kernel on the decode hot path, or through the
        # gathered row view (the kernel's oracle / S>1 fallback). The
        # speculative-decoding verify step (serve/spec_decode.py) is an
        # S = k+1 decode continuation and deliberately takes the gather
        # route: every lane needs its own causal slice of the pool, which
        # is exactly the chunked-prefill contract (a multi-query kernel
        # variant is a recorded follow-up).
        assert mode != "prefill", "paged cache serves chunked prefill only"
        cache = _paged_update(cache, {"k": k, "v": v}, positions,
                              block_tables)
        if (paged_kernel and s == 1
                and not isinstance(layer_is_global, jax.core.Tracer)):
            qpos = (positions[:, 0] if positions.ndim == 2
                    else jnp.broadcast_to(positions[0], (b,)))
            out = paged_decode_attend(
                q[:, 0], cache["k"], cache["v"], cache["pos"],
                block_tables, qpos, causal=cfg.causal, window=window,
                is_global=bool(layer_is_global),
            )[:, None]
            out = jnp.einsum("bshk,hkd->bsd", out,
                             params["wo"].astype(x.dtype))
            return out, cache
        gathered, kpos = _paged_view(cache, block_tables)
        k_all, v_all = gathered["k"], gathered["v"]
    else:
        cache = _ring_update(cache, {"k": k, "v": v}, positions)
        if s > 1 and mode == "prefill":
            # Whole-prompt prefill: attend the input KV directly — the ring
            # buffer may already have wrapped (window < prefill length), so
            # the cache is only valid for *subsequent* decode steps.
            k_all, v_all, kpos = k, v, positions
        else:
            # Decode (s==1) and chunked-prefill continuation (s>1 with
            # mode="decode"): attend over the cache, which now holds both
            # prior chunks and the tokens just written.
            k_all, v_all, kpos = cache["k"], cache["v"], cache["pos"]

    # Flash-style path for long KV: never materializes (Sq, Sk) logits.
    if k_all.shape[1] * max(s, 1) > _CHUNKED_THRESHOLD:
        out = _attend_chunked(q, k_all, v_all, positions, kpos,
                              cfg.causal, window, is_global=layer_is_global)
    else:
        mask = make_mask(positions, kpos, cfg.causal, window,
                         layer_is_global)
        if mask.ndim == 2:  # shared (S,) positions -> add batch dim
            mask = mask[None]
        out = _attend(q, k_all, v_all, mask)
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return out, cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2)
# ---------------------------------------------------------------------------


def mla_init(rng, cfg):
    a = cfg.attention
    d = cfg.d_model
    rs = split_rngs(rng, 6)
    qk_head = a.qk_nope_head_dim + a.qk_rope_head_dim
    return {
        "wq": lecun_init(rs[0], (d, a.num_heads, qk_head), fan_in=d),
        "w_dkv": lecun_init(rs[1], (d, a.kv_lora_rank), fan_in=d),
        "w_krope": lecun_init(rs[2], (d, a.qk_rope_head_dim), fan_in=d),
        "w_uk": lecun_init(
            rs[3], (a.kv_lora_rank, a.num_heads, a.qk_nope_head_dim),
            fan_in=a.kv_lora_rank,
        ),
        "w_uv": lecun_init(
            rs[4], (a.kv_lora_rank, a.num_heads, a.v_head_dim),
            fan_in=a.kv_lora_rank,
        ),
        "wo": lecun_init(
            rs[5], (a.num_heads, a.v_head_dim, d),
            fan_in=a.num_heads * a.v_head_dim,
        ),
    }


def mla_apply(params, cfg, x, *, positions=None, cache=None,
              mode: str = "train", layer_is_global: bool = True,
              block_tables=None, paged_kernel: bool = False,
              use_kernel: bool = False):
    """MLA with compressed-KV cache. Decode uses the *absorbed* form:
    q_nope is projected into the latent rank space so attention scores are
    computed against the (B, S, rank) cache directly — no per-step
    re-expansion of K (the production DeepSeek inference trick).

    ``paged_kernel`` is accepted for signature parity but MLA keeps the
    gather fallback: the absorbed decode attends a latent cache whose
    score/value widths differ (rank + rope vs rank), and the latent-pool
    kernel variant is a recorded follow-up (ROADMAP). ``use_kernel`` is
    accepted for the same parity: MLA's full-sequence calls keep
    ``_attend``."""
    del paged_kernel, use_kernel
    a = cfg.attention
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.arange(s)
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    q_nope = q[..., : a.qk_nope_head_dim]
    q_rope = apply_rope(q[..., a.qk_nope_head_dim:], positions, a.rope_theta)

    ckv = jnp.einsum("bsd,dr->bsr", x, params["w_dkv"].astype(dt))
    krope = apply_rope(
        jnp.einsum("bsd,dk->bsk", x, params["w_krope"].astype(dt))[
            :, :, None
        ],
        positions,
        a.rope_theta,
    )[:, :, 0]

    scale = 1.0 / float(a.qk_nope_head_dim + a.qk_rope_head_dim) ** 0.5

    if cache is not None and block_tables is not None:
        assert mode != "prefill", "paged cache serves chunked prefill only"
        cache = _paged_update(cache, {"ckv": ckv, "krope": krope},
                              positions, block_tables)
        gathered, kpos = _paged_view(cache, block_tables)
        ckv_all, krope_all = gathered["ckv"], gathered["krope"]
    elif cache is not None:
        cache = _ring_update(cache, {"ckv": ckv, "krope": krope}, positions)
        if s > 1 and mode == "prefill":
            # whole-prompt prefill: attend input latents (see gqa_apply)
            ckv_all, krope_all, kpos = ckv, krope, positions
        else:  # decode / chunked-prefill continuation: attend the cache
            ckv_all, krope_all = cache["ckv"], cache["krope"]
            kpos = cache["pos"]
    else:
        ckv_all, krope_all, kpos = ckv, krope, positions

    # Absorbed form: project q_nope into the latent rank space, then MLA is
    # exactly MHA with a single shared KV "head" of dim (rank + rope_dim)
    # for scores and dim rank for values — so it reuses the dense/flash
    # attend paths (and the compressed cache is attended to directly).
    q_lat = jnp.einsum(
        "bshk,rhk->bshr", q_nope.astype(jnp.float32),
        params["w_uk"].astype(jnp.float32),
    ).astype(dt)
    q_cat = jnp.concatenate([q_lat, q_rope], axis=-1)  # (b,s,h,r+rd)
    k_cat = jnp.concatenate([ckv_all, krope_all], axis=-1)[:, :, None]
    v_lat = ckv_all[:, :, None]  # (b,t,1,r)

    if k_cat.shape[1] * max(s, 1) > _CHUNKED_THRESHOLD:
        lat = _attend_chunked(q_cat, k_cat, v_lat, positions, kpos,
                              cfg.causal, None, scale=scale)
    else:
        mask = make_mask(positions, kpos, cfg.causal, None)
        if mask.ndim == 2:
            mask = mask[None]
        lat = _attend(q_cat, k_cat, v_lat, mask, scale=scale)

    # Expand the weighted latent through W_uv once.
    out = jnp.einsum(
        "bshr,rhv->bshv", lat.astype(jnp.float32),
        params["w_uv"].astype(jnp.float32),
    )
    out = jnp.einsum("bshv,hvd->bsd", out.astype(dt), params["wo"].astype(dt))
    return out, cache


def attention_init(rng, cfg):
    return mla_init(rng, cfg) if cfg.attention.kind == "mla" else gqa_init(rng, cfg)


def attention_apply(params, cfg, x, **kw):
    if cfg.attention.kind == "mla":
        return mla_apply(params, cfg, x, **kw)
    return gqa_apply(params, cfg, x, **kw)
