"""Decoder-only LM covering the dense / ssm / hybrid / moe / vlm families.

Training lowers one scan-over-layers per homogeneous *segment* (contiguous
layers with the same block structure — e.g. deepseek = [1 dense layer] +
[26 MoE layers]) with remat, MaxText-style: HLO size and compile time stay
bounded for 80-layer models. Serving (prefill/decode) unrolls a python loop
over layers so per-layer caches may be heterogeneous (ring buffers for
sliding-window layers, full-length for global layers, SSM states).
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import moe_apply, moe_init, resolve_moe_cfg
from ..distributed.api import constrain
from ..layers.attention import attention_apply, attention_init, init_kv_cache
from ..layers.common import lecun_init, norm_apply, norm_init, split_rngs, stack_pytrees
from ..layers.embedding import embed, embedding_init, unembed
from ..layers.mlp import mlp_apply, mlp_init
from ..layers.ssm import init_ssm_cache, ssm_apply, ssm_init


# ---------------------------------------------------------------------------
# Segment plan
# ---------------------------------------------------------------------------


def segment_plan(cfg, n_layers: Optional[int] = None) -> List[Tuple[int, int, bool]]:
    """Contiguous runs of (start, count, is_moe) with identical structure."""
    n = n_layers if n_layers is not None else cfg.num_layers
    moe_idx = set(cfg.moe_layer_indices())
    segs: List[Tuple[int, int, bool]] = []
    for i in range(n):
        is_moe = i in moe_idx
        if segs and segs[-1][2] == is_moe:
            start, count, _ = segs[-1]
            segs[-1] = (start, count + 1, is_moe)
        else:
            segs.append((i, 1, is_moe))
    return segs


# ---------------------------------------------------------------------------
# Transformer block
# ---------------------------------------------------------------------------


def block_init(rng, cfg, is_moe: bool):
    rs = split_rngs(rng, 4)
    d = cfg.d_model
    p = {"norm1": norm_init(cfg, d)}
    if cfg.has_attention():
        p["attn"] = attention_init(rs[0], cfg)
    if cfg.has_ssm():
        p["ssm"] = ssm_init(rs[1], cfg)
    if is_moe:
        p["norm2"] = norm_init(cfg, d)
        p["moe"] = moe_init(rs[2], d, resolve_moe_cfg(cfg.moe, cfg.d_ff),
                            cfg.mlp_style)
    elif cfg.d_ff > 0:
        p["norm2"] = norm_init(cfg, d)
        p["mlp"] = mlp_init(rs[3], d, cfg.d_ff, cfg.mlp_style)
    return p


def block_apply(params, cfg, x, *, is_moe: bool, is_global=True,
                positions=None, cache=None, mode: str = "train",
                use_kernel: bool = False, block_tables=None,
                paged_kernel: bool = False, telemetry: bool = False):
    """Returns (y, new_cache, aux) — or (y, new_cache, aux, telem) when
    ``telemetry=True``. `is_global` may be a traced bool (scan over
    gemma3's 5-local:1-global pattern with shared weights).
    ``block_tables`` (B, blocks_per_row) switches attention caches to the
    paged block-pool layout (shared by every layer — all attention layers
    write the same positions); ``paged_kernel`` additionally routes paged
    single-token decode through the Pallas paged-attention kernel.
    ``use_kernel`` routes Soft MoE through its Pallas kernels and
    cache-less, unmasked attention through the fused full-sequence
    kernel (``gqa_apply`` states when).

    ``telemetry`` is a static build flag: the extra return is a dict of
    ``stop_gradient``'d f32 scalars (attention-path absmax, residual RMS,
    and the MoE routing-health set) with a structure fixed by the arch —
    the block's output is bit-identical either way."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = {} if cache is not None else None
    with jax.named_scope("norm"):
        xn = norm_apply(params["norm1"], cfg, x)
    mix = 0.0
    a_out = None
    if cfg.has_attention():
        with jax.named_scope("attention"):
            a_out, a_cache = attention_apply(
                params["attn"], cfg, xn,
                layer_is_global=is_global, positions=positions,
                cache=None if cache is None else cache.get("attn"),
                mode=mode, block_tables=block_tables,
                paged_kernel=paged_kernel, use_kernel=use_kernel,
            )
        mix = mix + a_out
        if new_cache is not None:
            new_cache["attn"] = a_cache
    if cfg.has_ssm():
        with jax.named_scope("ssm"):
            s_out, s_cache = ssm_apply(
                params["ssm"], cfg, xn,
                cache=None if cache is None else cache.get("ssm"),
                mode=mode, positions=positions,
            )
        if cfg.hybrid_parallel and cfg.has_attention():
            mix = (mix + s_out) * 0.5  # Hymba: mean-fuse parallel heads
        else:
            mix = mix + s_out
        if new_cache is not None:
            new_cache["ssm"] = s_cache
    x = x + constrain(mix, "batch", "seq", None)

    moe_telem = None
    if "norm2" in params:
        with jax.named_scope("norm"):
            xn = norm_apply(params["norm2"], cfg, x)
        if is_moe:
            with jax.named_scope("moe"):
                m_out, metrics = moe_apply(
                    params["moe"], resolve_moe_cfg(cfg.moe, cfg.d_ff), xn,
                    cfg.act, use_kernel=use_kernel, telemetry=telemetry,
                    mode=mode,
                )
            aux = aux + metrics["moe_aux_loss"]
            moe_telem = metrics.get("telemetry")
        else:
            with jax.named_scope("mlp"):
                m_out = mlp_apply(params["mlp"], xn, cfg.act)
        x = x + constrain(m_out, "batch", "seq", None)
    if not telemetry:
        return x, new_cache, aux
    sg = jax.lax.stop_gradient
    telem = {
        "residual_rms": sg(jnp.sqrt(
            jnp.mean(jnp.square(x.astype(jnp.float32))))),
    }
    if a_out is not None:
        telem["max_attn_out"] = sg(
            jnp.abs(a_out.astype(jnp.float32)).max())
    if moe_telem is not None:
        telem["moe"] = moe_telem
    return x, new_cache, aux, telem


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


def _layer_flags(cfg, start: int, count: int):
    a = cfg.attention
    if a is None:
        return jnp.ones((count,), bool)
    return jnp.array(
        [a.is_global_layer(start + j) for j in range(count)], bool
    )


def lm_init(rng, cfg):
    rs = split_rngs(rng, 4)
    params = {"embed": embedding_init(rs[0], cfg.vocab_size, cfg.d_model)}
    if cfg.frontend.kind != "none":
        params["frontend"] = {
            "w": lecun_init(
                rs[1], (cfg.frontend.embed_dim, cfg.d_model),
                fan_in=cfg.frontend.embed_dim,
            )
        }
    segs = segment_plan(cfg)
    seg_params = []
    for start, count, is_moe in segs:
        blocks = [
            block_init(jax.random.fold_in(rs[2], start + j), cfg, is_moe)
            for j in range(count)
        ]
        seg_params.append(stack_pytrees(blocks))
    params["segments"] = seg_params
    params["final_norm"] = norm_init(cfg, cfg.d_model)
    if not cfg.tie_embeddings and cfg.vocab_size:
        params["unembed"] = embedding_init(rs[3], cfg.vocab_size, cfg.d_model)
    return params


def _remat_policy(cfg):
    # "nothing": recompute everything inside the layer (min memory).
    # "dots": save matmul outputs with no batch dims (less recompute).
    name = getattr(cfg, "remat_policy", "nothing")
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable


def _scan_segment(seg_params, cfg, x, flags, is_moe, use_kernel, positions,
                  telemetry=False):
    def body(carry, xs):
        p, is_global = xs
        out = block_apply(
            p, cfg, carry, is_moe=is_moe, is_global=is_global,
            positions=positions, cache=None, mode="train",
            use_kernel=use_kernel, telemetry=telemetry,
        )
        if telemetry:
            y, _, aux, telem = out
            return y, (aux, telem)
        y, _, aux = out
        return y, aux

    if cfg.remat:
        body = jax.checkpoint(
            body, policy=_remat_policy(cfg), prevent_cse=False
        )
    x, ys = jax.lax.scan(body, x, (seg_params, flags))
    if telemetry:
        auxs, telem = ys  # telem leaves stacked over the segment: (count,)
        return x, auxs.sum(), telem
    return x, ys.sum(), None


def _unrolled_segment(seg_params, cfg, x, start, count, is_moe, caches,
                      positions, mode, use_kernel, block_tables=None,
                      paged_kernel=False, telemetry=False):
    """Python loop (serving path / scan_layers=False): heterogeneous caches."""
    aux = jnp.zeros((), jnp.float32)
    new_caches = []
    telems = {}
    for j in range(count):
        p = jax.tree_util.tree_map(lambda a: a[j], seg_params)
        is_global = (
            cfg.attention.is_global_layer(start + j)
            if cfg.attention is not None
            else True
        )
        cache_j = caches[start + j] if caches is not None else None
        out = block_apply(
            p, cfg, x, is_moe=is_moe, is_global=is_global,
            positions=positions, cache=cache_j, mode=mode,
            use_kernel=use_kernel, block_tables=block_tables,
            paged_kernel=paged_kernel, telemetry=telemetry,
        )
        if telemetry:
            x, c, a, telems[start + j] = out
        else:
            x, c, a = out
        aux = aux + a
        new_caches.append(c)
    return x, aux, new_caches, telems


def lm_apply(params, cfg, tokens, *, embeds=None, positions=None,
             cache=None, mode: str = "train", use_kernel: bool = False,
             last_only: bool = False, block_tables=None,
             paged_kernel: bool = False, telemetry: bool = False):
    """tokens: (B, S) int32; embeds: (B, N, E) frontend stub (vlm);
    positions: (S,) shared or (B, S) per-row (continuous-batching decode —
    entries < 0 mark pad/inactive tokens that neither write nor read any
    cache). ``block_tables`` (B, blocks_per_row) makes every attention
    cache a paged block pool (serve/block_manager.py) addressed through
    the tables; ``paged_kernel`` streams paged single-token decode through
    the Pallas paged-attention kernel instead of gathering per-row KV
    views. Returns (logits, new_cache, aux) — plus a trailing ``telem``
    pytree when ``telemetry=True`` (a STATIC build flag, never traced:
    existing 3-tuple call sites are untouched). ``telem`` holds
    fixed-shape ``stop_gradient``'d stats: per-layer block/MoE health
    keyed by layer index (scan segments stack leaves to ``(count,)``)
    and per-row logit numerics probes. ``last_only`` unembeds
    only the final position — prefill needs one next-token distribution,
    not S×vocab logits (at qwen2-72b:prefill_32k the full-logit tensor is
    32×32768×152064 f32 ≈ 638GB global)."""
    dtype = jnp.dtype(cfg.dtype)
    x = embed(params["embed"], tokens, dtype)
    if embeds is not None and "frontend" in params:
        fe = embeds.astype(dtype) @ params["frontend"]["w"].astype(dtype)
        x = jnp.concatenate([fe, x], axis=1)
    s = x.shape[1]
    if positions is None:
        positions = jnp.arange(s)
    x = constrain(x, "batch", "seq", None)

    aux = jnp.zeros((), jnp.float32)
    layer_telem = {}
    segs = segment_plan(cfg)
    if cache is None and cfg.scan_layers and mode == "train":
        for seg_params, (start, count, is_moe) in zip(params["segments"], segs):
            flags = _layer_flags(cfg, start, count)
            x, a, t = _scan_segment(
                seg_params, cfg, x, flags, is_moe, use_kernel, positions,
                telemetry,
            )
            aux = aux + a
            if t is not None:
                layer_telem[start] = t  # leaves stacked (count,)
        new_cache = None
    else:
        new_cache = []
        for seg_params, (start, count, is_moe) in zip(params["segments"], segs):
            x, a, cs, ts = _unrolled_segment(
                seg_params, cfg, x, start, count, is_moe, cache,
                positions, mode, use_kernel, block_tables, paged_kernel,
                telemetry,
            )
            aux = aux + a
            new_cache.extend(cs)
            layer_telem.update(ts)
        if cache is None:
            new_cache = None

    if last_only:
        x = x[:, -1:]
    x = norm_apply(params["final_norm"], cfg, x)
    table = params["unembed"] if "unembed" in params else params["embed"]
    logits = unembed(table, x, cfg.logits_softcap)
    if not telemetry:
        return logits, new_cache, aux
    sg = jax.lax.stop_gradient
    lf = logits.astype(jnp.float32)
    p = jax.nn.softmax(lf, axis=-1)
    lse = jax.nn.logsumexp(lf, axis=-1)
    telem = {
        "layers": layer_telem,
        "logits": {
            # per-row reductions: (B,) — continuous batching mixes
            # unrelated requests in one tick, so rows stay separable
            "max_abs_logit": sg(jnp.abs(lf).max(axis=(1, 2))),
            "softmax_entropy": sg((lse - jnp.sum(p * lf, axis=-1)
                                   ).mean(axis=1)),
            "nonfinite_count": sg(jnp.sum(
                ~jnp.isfinite(lf), axis=(1, 2)).astype(jnp.float32)),
        },
    }
    return logits, new_cache, aux, telem


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Per-layer cache list (python list pytree — heterogeneous lengths).

    Every leaf has a leading `batch` dim, and attention caches carry a
    per-row (batch, length) `pos` array — rows advance independently, so
    the serving layer (serve/cache_pool.py) can admit/retire individual
    rows at any decode step (continuous batching)."""
    caches = []
    for i in range(cfg.num_layers):
        c = {}
        if cfg.has_attention():
            a = cfg.attention
            c["attn"] = init_kv_cache(
                cfg, batch, max_len, a.is_global_layer(i), dtype
            )
        if cfg.has_ssm():
            c["ssm"] = init_ssm_cache(cfg, batch, dtype)
        caches.append(c)
    return caches


def lm_loss(params, cfg, batch, use_kernel: bool = False,
            telemetry: bool = False):
    """Next-token cross-entropy. batch: {"tokens": (B,S) [, "embeds"]}

    ``telemetry=True`` (static flag) adds the ``lm_apply`` telemetry
    pytree under ``metrics["telemetry"]`` — loss value is unchanged."""
    tokens = batch["tokens"]
    out = lm_apply(
        params, cfg, tokens, embeds=batch.get("embeds"), mode="train",
        use_kernel=use_kernel, telemetry=telemetry,
    )
    telem = out[3] if telemetry else None
    logits, _, aux = out[:3]
    # frontend embeds prepend non-text positions; score text only
    n_prefix = logits.shape[1] - tokens.shape[1]
    logits = logits[:, n_prefix:]
    targets = tokens[:, 1:]
    nll = cross_entropy(logits[:, :-1], targets)
    mask = batch.get("loss_mask")
    if mask is not None:
        mask = mask[:, 1:].astype(jnp.float32)
        loss = (nll * mask).sum() / jnp.clip(mask.sum(), 1.0)
    else:
        loss = nll.mean()
    metrics = {"loss": loss, "aux_loss": aux}
    if telem is not None:
        metrics["telemetry"] = telem
    return loss + aux, metrics


def cross_entropy(logits, targets):
    """Sharding-friendly CE: lse(logits) - logits[target]. Unlike
    take_along_axis over the (model-sharded) vocab axis — which forces an
    all-gather of the full logits (40GB/device at the 152k-vocab train_4k
    cell) — both terms reduce over the local vocab shard and psum per
    token. The target pick is a masked reduce (select fuses; an explicit
    one_hot would materialize a (B,S,V/16) f32 tensor)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
    picked = jnp.sum(
        jnp.where(vocab_iota == targets[..., None], logits, 0.0), axis=-1
    )
    return lse - picked
