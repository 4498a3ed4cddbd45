"""Soft MoE — the paper's contribution (Puigcerver et al., ICLR 2024, §2).

Faithful to Algorithm 1 + the Algorithm 2 L2-normalization fix:

    logits = l2norm(X) @ (scale * l2norm(Phi))        # (m, n·p)
    D = softmax over tokens  (per slot / column)       # dispatch
    C = softmax over slots   (per token / row)         # combine
    X~ = Dᵀ X ; Y~_i = f_{⌊i/p⌋}(X~_i) ; Y = C Y~

Every op is continuous/differentiable; there is no top-k/sort anywhere on
this path (the paper's perf point). Experts are stacked along a leading
axis so they shard over the `model` mesh axis (expert parallelism); Phi is
sharded over its slot axis the same way.

``use_kernel=True`` routes dispatch/combine through the fused Pallas TPU
kernels in ``repro.kernels`` (interpret-mode on CPU).

Per-sequence invariant: both softmaxes normalize WITHIN one sequence —
dispatch over that sequence's m tokens (axis 1), combine over its n·p
slots — and the expert mixes are per-row weighted sums, so a sequence's
output is identical however it is batched (the paper's §3.5 contrast
with sparse routing, and the reason Soft MoE is batch-invariant at
serving with no mode switch; the fused kernels keep the batch axis a
pure grid axis — see kernels/soft_moe_kernels.py — and ref.py states the
same math for a single sequence). Unlike the sparse variants there is no
train/serve routing split to thread ``mode`` through.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..distributed.api import constrain
from ..layers.common import l2_normalize, lecun_init, split_rngs
from ..layers.mlp import expert_init, experts_apply


def soft_moe_init(rng, d_model: int, moe_cfg, style: str = "gated"):
    r_phi, r_e = split_rngs(rng, 2)
    n, p = moe_cfg.num_experts, moe_cfg.slots_per_expert
    d_ff = moe_cfg.expert_d_ff
    params = {
        "phi": lecun_init(r_phi, (d_model, n, p), fan_in=d_model),
        "scale": jnp.ones(()),
        "experts": expert_init(r_e, n, d_model, d_ff, style),
    }
    if moe_cfg.num_shared_experts:
        params["shared"] = expert_init(
            jax.random.fold_in(r_e, 1), moe_cfg.num_shared_experts, d_model,
            d_ff, style,
        )
    return params


def soft_moe_weights(x, phi, scale, normalize: bool = True):
    """Dispatch/combine weights for one sequence batch.

    x: (b, m, d); phi: (d, n, p). Returns (d_weights, c_weights), both
    (b, m, n, p): D normalized over m, C normalized over (n, p).
    """
    if normalize:
        x = l2_normalize(x, axis=-1)
        phi = scale * l2_normalize(phi, axis=0)
    logits = jnp.einsum(
        "bmd,dnp->bmnp", x.astype(jnp.float32), phi.astype(jnp.float32)
    )
    d_weights = jax.nn.softmax(logits, axis=1)  # over tokens (per slot)
    b, m, n, p = logits.shape
    c_weights = jax.nn.softmax(
        logits.reshape(b, m, n * p), axis=-1
    ).reshape(b, m, n, p)  # over all slots (per token)
    return d_weights, c_weights


def soft_moe_apply(params, moe_cfg, x, act: str = "silu",
                   use_kernel: bool = False, telemetry: bool = False):
    """x: (b, m, d) -> (b, m, d). Returns (y, metrics).

    ``telemetry=True`` adds a ``metrics["telemetry"]`` dict of
    ``stop_gradient``'d f32 scalars — the Fig. 9 routing-health set (see
    docs/observability.md). It never changes ``y``: the kernel path reads
    the routing pass's saved softmax stats (plus one extra logits pass in
    ``routing_health``) instead of materializing the (m × S) weights.
    """
    b, m, d = x.shape
    n, p = moe_cfg.num_experts, moe_cfg.slots_per_expert
    phi = params["phi"]
    c_weights = c_stats = d_w = d_stats = None
    with jax.named_scope("dispatch"):
        if use_kernel:
            from ..kernels import ops as kops
            from ..kernels.tuning import config_from_moe

            kcfg = config_from_moe(moe_cfg, m=m, d=d)
            phi_n = kops.normalized_phi(phi, params["scale"])
            # one logits pass: dispatched slots + the combine softmax stats
            if telemetry:
                slots, d_stats, c_stats = kops.soft_moe_routing(
                    x, phi_n, config=kcfg, with_d_stats=True)
            else:
                slots, c_stats = kops.soft_moe_routing(x, phi_n, config=kcfg)
            slots = slots.reshape(b, n, p, d)  # (b, n·p, d) -> (b, n, p, d)
        else:
            d_w, c_weights = soft_moe_weights(x, phi, params["scale"])
            # Distribution note: GSPMD's propagated layout (slot axis of the
            # weight tensors sharded with Phi over `model`) is left alone.
            # Forcing slot-replication here (gather the small axis early,
            # avoid the combine all-reduce) was tried and REFUTED — it ADDED
            # ~1.3s/step of resharding traffic at deepseek+soft:train_4k
            # (EXPERIMENTS.md §Perf, H7).
            # input slots: weighted average of all tokens per slot
            slots = jnp.einsum("bmd,bmnp->bnpd", x.astype(jnp.float32), d_w)
        slots = slots.astype(x.dtype)

    # expert compute: (b,n,p,d) -> (n, b*p, d) so the expert axis leads
    # (sharded over `model` = expert parallelism)
    with jax.named_scope("experts"):
        ys = slots.transpose(1, 0, 2, 3).reshape(n, b * p, d)
        ys = experts_apply(params["experts"], ys, act)
        ys = ys.reshape(n, b, p, d).transpose(1, 0, 2, 3)  # (b,n,p,d)

    with jax.named_scope("combine"):
        if use_kernel:
            y = kops.soft_moe_combine(x, phi_n, ys.reshape(b, n * p, d),
                                      c_stats=c_stats, config=kcfg)
        else:
            y = jnp.einsum(
                "bnpd,bmnp->bmd", ys.astype(jnp.float32), c_weights
            )
        y = y.astype(x.dtype)

    if moe_cfg.num_shared_experts:
        # reshape once; experts_apply broadcasts the leading expert axis
        # (no (num_shared × b·m × d) materialization).
        with jax.named_scope("experts"):
            sh = experts_apply(params["shared"], x.reshape(1, b * m, d), act)
        y = y + sh.sum(0).reshape(b, m, d)

    metrics = {
        "moe_aux_loss": jnp.zeros((), jnp.float32),  # balanced by construction
    }
    # model-inspection stat (paper §5 / App. E): max combine weight —
    # values approaching 1.0 signal the softmax collapse the L2-norm fix
    # prevents. On the kernel path it falls out of the saved softmax
    # stats: the max weight for token i is exp(mx_i − mx_i)/den_i = 1/den_i.
    if c_weights is not None:
        metrics["max_combine"] = jax.lax.stop_gradient(c_weights.max())
    elif c_stats is not None:
        metrics["max_combine"] = jax.lax.stop_gradient(
            (1.0 / c_stats[1]).max()
        )
    if telemetry:
        if use_kernel:
            sg = jax.lax.stop_gradient
            dent, imp, cent, contrib = kops.routing_health(
                sg(x), sg(phi_n), jax.tree_util.tree_map(sg, d_stats),
                jax.tree_util.tree_map(sg, c_stats), config=kcfg)
            max_dispatch = (1.0 / d_stats[1]).max()
        else:
            dent, imp, cent, contrib = _dense_routing_health(d_w, c_weights)
            max_dispatch = d_w.max()
        imp_e = imp.reshape(b, n, p).sum(axis=(0, 2))  # per-expert mass
        metrics["telemetry"] = jax.tree_util.tree_map(
            jax.lax.stop_gradient,
            {
                "max_combine": metrics["max_combine"],
                "max_dispatch": max_dispatch.astype(jnp.float32),
                "dispatch_entropy": dent.mean().astype(jnp.float32),
                "combine_entropy": cent.mean().astype(jnp.float32),
                "expert_importance_spread": (
                    imp_e.max() / jnp.clip(imp_e.min(), 1e-9)
                ).astype(jnp.float32),
                "token_contribution_min": contrib.min().astype(jnp.float32),
                "token_contribution_max": contrib.max().astype(jnp.float32),
                # per-sequence rows (b,) for the batch-variance probe:
                # Soft-MoE softmaxes are per-row, so these should NOT move
                # with batch composition — the probe's null hypothesis
                "rows": {
                    "dispatch_entropy": dent.mean(axis=1).astype(
                        jnp.float32),
                    "combine_entropy": cent.mean(axis=1).astype(jnp.float32),
                    "token_contribution_min": contrib.min(axis=1).astype(
                        jnp.float32),
                },
            },
        )
    return y, metrics


def _dense_routing_health(d_w, c_weights):
    """Dense oracle for the kernel's routing_health reductions.

    d_w/c_weights: (b, m, n, p) softmax weights. Returns the same
    (disp_entropy (b, S), importance (b, S), comb_entropy (b, m),
    token_contrib (b, m)) tuple as ``kernels.ops.routing_health``.
    """
    b, m, n, p = d_w.shape
    d_flat = d_w.reshape(b, m, n * p)
    c_flat = c_weights.reshape(b, m, n * p)

    def _ent(w, axis):
        return -jnp.sum(jnp.where(w > 0, w * jnp.log(jnp.clip(w, 1e-30)),
                                  0.0), axis=axis)

    return (_ent(d_flat, 1), c_flat.sum(1), _ent(c_flat, 2), d_flat.sum(2))
