"""Vision Transformer — the paper's own backbone family (§3). Patch
embeddings come from the frontend stub (flattened patches projected
linearly); encoder blocks are non-causal; classification by mean-pool +
linear head (the v-moe/ViT "gap" head). Soft MoE / sparse MoE layers slot
into the second half of blocks per the config.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..distributed.api import constrain
from ..layers.common import lecun_init, norm_apply, norm_init, split_rngs, stack_pytrees, truncated_normal
from .lm import block_apply, block_init, segment_plan


def vit_init(rng, cfg, num_classes: int = 1000):
    rs = split_rngs(rng, 5)
    d = cfg.d_model
    params = {
        "patch_proj": {
            "w": lecun_init(rs[0], (cfg.frontend.embed_dim, d),
                            fan_in=cfg.frontend.embed_dim),
            "b": jnp.zeros((d,)),
        },
        "pos_emb": truncated_normal(rs[1], (cfg.frontend.num_embeds, d), 0.02),
        "segments": [
            stack_pytrees(
                [
                    block_init(jax.random.fold_in(rs[2], start + j), cfg, is_moe)
                    for j in range(count)
                ]
            )
            for start, count, is_moe in segment_plan(cfg)
        ],
        "final_norm": norm_init(cfg, d),
        "head": {
            "w": jnp.zeros((d, num_classes)),
            "b": jnp.zeros((num_classes,)),
        },
    }
    return params


def _encode(params, cfg, patches, use_kernel: bool):
    """patches: (B, num_patches, patch_dim) -> the final-normed tokens
    (B, num_patches, d) and the blocks' summed aux loss."""
    dt = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        x = patches.astype(dt) @ params["patch_proj"]["w"].astype(dt)
        x = x + params["patch_proj"]["b"].astype(dt)
        x = x + params["pos_emb"].astype(dt)
        x = constrain(x, "batch", None, None)
    aux = jnp.zeros((), jnp.float32)
    with jax.named_scope("encoder"):
        for seg_params, (start, count, is_moe) in zip(
            params["segments"], segment_plan(cfg)
        ):
            def body(carry, p, _is_moe=is_moe):
                y, _, a = block_apply(
                    p, cfg, carry, is_moe=_is_moe, mode="train",
                    use_kernel=use_kernel,
                )
                return y, a

            if cfg.remat:
                body = jax.checkpoint(body, prevent_cse=False)
            x, auxs = jax.lax.scan(body, x, seg_params)
            aux = aux + auxs.sum()
    with jax.named_scope("final_norm"):
        x = norm_apply(params["final_norm"], cfg, x)
    return x, aux


def vit_apply(params, cfg, patches, use_kernel: bool = False):
    """patches: (B, num_patches, patch_dim) -> (B, num_classes) logits."""
    x, aux = _encode(params, cfg, patches, use_kernel)
    with jax.named_scope("head"):
        pooled = x.mean(axis=1).astype(jnp.float32)
        logits = (pooled @ params["head"]["w"].astype(jnp.float32)
                  + params["head"]["b"])
    return logits, aux


def vit_loss(params, cfg, batch, use_kernel: bool = False):
    logits, aux = vit_apply(params, cfg, batch["patches"],
                            use_kernel=use_kernel)
    with jax.named_scope("loss"):
        labels = batch["labels"]
        lp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(lp, labels[:, None], axis=-1).mean()
        acc = (logits.argmax(-1) == labels).mean()
        return nll + aux, {"loss": nll, "aux_loss": aux, "accuracy": acc}


def vit_features(params, cfg, patches, use_kernel: bool = False):
    """Mean-pooled pre-head features (for the LIT-style contrastive example)."""
    return _encode(params, cfg, patches, use_kernel)[0].mean(axis=1)
