"""Operations and bytes the algorithm needs, counted from shapes.

These are the algorithm's, not this implementation's: a kernel that
recomputes, pads or reads a tensor twice does more than is counted here,
so a share of the roofline or of the peak computed from them cannot pass
100%. A multiply-add is 2 operations; elementwise work, softmaxes and norms
are not counted (the usual convention for model FLOPs).
"""
from __future__ import annotations

from .ref_vit import moe_layers


def vit_forward_flops(cfg: dict) -> float:
    """Forward operations for one image of the ViT configuration file."""
    m, d = cfg["num_patches"], cfg["hidden_size"]
    hk = cfg["num_attention_heads"] * cfg["head_dim"]
    f, s = cfg["intermediate_size"], cfg["num_experts"] * cfg[
        "slots_per_expert"]
    n = cfg["num_hidden_layers"]
    n_moe = len(moe_layers(cfg))
    attn = 2 * m * d * 3 * hk + 2 * 2 * m * m * hk + 2 * m * hk * d
    mlp = 2 * 2 * m * d * f
    moe = softmoe_fwd(1, m, d, s, 2)["flops"] + s * 2 * 2 * d * f
    return (2 * m * cfg["patch_dim"] * d + n * attn + (n - n_moe) * mlp
            + n_moe * moe + 2 * d * cfg["num_classes"])


def vit_train_flops(cfg: dict) -> float:
    """Forward and backward for one image: 3 x forward, no recompute."""
    return 3 * vit_forward_flops(cfg)


def softmoe_fwd(b: int, m: int, d: int, s: int, act_bytes: int) -> dict:
    """Soft-MoE routing and combine of one layer, forward: logits
    l2n(X) Phi, slot mix D^T X and output C Ys, each 2·b·m·d·s. Bytes:
    X, Phi, Ys read once and slots, Y written once."""
    return {"flops": 3 * 2 * b * m * d * s,
            "bytes": act_bytes * (2 * b * m * d + d * s + 2 * b * s * d)}


def softmoe_bwd(b: int, m: int, d: int, s: int, act_bytes: int) -> dict:
    """Their backward: two products for each forward product. Bytes: X,
    Phi, Ys and the two incoming gradients read once; dX, dPhi, dYs
    written once."""
    return {"flops": 2 * softmoe_fwd(b, m, d, s, act_bytes)["flops"],
            "bytes": act_bytes * (3 * b * m * d + 2 * d * s
                                  + 3 * b * s * d)}


def roofline_s(work: dict, peaks: dict) -> float:
    """The least time the chip could take: operations at peak or bytes at
    full bandwidth, whichever is longer."""
    return max(work["flops"] / peaks["flops_bf16"],
               work["bytes"] / peaks["hbm_bytes_per_s"])
