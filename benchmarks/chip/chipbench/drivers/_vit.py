"""What the ViT cells share: the program's config built from the file, and
the check that the program lays its parameters out as the reference reads
them."""
from __future__ import annotations

import dataclasses

import jax

from .. import ref_vit


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for the file: its registry entry with
    every size the file states, so the file is the configuration as run."""
    from repro.configs import get_config

    base = get_config(cfg["program_config"])
    attn = dataclasses.replace(
        base.attention, num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        rope_theta=cfg["rope_theta"])
    moe = dataclasses.replace(
        base.moe, num_experts=cfg["num_experts"],
        slots_per_expert=cfg["slots_per_expert"], expert_d_ff=0)
    front = dataclasses.replace(base.frontend, embed_dim=cfg["patch_dim"],
                                num_embeds=cfg["num_patches"])
    return dataclasses.replace(
        base, num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        d_ff=cfg["intermediate_size"], max_seq_len=cfg["num_patches"],
        attention=attn, moe=moe, moe_layers=cfg["moe_layers"],
        frontend=front, dtype=cfg["dtype"], param_dtype=cfg["param_dtype"],
        norm="layernorm", act="gelu", mlp_style="classic", causal=False)


def check_layout(mcfg, cfg: dict) -> dict:
    """The reference's parameter shapes, after checking that the
    program's own init has exactly that tree."""
    from repro.models import vit_init

    shapes = ref_vit.param_shapes(cfg)
    prog = jax.eval_shape(
        lambda r: vit_init(r, mcfg, num_classes=cfg["num_classes"]),
        jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: tuple(a.shape), prog)
    if got != shapes:
        raise ValueError(f"the program's parameter tree {got} is not the "
                         f"reference's {shapes}")
    return shapes
