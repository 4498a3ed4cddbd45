"""Per-arch smoke tests (deliverable f): every assigned architecture at a
REDUCED same-family config runs one forward/train step on CPU with correct
output shapes and no NaNs; decode consistency vs the train path."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ASSIGNED_NAMES, get_config, reduced, softify
from repro.models import build_model, init_cache, lm_apply, lm_init


def _batch_for(cfg, rng, b=2, s=32):
    if cfg.family == "vit":
        return {
            "patches": jax.random.normal(
                rng, (b, cfg.frontend.num_embeds, cfg.frontend.embed_dim)
            ),
            "labels": jax.random.randint(rng, (b,), 0, 1000),
        }
    batch = {"tokens": jax.random.randint(rng, (b, s), 0, cfg.vocab_size)}
    if cfg.frontend.kind != "none":
        batch["embeds"] = jax.random.normal(
            rng, (b, cfg.frontend.num_embeds, cfg.frontend.embed_dim)
        )
    return batch


@pytest.mark.parametrize("name", ASSIGNED_NAMES)
def test_arch_train_step(name):
    cfg = reduced(get_config(name))
    init, loss_fn, _ = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    params = init(rng)
    batch = _batch_for(cfg, rng)
    (l, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, batch
    )
    assert bool(jnp.isfinite(l)), f"{name}: non-finite loss"
    assert float(l) > 0
    finite = all(
        bool(jnp.isfinite(g).all()) for g in jax.tree_util.tree_leaves(grads)
    )
    assert finite, f"{name}: non-finite grads"


@pytest.mark.parametrize("name", ASSIGNED_NAMES)
def test_arch_forward_shapes(name):
    cfg = reduced(get_config(name))
    init, _, apply_fn = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    params = init(rng)
    batch = _batch_for(cfg, rng)
    out = apply_fn(params, batch)
    logits = out[0]
    assert bool(jnp.isfinite(logits).all()), f"{name}: NaNs in logits"
    if cfg.family == "vit":
        assert logits.shape == (2, 1000)
    else:
        assert logits.shape[0] == 2
        assert logits.shape[-1] == cfg.vocab_size


@pytest.mark.parametrize(
    "name",
    [n for n in ASSIGNED_NAMES if get_config(n).encoder_layers == 0],
)
def test_arch_decode_consistency(name):
    """prefill + token-by-token decode == full forward (sparse-MoE archs
    are checked with slack capacity: tight capacity legitimately makes
    routing batch-dependent — paper §2.2)."""
    cfg = reduced(get_config(name))
    if cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=16.0)
        )
    params = lm_init(jax.random.PRNGKey(0), cfg)
    B, S = 2, 24
    rng = jax.random.PRNGKey(1)
    toks = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    embeds = None
    if cfg.frontend.kind != "none":
        embeds = jax.random.normal(
            rng, (B, cfg.frontend.num_embeds, cfg.frontend.embed_dim)
        )
    full, _, _ = lm_apply(params, cfg, toks, embeds=embeds, mode="train")
    split = S - 4
    n_prefix = full.shape[1] - S
    cache = init_cache(cfg, B, S + n_prefix)
    lp, cache, _ = lm_apply(
        params, cfg, toks[:, :split], embeds=embeds,
        positions=jnp.arange(split + n_prefix), cache=cache, mode="prefill",
    )
    outs = [lp[:, -1]]
    for t in range(split, S):
        lt, cache, _ = lm_apply(
            params, cfg, toks[:, t:t + 1],
            positions=jnp.arange(n_prefix + t, n_prefix + t + 1),
            cache=cache, mode="decode",
        )
        outs.append(lt[:, 0])
    dec = jnp.stack(outs, 1)
    ref = full[:, n_prefix + split - 1:]
    err = float(jnp.abs(dec - ref).max())
    rel = err / (float(jnp.abs(ref).max()) + 1e-9)
    assert rel < 2e-2, f"{name}: decode mismatch rel={rel:.3e}"


def test_softified_variants_train():
    """The paper's technique as a first-class config option (`+soft`)."""
    for name in ("llama3-8b", "deepseek-v2-lite-16b", "granite-moe-1b-a400m"):
        cfg = reduced(get_config(name + "+soft"))
        assert cfg.moe is not None and cfg.moe.variant == "soft"
        init, loss_fn, _ = build_model(cfg)
        params = init(jax.random.PRNGKey(0))
        batch = _batch_for(cfg, jax.random.PRNGKey(1))
        l, _ = loss_fn(params, batch)
        assert bool(jnp.isfinite(l))


def test_softify_rejects_mlp_free_arch():
    with pytest.raises(ValueError):
        softify(get_config("mamba2-370m"))


def test_paper_vit_models_train():
    from repro.configs import soft_moe_vit

    cfg = reduced(soft_moe_vit("s", 16, 8))
    init, loss_fn, _ = build_model(cfg)
    params = init(jax.random.PRNGKey(0))
    batch = _batch_for(cfg, jax.random.PRNGKey(1))
    l, metrics = loss_fn(params, batch)
    assert bool(jnp.isfinite(l))


def test_vit_features_are_the_pooled_encoder_of_vit_apply():
    """``vit_features`` is the mean pool of ``vit_apply``'s encoder output
    after ``final_norm``: the head applied to it gives ``vit_apply``'s
    logits, and it equals the embedding, block loop and final norm
    written out."""
    from repro.layers.common import norm_apply
    from repro.models.lm import block_apply, segment_plan
    from repro.models.vit import vit_apply, vit_features, vit_init

    cfg = reduced(get_config("soft-moe-s/16-128e"))
    params = vit_init(jax.random.PRNGKey(0), cfg, num_classes=10)
    head = params["head"]
    head["w"] = jax.random.normal(jax.random.PRNGKey(2), head["w"].shape)
    patches = _batch_for(cfg, jax.random.PRNGKey(1))["patches"]
    feats = vit_features(params, cfg, patches)
    logits, _ = vit_apply(params, cfg, patches)
    assert jnp.allclose(feats.astype(jnp.float32) @ head["w"] + head["b"],
                        logits, rtol=1e-5, atol=1e-5)

    dt = jnp.dtype(cfg.dtype)
    x = patches.astype(dt) @ params["patch_proj"]["w"].astype(dt)
    x = x + params["patch_proj"]["b"].astype(dt) + params["pos_emb"].astype(dt)
    for seg, (_, _, is_moe) in zip(params["segments"], segment_plan(cfg)):
        x, _ = jax.lax.scan(
            lambda c, p, _m=is_moe: (block_apply(p, cfg, c, is_moe=_m)[0],
                                     None), x, seg)
    want = norm_apply(params["final_norm"], cfg, x).mean(axis=1)
    assert feats.dtype == want.dtype
    assert jnp.allclose(feats, want, rtol=1e-6, atol=1e-6)
