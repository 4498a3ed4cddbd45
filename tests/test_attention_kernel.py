"""The fused full-sequence attention kernel (kernels/attention_kernels.py)
against ``layers.attention._attend``, in interpret mode on the CPU, and
the calls for which ``gqa_apply`` takes it.

The kernel applies RoPE itself, so its oracle is ``apply_rope`` then
``_attend`` on the same bf16 projections. Both accumulate in f32; the
kernel also rounds the unnormalised probabilities to bf16 for P·V, so the
two agree to a bf16 rounding, not bitwise. A differentiated call runs the
plain path, so its gradient is the plain path's, bitwise."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.vit import vit
from repro.kernels import attention_kernels
from repro.kernels.attention_kernels import mha_full
from repro.layers.attention import _attend, gqa_apply, gqa_init, init_kv_cache
from repro.layers.rotary import apply_rope, rope_cos_sin
from repro.models import vit_apply, vit_init

H, D, THETA = 6, 64, 1e4


def _qkv(b, s, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return [jax.random.normal(kk, (b, s, H * D)).astype(jnp.bfloat16)
            for kk in ks]


def _kernel(q, k, v):
    cos, sin = rope_cos_sin(jnp.arange(q.shape[1]), D, THETA)
    return mha_full(jnp.concatenate([q, k, v], axis=-1), cos, sin,
                    num_heads=H)


def _plain(q, k, v):
    b, s, _ = q.shape
    heads = lambda x: x.reshape(b, s, H, D)  # noqa: E731
    pos = jnp.arange(s)
    out = _attend(apply_rope(heads(q), pos, THETA),
                  apply_rope(heads(k), pos, THETA), heads(v), None)
    return out.reshape(b, s, H * D)


def _gap(got, want):
    """Worst row's ‖got − want‖ / ‖want‖."""
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    return float(np.max(np.linalg.norm(got - want, axis=-1)
                        / np.linalg.norm(want, axis=-1)))


def _layer_grads(s, use_kernel):
    """Gradients of a ViT-S/16-wide attention layer (6 heads of 64) with
    respect to its parameters and input."""
    cfg = vit("s", 16)
    params = gqa_init(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, s, cfg.d_model))
    g = jax.random.normal(jax.random.PRNGKey(2), (2, s, cfg.d_model))

    def loss(p, x):
        y = gqa_apply(p, cfg, x.astype(jnp.bfloat16), use_kernel=use_kernel)
        return jnp.sum(y[0].astype(jnp.float32) * g)

    return jax.grad(loss, argnums=(0, 1))(params, x)


@pytest.mark.parametrize("check,s", [
    ("forward", 196),   # keys padded to 256 lanes in VMEM on the chip
    ("forward", 256),   # no padding
    ("gradient", 196),
    ("gradient", 256),
    ("rows", 196),
])
def test_mha_full_matches_attend(check, s):
    if check == "forward":
        q, k, v = _qkv(3, s, s)
        assert _gap(_kernel(q, k, v), _plain(q, k, v)) < 1e-2
    elif check == "gradient":
        got, want = _layer_grads(s, True), _layer_grads(s, False)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert bool(jnp.array_equal(a, b))
    else:
        # Row i of a batched launch is a batch-1 launch of that row,
        # bitwise: nothing reduces across images.
        q, k, v = _qkv(3, s, s)
        out = _kernel(q, k, v)
        for i in range(q.shape[0]):
            one = _kernel(q[i:i + 1], k[i:i + 1], v[i:i + 1])
            assert bool(jnp.array_equal(out[i], one[0])), f"row {i}"


def test_tiling_policy():
    """Images per step divide the batch and stay under the VMEM bound;
    the kernel takes heads that tile the width in 128-lane blocks."""
    assert [attention_kernels.block_batch(b, s) for b, s in
            ((512, 196), (6, 196), (7, 196), (11, 196), (8, 512))] == [
                8, 6, 7, 1, 2]
    assert attention_kernels.fits(196, 6, 64)    # ViT-S: 3 blocks of 2
    assert attention_kernels.fits(196, 12, 64)   # ViT-B
    assert attention_kernels.fits(256, 8, 128)   # one head a block
    assert not attention_kernels.fits(196, 3, 64)   # 192 lanes
    assert not attention_kernels.fits(196, 4, 96)   # 96-wide heads
    assert not attention_kernels.fits(1024, 6, 64)  # scores over budget
    assert attention_kernels.fits(512, 6, 64)


def _attn_cfg(**kw):
    """A small non-causal MHA encoder config; ``kw`` overrides it."""
    base = vit("s", 16, image_size=64)
    attn = {"num_heads": 2, "num_kv_heads": 2, "head_dim": 64}
    for k in ("num_kv_heads", "sliding_window", "head_dim"):
        if k in kw:
            attn[k] = kw.pop(k)
    return dataclasses.replace(
        base, d_model=64,
        attention=dataclasses.replace(base.attention, **attn), **kw)


# (what differs from the engaging call, config overrides, call overrides,
# sequence length, whether the kernel is taken)
_ROUTES = [
    ("engaged", {}, {}, 16, True),
    ("use_kernel off", {}, {"use_kernel": False}, 16, False),
    ("cache", {}, {"cache": "kv", "mode": "prefill"}, 16, False),
    ("causal", {"causal": True}, {}, 16, False),
    ("sliding window", {"sliding_window": 8}, {}, 16, False),
    ("positions given", {}, {"positions": "arange"}, 16, False),
    ("grouped kv heads", {"num_kv_heads": 1}, {}, 16, False),
    ("heads narrower than 128 lanes", {"head_dim": 32}, {}, 16, False),
    ("over the VMEM bound", {}, {}, 1100, False),
]


@pytest.mark.parametrize("case,cfg_kw,call_kw,s,engaged", _ROUTES,
                         ids=[r[0] for r in _ROUTES])
def test_gqa_apply_routes_full_sequence_kernel(case, cfg_kw, call_kw, s,
                                               engaged):
    cfg = _attn_cfg(**cfg_kw)
    params = gqa_init(jax.random.PRNGKey(0), cfg)
    x = jnp.zeros((2, s, cfg.d_model), jnp.bfloat16)
    kw = {"use_kernel": True, **call_kw}
    if kw.get("cache") == "kv":
        kw["cache"] = init_kv_cache(cfg, 2, s, True)
    if kw.get("positions") == "arange":
        kw["positions"] = jnp.arange(s)
    jaxpr = str(jax.make_jaxpr(
        lambda p, x: gqa_apply(p, cfg, x, **kw)[0])(params, x))
    assert ("mha_full_fwd" in jaxpr) == engaged, case


def test_vit_forward_through_kernel_matches_plain():
    """A whole (dense) ViT forward with ``use_kernel`` agrees with the
    plain path to the kernel's bf16 rounding."""
    cfg = dataclasses.replace(_attn_cfg(), num_layers=2, d_ff=128,
                              dtype="bfloat16")
    params = vit_init(jax.random.PRNGKey(0), cfg, num_classes=10)
    params["head"]["w"] = jax.random.normal(jax.random.PRNGKey(1),
                                            params["head"]["w"].shape)
    patches = jax.random.normal(jax.random.PRNGKey(2),
                                (3, 16, cfg.frontend.embed_dim))
    got = vit_apply(params, cfg, patches, use_kernel=True)[0]
    want = vit_apply(params, cfg, patches, use_kernel=False)[0]
    assert _gap(got, want) < 2e-2
