"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode, in which every other kernel test runs on the CPU, never
applies Mosaic's tiling rules; the TPU compiler, installed here without a
chip, does. Each test lowers kernels at published widths for one chip of a
described v5e:2x2 topology and asserts that the compiled HLO holds them
(``tpu_custom_call``) under their stable names, which the device trace
shows as the instruction name of each kernel's op. Nothing runs, so
nothing here says anything about results or time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import KernelConfig, default_config, ops, paged_config
from repro.kernels.paged_attention_kernels import paged_decode_attend
from repro.layers.attention import gqa_apply, gqa_init
from repro.launch.scope_time import KERNELS
from repro.models import lm_apply, lm_init, vit_apply, vit_init
from repro.models.lm import init_cache
from repro.serve.block_manager import init_paged_cache

# soft-moe-s/16-128e: tokens per image, width, slots
_VIT = get_config("soft-moe-s/16-128e")
M, D = _VIT.frontend.num_embeds, _VIT.d_model
S = _VIT.moe.num_experts * _VIT.moe.slots_per_expert
# qwen2-0.5b decode: query heads, kv heads, head dim; 16-token pool blocks
_ATTN = get_config("qwen2-0.5b").attention
H, G, HD, BLOCK = _ATTN.num_heads, _ATTN.num_kv_heads, _ATTN.head_dim, 16
ROWS, MAX_LEN = 8, 544


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else libtpu logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure: no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache off meanwhile.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


def _shape(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


_PALLAS_OP = re.compile(
    r'^\s*(?:ROOT )?%(\S+) = .*custom_call_target="tpu_custom_call"', re.M)


def _kernel_names(hlo: str) -> collections.Counter:
    """How many Pallas ops of each kernel name the compiled HLO holds, by
    the name each op's instruction name contains (a transform may wrap
    it: ``transpose_jvp_softmoe_combine_bwd_dx__.3``); ``None`` counts
    the ops that carry none of them."""
    return collections.Counter(
        next((k for k in KERNELS if k in op), None)
        for op in _PALLAS_OP.findall(hlo))


def _routing_combine(x, phi_n, ys):
    cfg = default_config(M, D, S, KernelConfig(interpret=False))
    slots, d_stats, c_stats = ops.soft_moe_routing(x, phi_n, cfg,
                                                   with_d_stats=True)
    y = ops.soft_moe_combine(x, phi_n, ys, c_stats, cfg)
    y_online = ops.soft_moe_combine(x, phi_n, ys, None, cfg)
    return slots, y, y_online, (cfg, d_stats, c_stats)


def _soft_moe_fwd(x, phi_n, ys):
    slots, y, y_online, (cfg, d_stats, c_stats) = _routing_combine(
        x, phi_n, ys)
    health = ops.routing_health(x, phi_n, d_stats, c_stats, cfg)
    return slots, y, y_online, health


def _soft_moe_loss(x, phi_n, ys):
    slots, y, y_online, _ = _routing_combine(x, phi_n, ys)
    return jnp.sum(slots * slots) + jnp.sum(y * y) + jnp.sum(y_online)


@pytest.mark.parametrize("b", [8, 1])
def test_soft_moe_forward_kernels_compile(one_chip, b):
    args = (_shape(one_chip, (b, M, D)), _shape(one_chip, (D, S)),
            _shape(one_chip, (b, S, D)))
    hlo = _kernel_hlo(_soft_moe_fwd, *args)
    # routing, combine (saved stats), combine (online), routing health
    assert hlo.count('custom_call_target="tpu_custom_call"') == 4
    assert _kernel_names(hlo) == {
        "softmoe_routing_fwd": 1, "softmoe_combine_fwd": 1,
        "softmoe_combine_online": 1, "softmoe_routing_health": 1}


@pytest.mark.parametrize("b", [8, 1])
def test_soft_moe_backward_kernels_compile(one_chip, b):
    args = (_shape(one_chip, (b, M, D)), _shape(one_chip, (D, S)),
            _shape(one_chip, (b, S, D)))
    hlo = _kernel_hlo(jax.grad(_soft_moe_loss, argnums=(0, 1, 2)), *args)
    # 3 forward + dispatch dx/dphi + 2 x combine dx/(dys, dphi)
    assert hlo.count('custom_call_target="tpu_custom_call"') == 9
    assert _kernel_names(hlo) == {
        "softmoe_routing_fwd": 1, "softmoe_combine_fwd": 1,
        "softmoe_combine_online": 1, "softmoe_dispatch_bwd_dx": 1,
        "softmoe_dispatch_bwd_dphi": 1, "softmoe_combine_bwd_dx": 2,
        "softmoe_combine_bwd_dys_dphi": 2}


def test_paged_decode_kernel_compiles(one_chip):
    blocks_per_row = MAX_LEN // BLOCK
    num_blocks = ROWS * blocks_per_row + 1  # + the reserved null block
    bf16 = jnp.bfloat16
    args = (
        _shape(one_chip, (ROWS, H, HD), bf16),
        _shape(one_chip, (num_blocks, BLOCK, G, HD), bf16),
        _shape(one_chip, (num_blocks, BLOCK, G, HD), bf16),
        _shape(one_chip, (num_blocks, BLOCK), jnp.int32),
        _shape(one_chip, (ROWS, blocks_per_row), jnp.int32),
        _shape(one_chip, (ROWS,), jnp.int32),
    )
    cfg = paged_config(BLOCK, KernelConfig(interpret=False))
    hlo = _kernel_hlo(lambda *a: paged_decode_attend(*a, cfg=cfg), *args)
    assert _kernel_names(hlo) == {"paged_decode_attend": 1}


def _abstract(one_chip, tree):
    return jax.tree.map(
        lambda a: _shape(one_chip, a.shape, a.dtype), tree)


@pytest.mark.parametrize("phase,b,kernels", [
    ("forward", 512, 1), ("forward", 32, 1), ("gradient", 32, 0)])
def test_full_attention_kernel_compiles(one_chip, monkeypatch, phase, b,
                                        kernels):
    """A soft-moe-s/16 attention layer with ``use_kernel`` takes the fused
    kernel in the inference cell's forward and at the train cell's batch;
    its gradient runs the plain path."""
    monkeypatch.setattr("repro.kernels.tuning.backend_is_tpu", lambda: True)
    params = _abstract(one_chip, jax.eval_shape(
        lambda r: gqa_init(r, _VIT), jax.random.PRNGKey(0)))
    x = _shape(one_chip, (b, M, D), jnp.bfloat16)

    def fwd(p, x):
        return gqa_apply(p, _VIT, x, use_kernel=True)[0]

    fn = fwd if phase == "forward" else jax.grad(
        lambda p, x: jnp.sum(fwd(p, x).astype(jnp.float32)))
    names = _kernel_names(_kernel_hlo(fn, params, x))
    assert names == ({"mha_full_fwd": kernels} if kernels else {})


@pytest.mark.parametrize("use_kernel", [True, False])
def test_vit_forward_routes_full_attention_kernel(one_chip, monkeypatch,
                                                  use_kernel):
    """The batch-512 soft-moe-s/16-128e forward holds the attention kernel
    once in each segment's scan body (dense, then Soft MoE) with
    ``use_kernel``, and no kernel at all without it."""
    # Kernels resolve interpret mode from the backend, which is the CPU
    # here: compile them for the described chip instead.
    monkeypatch.setattr("repro.kernels.tuning.backend_is_tpu", lambda: True)
    params = _abstract(one_chip, jax.eval_shape(
        lambda r: vit_init(r, _VIT, num_classes=1000),
        jax.random.PRNGKey(0)))
    patches = _shape(one_chip, (512, M, _VIT.frontend.embed_dim))
    hlo = _kernel_hlo(
        lambda p, x: vit_apply(p, _VIT, x, use_kernel=use_kernel)[0],
        params, patches)
    names = _kernel_names(hlo)
    if use_kernel:
        assert names["mha_full_fwd"] == 2
        assert names["softmoe_routing_fwd"] == 1
    else:
        assert not names


def _qwen_program(phase, params, cfg, one_chip):
    """qwen2-0.5b's cache-holding programs (prefill, paged decode) and
    its causal training forward, every one asked for the kernels."""
    rows, block = 4, 16
    tokens = _shape(one_chip, (rows, 1 if phase == "paged_decode" else 64),
                    jnp.int32)
    if phase == "prefill":
        cache = _abstract(one_chip, jax.eval_shape(
            lambda: init_cache(cfg, rows, 128)))
        fn = lambda p, t, c: lm_apply(  # noqa: E731
            p, cfg, t, positions=jnp.arange(t.shape[1]), cache=c,
            mode="prefill", last_only=True, use_kernel=True)[:2]
        return fn, (params, tokens, cache)
    if phase == "paged_decode":
        blocks_per_row = 8
        cache = _abstract(one_chip, jax.eval_shape(
            lambda: init_paged_cache(cfg, rows * blocks_per_row + 1, block,
                                     rows)))
        pos = _shape(one_chip, (rows,), jnp.int32)
        tables = _shape(one_chip, (rows, blocks_per_row), jnp.int32)
        fn = lambda p, t, q, tb, c: lm_apply(  # noqa: E731
            p, cfg, t, positions=q[:, None], cache=c, mode="decode",
            block_tables=tb, paged_kernel=True, use_kernel=True)[:2]
        return fn, (params, tokens, pos, tables, cache)
    fn = lambda p, t: lm_apply(p, cfg, t, use_kernel=True)[0]  # noqa: E731
    return fn, (params, tokens)


@pytest.mark.parametrize("phase", ["prefill", "paged_decode", "train"])
def test_lm_programs_hold_no_full_attention_kernel(one_chip, monkeypatch,
                                                   phase):
    """Causal attention, with or without a cache, never takes the fused
    full-sequence kernel, though the call asks for kernels: qwen2-0.5b at
    its widths, two layers deep (depth does not change the route)."""
    monkeypatch.setattr("repro.kernels.tuning.backend_is_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("qwen2-0.5b"), num_layers=2)
    params = _abstract(one_chip, jax.eval_shape(
        lambda r: lm_init(r, cfg), jax.random.PRNGKey(0)))
    fn, args = _qwen_program(phase, params, cfg, one_chip)
    names = _kernel_names(_kernel_hlo(fn, *args))
    assert "mha_full_fwd" not in names
    if phase == "paged_decode":
        assert names == {"paged_decode_attend": 2}
