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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import KernelConfig, default_config, ops, paged_config
from repro.kernels.paged_attention_kernels import paged_decode_attend
from repro.launch.scope_time import KERNELS

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
