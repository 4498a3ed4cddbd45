"""Roofline machinery: HLO collective parsing (trip counts, replica groups,
pod-crossing), CPU-upcast correction, analytic-vs-HLO FLOPs cross-check."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config
from repro.roofline.analysis import model_flops
from repro.roofline.flops import analytic_cost, fwd_flops
from repro.roofline.hlo_parse import (
    Collective,
    cpu_upcast_correction,
    parse_module_collectives,
)

FAKE_HLO = """
HloModule test, is_scheduled=true

%inner_body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %ar0 = f32[8,8]{1,0} all-reduce(%x), replica_groups=[2,4]<=[8], channel_id=1
  ROOT %t = (s32[], f32[8,8]) tuple(%i, %ar0)
}

%outer_body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %w2 = (s32[], f32[8,8]) while(%t0), condition=%c, body=%inner_body, backend_config={"known_trip_count":{"n":"3"}}
  %ag = f32[16,4]{1,0} all-gather(%y), replica_groups=[4,2]<=[2,4]T(1,0), channel_id=2
  ROOT %t2 = (s32[], f32[8,8]) tuple(%i2, %w2)
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %w1 = (s32[], f32[8,8]) while(%t1), condition=%c2, body=%outer_body, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %done = f32[8,8] copy(%a)
}
"""


def test_parse_nested_trip_counts():
    mc = parse_module_collectives(FAKE_HLO)
    counts = mc.counts()
    # inner all-reduce: 5 (outer) x 3 (inner) = 15; all-gather: 5
    assert counts["all-reduce"] == 15
    assert counts["all-gather"] == 5
    by = mc.by_kind()
    assert by["all-reduce"] == 15 * 8 * 8 * 4
    assert by["all-gather"] == 5 * 16 * 4 * 4


def test_pod_crossing_detection():
    # groups [4,2]<=[2,4]T(1,0): transpose makes groups {0,4},{1,5},... —
    # with pod_size=4 those cross pods.
    mc = parse_module_collectives(FAKE_HLO, pod_size=4)
    ag = [c for c in mc.collectives if c.kind == "all-gather"][0]
    assert ag.crosses_pod
    ar = [c for c in mc.collectives if c.kind == "all-reduce"][0]
    assert not ar.crosses_pod  # [2,4]<=[8]: contiguous groups of 4


def test_alg_factors():
    c = Collective("all-reduce", 100, 4, False)
    assert c.alg_factor() == pytest.approx(2 * 3 / 4)
    c = Collective("all-gather", 100, 4, False)
    assert c.alg_factor() == pytest.approx(3 / 4)
    c = Collective("collective-permute", 100, 4, False)
    assert c.alg_factor() == 1.0


def test_cpu_upcast_correction_detects_converts():
    txt = """
ENTRY %m (p: bf16[1000,1000]) -> f32[1000,1000] {
  %p0 = bf16[1000,1000]{1,0} parameter(0)
  %big = f32[10000,10000]{1,0} convert(%w)
  %w = bf16[10000,10000]{1,0} parameter(1)
  ROOT %r = f32[1000,1000] convert(%p0)
}
"""
    # 10000x10000 f32 = 400MB > threshold; 1000x1000 f32 = 4MB < threshold
    assert cpu_upcast_correction(txt) == 10000 * 10000 * 4


def test_analytic_flops_cross_check_vs_hlo():
    """On an UNROLLED graph (decode path, no scan) XLA's cost_analysis is
    trustworthy — the analytic model must agree within 2x (it ignores
    elementwise ops; XLA ignores some fusions)."""
    from repro.configs import reduced
    from repro.models import init_cache, lm_apply

    cfg = reduced(get_config("llama3-8b"))
    from repro.models import lm_init

    params = lm_init(jax.random.PRNGKey(0), cfg)
    B, S = 4, 64
    cache = init_cache(cfg, B, S)
    toks = jnp.zeros((B, 1), jnp.int32)

    def decode(p, t, cache):
        return lm_apply(p, cfg, t, positions=jnp.arange(63, 64),
                        cache=cache, mode="decode")[0]

    c = jax.jit(decode).lower(params, toks, cache).compile()
    hlo_flops = c.cost_analysis().get("flops", 0)
    ana = fwd_flops(cfg, B, 1, "decode", cache_len=S)
    assert ana > 0 and hlo_flops > 0
    ratio = ana / hlo_flops
    assert 0.4 < ratio < 2.5, f"analytic/HLO flops ratio {ratio:.2f}"


def test_model_flops_moe_uses_active_params():
    cfg_moe = get_config("deepseek-v2-lite-16b")
    shape = SHAPES["train_4k"]
    mf = model_flops(cfg_moe, shape, "train")
    dense_equiv = 6.0 * cfg_moe.param_count() * shape.global_batch * shape.seq_len
    assert mf < dense_equiv * 0.5  # top-6/64 of experts active


def test_analytic_cost_modes():
    cfg = get_config("llama3-8b")
    tr = analytic_cost(cfg, "train_4k")
    pf = analytic_cost(cfg, "prefill_32k")
    dc = analytic_cost(cfg, "decode_32k")
    assert tr.flops_global > pf.flops_global > dc.flops_global
    # decode is dominated by bytes (params + cache), train by flops
    assert dc.bytes_global > dc.flops_global / 1000
