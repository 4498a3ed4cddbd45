"""Counting functions and the peak table."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import counting, peaks, spec  # noqa: E402

V5E = peaks.PEAKS["TPU v5 lite"]


def _cfg(**kw):
    with open(os.path.join(BENCH, "configs", "soft-moe-s16-128e.json")) as f:
        cfg = json.load(f)
    cfg.update(kw)
    return cfg


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")
    assert peaks.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12


def test_published_size():
    # 8.55 GFLOP per image forward at the paper's S/16 128E widths, with
    # the JFT-4B head of 29,593 classes
    assert counting.vit_forward_flops(_cfg()) == pytest.approx(8.554e9,
                                                               rel=1e-3)
    assert counting.vit_train_flops(_cfg()) == 3 * counting.vit_forward_flops(
        _cfg())


@pytest.mark.parametrize("is_moe", [False, True])
def test_layer_count_against_cost_analysis(is_moe):
    """One block of the program, lowered at a small size: the algorithm's
    matmul count is at most XLA's count (which adds the elementwise work)
    and most of it."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        BENCH)), "src"))
    import jax
    import jax.numpy as jnp
    from repro.models.lm import block_apply

    from chipbench import ref_vit, weights
    from chipbench.drivers import _vit

    cfg = _cfg(num_hidden_layers=1, moe_layers="all" if is_moe else "",
               num_patches=196, num_experts=16, patch_dim=48,
               num_classes=10)
    mcfg = _vit.model_config(cfg)
    shapes = ref_vit.param_shapes(cfg)["segments"][0]
    layer = jax.eval_shape(lambda: jax.tree.map(
        lambda a: a[0], weights.make_params({"s": shapes}, 0)["s"]))
    x = jax.ShapeDtypeStruct((1, 196, cfg["hidden_size"]), jnp.bfloat16)
    fn = jax.jit(lambda p, x: block_apply(p, mcfg, x, is_moe=is_moe,
                                          mode="train")[0])
    xla = fn.lower(layer, x).compile().cost_analysis()["flops"]
    edge = 2 * 196 * 48 * 384 + 2 * 384 * 10  # patch projection and head
    ours = counting.vit_forward_flops(cfg) - edge
    assert 0.8 * xla <= ours <= xla


def test_fused_and_unfused_softmoe_share_one_denominator():
    """The roofline time comes from shapes alone: a kernel split in more
    calls, or one that recomputes, reads against the same roofline, and a
    kernel at the roofline reads 100 %."""
    cfg = _cfg()
    passes = ("fwd", "bwd")
    shape = (32, 196, 384, 128, 2)
    roof = 6 * sum(counting.roofline_s(getattr(counting, "softmoe_" + p)(
        *shape), V5E) for p in passes)
    read = _roofline_reader()
    base = {"config": cfg, "peaks": V5E, "batch": 32, "calls": 1,
            "softmoe_passes": passes}
    x, phi = "bf16[32,256,384]", "f32[384,128]"

    def trace(*kernels):
        return {"pallas_by_operands": {ops: [t, 6] for ops, t in kernels}}

    fused = read(dict(base, trace=trace((f"{x} {phi}", roof))))
    unfused = read(dict(base, trace=trace(
        (f"{x} {phi}", roof / 2), (f"{x} {phi} bf16[32,128,384]", roof / 2))))
    assert fused == unfused == pytest.approx(100.0)
    slower = read(dict(base, trace=trace((f"{x} {phi}", 2 * roof))))
    assert slower == pytest.approx(50.0)
    assert read(dict(base, trace=trace())) is None


def _roofline_reader():
    return spec.metric_reader(spec.Cell(
        name="x", chips=1, config_name="c", config={}, traffic={},
        limits={}, bench_dir=BENCH), "softmoe_kernels_roofline")


def test_other_pallas_kernels_are_not_counted():
    """A Pallas call without Phi and the tokens among its operands (an
    attention or norm kernel, say) adds nothing to the denominator; a
    trace with no Soft-MoE kernel gives no reading."""
    read = _roofline_reader()
    base = {"config": _cfg(), "peaks": V5E, "batch": 32, "calls": 1,
            "softmoe_passes": ("fwd",)}
    softmoe = "bf16[32,256,384] f32[384,128]"
    alone = read(dict(base, trace={"pallas_by_operands": {
        softmoe: [1e-3, 6]}}))
    beside = read(dict(base, trace={"pallas_by_operands": {
        softmoe: [1e-3, 6], "bf16[32,6,196,64] bf16[32,6,196,64]": [5e-3, 12],
        "bf16[16,256,384] f32[384,128]": [5e-3, 6]}}))
    assert alone == beside > 0
    assert read(dict(base, trace={"pallas_by_operands": {
        "bf16[32,6,196,64]": [1e-3, 12]}})) is None


def test_softmoe_counts_scale():
    f = counting.softmoe_fwd(2, 196, 384, 128, 2)
    g = counting.softmoe_fwd(4, 196, 384, 128, 2)
    assert g["flops"] == 2 * f["flops"]
    assert counting.softmoe_bwd(2, 196, 384, 128, 2)["flops"] == 2 * f["flops"]
    assert f["bytes"] < g["bytes"] < 2 * f["bytes"]  # phi is read once
