"""The trace reduction, on a trace recorded on one v5e: two gradient steps
of a two-block soft-moe-s/16-128e ViT (batch 4) through the fused Soft-MoE
kernels (6 Pallas calls per step)."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import trace  # noqa: E402

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "vit_grad.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData

    return trace.reduce(ProfileData.from_file(FIXTURE), 0.02, 1)


def test_busy_is_the_union_of_device_ops(reduced):
    assert 0 < reduced["busy_s"] < 0.02
    assert reduced["device_events"] > 100


def test_pallas_kernels_counted(reduced):
    assert reduced["pallas_calls"] == 12
    assert 0 < reduced["pallas_s"] < reduced["busy_s"]


def test_pallas_kernels_by_operands(reduced):
    """The fixture's six Soft-MoE kernels a step each take Phi, f32[384,128],
    and the padded tokens, bf16[4,256,384]; by operand shapes they account
    for every Pallas call and all of its time."""
    by = reduced["pallas_by_operands"]
    assert sum(c for _, c in by.values()) == reduced["pallas_calls"]
    assert sum(t for t, _ in by.values()) == pytest.approx(
        reduced["pallas_s"])
    assert all("f32[384,128]" in ops and "bf16[4,256,384]" in ops
               for ops in by)


def test_operand_shapes():
    hlo = ('%closed_call.13 = bf16[4,256,384]{2,1,0:T(8,128)(2,1)S(1)} '
           'custom-call(bf16[4,256,384]{2,1,0:T(8,128)(2,1)S(1)} %pad.34, '
           'f32[384,128]{1,0:T(8,128)S(1)} %fusion.6), custom_call_target='
           '"tpu_custom_call", operand_layout_constraints={bf16[4,256,384]'
           '{2,1,0}, f32[384,128]{1,0}}')
    assert trace.operand_shapes(hlo) == "bf16[4,256,384] f32[384,128]"
    assert trace.operand_shapes("%add.1 = f32[2] add(f32[2] %a)") == ""


def test_breakdown(reduced):
    ops = reduced["breakdown"]["device_ops"]
    gaps = reduced["breakdown"]["idle_gaps"]
    assert 0 < len(ops) <= trace.TOP and 0 < len(gaps) <= trace.TOP
    assert all(isinstance(n, str) and s > 0 for n, s in ops + gaps)
    assert [s for _, s in ops] == sorted((s for _, s in ops), reverse=True)
    # self times never exceed the busy time
    assert sum(s for _, s in ops) <= reduced["busy_s"] * 1.0001


def test_union_and_self_times():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [[0, 3], [5, 9]]
    events = [(0, 10, "while"), (1, 4, "a"), (5, 9, "b"), (6, 7, "c"),
              (12, 13, "a")]
    self_t = trace.self_times(events)
    assert self_t == {"while": 3, "a": 4, "b": 3, "c": 1}


def test_short_name():
    hlo = ('%closed_call.12 = (bf16[4,128,384]{2,1,0}, f32[4,1,128]{2,1,0})'
           ' custom-call(bf16[4,256,384]{2,1,0} %pad.34), custom_call_target='
           '"tpu_custom_call"')
    assert (trace.short_name(hlo)
            == "closed_call.12 custom-call:tpu_custom_call bf16[4,128,384]")
