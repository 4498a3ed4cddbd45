"""The names the program gives its layers, kernels and host phases.

Layer scopes (``jax.named_scope``) reach the compiled HLO's ``op_name``
metadata in forward, backward, recomputed and optimizer form;
``device_time_by_scope`` joins a profile's device ops with that metadata;
``Trainer.run`` writes its host spans into the profiler's own trace. The
kernel names are checked on the TPU compiler in ``test_tpu_compile.py``.
"""
import collections
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, reduced
from repro.data import SyntheticLM
from repro.launch.scope_time import (
    OpEvent, classify, device_time_by_scope, parse_hlo)
from repro.models import build_model, vit_init, vit_loss
from repro.optim import OptimizerConfig
from repro.train import Trainer, TrainerConfig
from repro.train.step import init_train_state, make_train_step

BLOCK = ("encoder/norm", "encoder/attention", "encoder/mlp",
         "encoder/moe/dispatch", "encoder/moe/experts",
         "encoder/moe/combine")


@pytest.fixture(scope="module")
def vit_step_names():
    """``(path, phase)`` of every op of a compiled Soft-MoE ViT train step
    (remat on, as the published configuration runs it), and the op_names."""
    cfg = dataclasses.replace(reduced(get_config("soft-moe-s/16-128e")),
                              remat=True)
    state = jax.eval_shape(
        lambda r: init_train_state(r, lambda k: vit_init(k, cfg, 10)),
        jax.random.PRNGKey(0))
    m, pd = cfg.frontend.num_embeds, cfg.frontend.embed_dim
    batch = {"patches": jax.ShapeDtypeStruct((2, m, pd), jnp.float32),
             "labels": jax.ShapeDtypeStruct((2,), jnp.int32)}
    step = make_train_step(lambda p, b: vit_loss(p, cfg, b),
                           OptimizerConfig())
    hlo = jax.jit(step).lower(state, batch).compile().as_text()
    op_names = set(parse_hlo(hlo).values())
    return {classify(n)[:2] for n in op_names}, op_names


def test_vit_train_step_scopes_in_every_phase(vit_step_names):
    found, op_names = vit_step_names
    for phase in ("forward", "backward"):
        for path in ("embed", "final_norm", "head", "loss") + BLOCK:
            assert (path, phase) in found, (path, phase)
    for path in BLOCK:
        assert (path, "recompute") in found, path
    for path in ("optimizer/clip", "optimizer/adamw"):
        assert (path, "optimizer") in found, path
    # the forms the phases are read from
    assert any("/transpose(jvp(encoder))/" in n for n in op_names)
    assert any("/rematted_computation/attention" in n for n in op_names)
    assert any(n.startswith("jit(train_step)/optimizer/adamw")
               for n in op_names)


def test_classify_reads_scopes_phases_and_kernels():
    assert classify("jit(step)/jvp(encoder)/while/body/closed_call/moe/"
                    "dispatch/softmoe_routing_fwd/pallas_call") == (
        "encoder/moe/dispatch", "forward", "softmoe_routing_fwd")
    assert classify("jit(step)/transpose(jvp(encoder))/while/body/"
                    "checkpoint/rematted_computation/attention/dot_general"
                    ) == ("encoder/attention", "recompute", "")
    assert classify("jit(step)/transpose(jvp(head))/dot_general") == (
        "head", "backward", "")
    assert classify("jit(step)/optimizer/clip/reduce_sum") == (
        "optimizer/clip", "optimizer", "")
    assert classify("jit(f)/add") == ("", "forward", "")


_HOISTED_HLO = """HloModule jit_f, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

%fused_computation.1 (param_0: f32[4]) -> (f32[4], f32[4]) {
  %param_0 = f32[4]{0} parameter(0)
  %mul.1 = f32[4]{0} multiply(%param_0, %param_0), metadata={op_name="jit(f)/encoder/while/body/attention/mul"}
  %add.1 = f32[4]{0} add(%mul.1, %param_0), metadata={op_name="jit(f)/encoder/while/body/attention/add"}
  ROOT %tuple.1 = (f32[4]{0}, f32[4]{0}) tuple(%mul.1, %add.1)
}

ENTRY %main.1 (p.1: f32[4]) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %convert.1 = bf16[4]{0} convert(%p.1)
  %tuple.2 = (bf16[4]{0}) tuple(%convert.1)
  %while.1 = (bf16[4]{0}) while(%tuple.2), condition=%c, body=%b, metadata={op_name="jit(f)/jvp(encoder)/while"}
  %while.2 = (bf16[4]{0}) while(%tuple.2), condition=%c, body=%b, metadata={op_name="jit(f)/transpose(jvp(encoder))/while"}
  %fusion.1 = (f32[4]{0}, f32[4]{0}) fusion(%p.1), kind=kLoop, calls=%fused_computation.1
  %copy.1 = f32[4]{0} copy(%p.1)
  %add.2 = f32[4]{0} add(%copy.1, %p.1), metadata={op_name="jit(f)/jvp(head)/add"}
  ROOT %add.3 = f32[4]{0} add(%copy.1, %add.2), metadata={op_name="jit(f)/jvp(loss)/add"}
}
"""


def test_parse_hlo_attributes_ops_xla_made_without_op_name():
    names = {k[1]: classify(v)[:2]
             for k, v in parse_hlo(_HOISTED_HLO).items()}
    # a multi-output fusion: the scopes of the ops it fuses
    assert names["fusion.1"] == ("encoder/attention", "forward")
    # a cast hoisted out of the loops: through its tuple, the loops' scope
    assert names["convert.1"] == ("encoder", "forward")
    # users under different scopes: no scope
    assert names["copy.1"] == ("", "forward")


def _scan_value_and_grad_hlo():
    def f(w, x):
        with jax.named_scope("encoder"):
            def body(c, wi):
                with jax.named_scope("attention"):
                    return jnp.tanh(c @ wi), None

            x, _ = jax.lax.scan(body, x, w)
        with jax.named_scope("head"):
            return jnp.sum(x * x)

    w = jax.ShapeDtypeStruct((3, 8, 8), jnp.float32)
    x = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    return jax.jit(jax.value_and_grad(f)).lower(w, x).compile().as_text()


def test_device_time_by_scope_self_time_nesting_and_remainder():
    hlo = _scan_value_and_grad_hlo()
    names = parse_hlo(hlo)

    def instr(path, phase, suffix=""):
        return next(k for k, n in sorted(names.items())
                    if classify(n)[:2] == (path, phase)
                    and n.endswith(suffix))

    module, fwd = instr("encoder/attention", "forward")
    _, bwd = instr("encoder/attention", "backward")
    _, loop = instr("encoder", "forward", "/while")
    _, head = instr("head", "forward")
    trace_module = module + "(1234)"  # the trace's name has a fingerprint
    events = []
    for dev in ("/device:TPU:0", "/device:TPU:1"):
        events += [
            # a while of 100 ns holding two attention ops of 30 and 10 ns
            OpEvent(dev, trace_module, f"%{loop} = (f32[4,8]) while(...)",
                    0, 100),
            OpEvent(dev, trace_module, fwd, 10, 40),
            OpEvent(dev, trace_module, bwd, 50, 60),
            OpEvent(dev, trace_module, head, 100, 120),
            OpEvent(dev, "jit_other(99)", "fusion.1", 120, 150),
        ]
    out = device_time_by_scope(events, hlo)
    ns = 1e-9
    # per device: the two devices' times are averaged
    assert out["by_scope"]["encoder/attention"] == pytest.approx(
        {"forward": 30 * ns, "backward": 10 * ns})
    assert out["by_scope"]["head"] == pytest.approx({"forward": 20 * ns})
    # the loop's self time (100 less its body's 40) has no leaf scope
    assert out["no_leaf"] == {"encoder": pytest.approx({loop: 60 * ns})}
    assert out["by_top"] == pytest.approx({"encoder": 100 * ns,
                                           "head": 20 * ns})
    assert out["total_s"] == pytest.approx(120 * ns)
    assert out["unmatched"] == pytest.approx({"jit_other": 30 * ns})
    assert out["kernels"] == {}


def test_trainer_run_writes_host_spans(tmp_path):
    cfg = reduced(get_config("qwen2-0.5b"))
    init, loss_fn, _ = build_model(cfg)
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=8, batch_size=2)
    steps = 3
    tr = Trainer(TrainerConfig(total_steps=steps, checkpoint_every=2,
                               checkpoint_dir=str(tmp_path / "ckpt"),
                               log_every=1),
                 loss_fn, init, OptimizerConfig(), data)
    with jax.profiler.trace(str(tmp_path / "prof")):
        tr.run(jax.random.PRNGKey(0))
    path, = glob.glob(os.path.join(tmp_path, "prof", "**", "*.xplane.pb"),
                      recursive=True)
    profile = jax.profiler.ProfileData.from_file(path)
    spans = collections.defaultdict(list)
    for plane in profile.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    spans[e.name].append(e)
    step_nums = sorted(dict(e.stats)["step_num"]
                       for e in spans["train_step"])
    assert step_nums == list(range(steps))
    for name in ("data", "dispatch", "sync"):
        assert len(spans[name]) == steps, name
    # one async save inside step 1, one blocking save after the loop
    assert len(spans["checkpoint"]) == 2
    # each step's phases lie inside its span
    for step in spans["train_step"]:
        inside = [e.name for e in spans["data"] + spans["dispatch"]
                  + spans["sync"]
                  if step.start_ns <= e.start_ns and e.end_ns <= step.end_ns]
        assert sorted(inside) == ["data", "dispatch", "sync"]
    assert len(tr.metrics_history) == steps


def test_program_timer_call_is_a_profiler_span(tmp_path):
    from repro.serve.tracing import ProgramTimer

    decode = ProgramTimer("decode", jax.jit(lambda x: x + 1))
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            jax.block_until_ready(decode(jnp.zeros(4)))
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    profile = jax.profiler.ProfileData.from_file(path)
    spans = [e for plane in profile.planes if plane.name.startswith("/host")
             for line in plane.lines for e in line.events
             if e.name == "decode"]
    assert len(spans) == decode.calls == 3
