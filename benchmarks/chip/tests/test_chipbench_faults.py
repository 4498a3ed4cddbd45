"""A whole run at a tiny size on the CPU, with the harness's look for a chip
skipped and the timed path broken underneath: ``correct`` has to come out
false for every fault the cell can have, and true with none."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import tiny_tree  # noqa: E402
from chipbench import harness, peaks  # noqa: E402


@pytest.fixture(autouse=True)
def _own_compile_cache():
    yield from tiny_tree.restore_compile_cache()


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_tree.make(str(tmp_path_factory.mktemp("tiny")),
                          use_kernel=True)


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", tiny_tree.CPU_PEAKS)


def _run(root, cell, seed=2**32 + 17):
    return harness.run(["--workload", cell, "--seed", str(seed),
                        "--seconds", "0.5", "--trace", "0"], root=root,
                       require_chip=False)


@pytest.mark.parametrize("cell", ["s16-128e.train", "s16-128e.infer"])
def test_sound_run_is_correct(root, cell):
    res = _run(root, cell)
    assert res["correct"] is True, res["checks"]


def test_state_left_unchanged(root, monkeypatch):
    import repro.train.trainer as trainer_mod

    real = trainer_mod.make_train_step

    def frozen(loss_fn, opt_cfg, microbatches=1):
        step = real(loss_fn, opt_cfg, microbatches)

        def run(state, batch):
            _, metrics = step(state, batch)
            return state, metrics

        return run

    monkeypatch.setattr(trainer_mod, "make_train_step", frozen)
    res = _run(root, "s16-128e.train")
    assert res["correct"] is False
    assert res["checks"]["grad1_gap"]["value"] == pytest.approx(1.0)


def test_half_the_batch_left_out(root, monkeypatch):
    import jax
    import repro.models as models

    real = models.vit_loss

    def half(params, cfg, batch, use_kernel=False):
        batch = jax.tree.map(lambda a: a[: a.shape[0] // 2], batch)
        return real(params, cfg, batch, use_kernel=use_kernel)

    monkeypatch.setattr(models, "vit_loss", half)
    res = _run(root, "s16-128e.train")
    assert res["correct"] is False, res["checks"]


def test_answer_altered_where_it_is_produced(root, monkeypatch):
    import repro.models as models

    real = models.vit_apply

    def altered(params, cfg, patches, use_kernel=False):
        logits, aux = real(params, cfg, patches, use_kernel=use_kernel)
        return logits.at[-1, 0].add(0.25 * abs(logits[-1]).max()), aux

    monkeypatch.setattr(models, "vit_apply", altered)
    res = _run(root, "s16-128e.infer")
    assert res["correct"] is False, res["checks"]


# The control at a size a test run holds: d 192, 3 heads, 16 experts, 49
# patches, batch 16. On the CPU there the program's bf16 first gradient
# reads up to 1.2e-2 against the f32 reference, more than the control's,
# while its loss and parameter change read 2-4x below the control's; so
# these limits are the test size's own, not the cell's (those come from
# chip runs at full size). The run is deterministic for its seed.
CONTROL_SIZE = {"hidden_size": 192, "num_attention_heads": 3,
                "head_dim": 64, "intermediate_size": 768, "num_patches": 49,
                "patch_dim": 192, "num_classes": 100, "num_experts": 16}
CONTROL_LIMITS = {
    "s16-128e.train": {"loss_gap": 6.5e-5, "grad1_gap": 0.05,
                       "change3_gap": 1.5e-3},
    "s16-128e.infer": {"logits_gap": 0.03},
}


@pytest.fixture(scope="module")
def control_root(tmp_path_factory):
    return tiny_tree.make(str(tmp_path_factory.mktemp("control")), batch=16,
                          size=CONTROL_SIZE, limits=CONTROL_LIMITS)


@pytest.mark.parametrize("cell", ["s16-128e.train", "s16-128e.infer"])
def test_control_is_not_correct(control_root, cell):
    """The reference in float8 in the program's place fails the limits that
    the program itself meets at the same size."""
    import numpy as np

    from chipbench import compare, ref_train, spec
    from chipbench.drivers import vit_infer

    seed = 2**32 + 29
    res = _run(control_root, cell, seed)
    assert res["correct"] is True, res["checks"]
    c = spec.resolve(control_root, cell)
    if cell.endswith("train"):
        numbers, _ = compare.train_numbers(
            ref_train.run(c.config, seed, 3, control=True),
            ref_train.run(c.config, seed, 3))
    else:
        b = c.traffic["batch"]
        ctl = vit_infer.reference_logits(c.config, seed, [0], b, True)[0]
        ref = vit_infer.reference_logits(c.config, seed, [0], b)[0]
        numbers = {"logits_gap": float(np.asarray(
            vit_infer.row_errors(ctl, ref)).max())}
    correct, checks = harness.judge(numbers, c.limits)
    assert correct is False, checks
