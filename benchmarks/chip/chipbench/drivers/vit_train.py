"""Closed loop of back-to-back training steps through ``Trainer.train_step``.

Set-up makes the train state in one jitted call from the seed (weights from
``weights.make_params``, AdamW moments by the program's
``init_train_state``), builds the ``Trainer`` and takes the first
``check_steps`` steps through its jitted, state-donating step, reading the
loss of each, the first gradient from the optimizer's first moment and the
parameters' change after the last. That same state goes on into the
window, one wait per step as ``Trainer.run`` waits. After the window the
state is freed and the reference takes the same steps.
"""
from __future__ import annotations

import functools
import gc
import sys

import jax

from .. import compare, ref_train, weights
from ..harness import Outcome, memory_peak_bytes
from . import _vit


class Program:
    """The program's trainer for one configuration, built once."""

    def __init__(self, cfg: dict, ckpt_dir: str):
        import repro.models as models
        from repro.optim import OptimizerConfig
        from repro.train import Trainer, TrainerConfig

        self.cfg = cfg
        self.batch = cfg["trainer"]["batch"]
        mcfg = _vit.model_config(cfg)
        self.shapes = _vit.check_layout(mcfg, cfg)
        self.opt = OptimizerConfig(
            schedule="constant", warmup_steps=0, total_steps=10**9,
            cooldown_steps=1, **cfg["trainer"]["optimizer"])
        use_kernel = cfg["use_kernel"]
        self.trainer = Trainer(
            TrainerConfig(checkpoint_dir=ckpt_dir),
            lambda p, b: models.vit_loss(p, mcfg, b, use_kernel=use_kernel),
            None, self.opt, data=None)
        self.make_batch = jax.jit(functools.partial(
            weights.images, batch=self.batch,
            num_patches=cfg["num_patches"], patch_dim=cfg["patch_dim"],
            num_classes=cfg["num_classes"]))

    def start(self, seed: int, n_check: int):
        """The seed's state after ``n_check`` steps, the readings of those
        steps, and ``step(i)``, which takes step ``i`` on that state and
        returns its loss (not waited for)."""
        from repro.train.step import init_train_state

        shapes = self.shapes
        state = weights.make_params(
            shapes, seed, lambda p: init_train_state(None, lambda _: p))
        image_key = weights.seed_key(seed, stream=1)
        box = [state]
        del state

        def step(i: int):
            box[0], m = self.trainer.train_step(
                box[0], self.make_batch(image_key, i))
            return m["total_loss"]

        losses, grad1 = [], None
        for i in range(n_check):
            losses.append(float(step(i)))
            if i == 0:  # mu after one step is (1 - b1) x the clipped grad
                grad1 = {k: v / (1 - self.opt.b1) for k, v in
                         ref_train.leaf_norms(box[0]["opt"]["mu"]).items()}
        readings = {"losses": losses, "grad1": grad1,
                    "change": ref_train.change_norms(box[0]["params"], seed)}
        return box, readings, step


def run(ctx) -> Outcome:
    n_check = ctx.traffic["check_steps"]
    prog = Program(ctx.config, ctx.scratch("ckpt"))
    box, readings, step = prog.start(ctx.seed, n_check)

    def call(i: int) -> int:
        jax.block_until_ready(step(n_check + i))
        return prog.batch

    win = ctx.window(call)
    peak = memory_peak_bytes()
    batch = prog.batch
    box.clear()
    del prog, step
    gc.collect()
    ref = ref_train.run(ctx.config, ctx.seed, n_check)
    numbers, detail = compare.train_numbers(readings, ref)
    print("train check " + repr(detail), file=sys.stderr)
    rate = win.units / win.seconds
    return Outcome(
        metrics={"images_per_s": rate}, attempted=win.units, failed=0,
        numbers=numbers, memory_peak_bytes=peak,
        record={"units": win.units, "calls": win.calls,
                "batch": batch, "units_per_s": rate,
                "softmoe_passes": ("fwd", "bwd")})
