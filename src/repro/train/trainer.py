"""Training loop with production fault-tolerance:

  * checkpoint/restart — CheckpointManager (atomic, async, keep-N); the
    loop always resumes from the latest committed step, and the data
    pipeline is stateless-resumable, so a preempted job replays nothing.
  * preemption handling — SIGTERM/SIGINT trigger a final blocking save
    before exit (the standard TPU-preemption grace-period pattern).
  * straggler watchdog — per-step wall time EWMA; steps slower than
    ``straggler_factor``× the EWMA are logged with their step index (on a
    real fleet this feeds the scheduler to replace the slow host; here it
    records the event and optionally aborts-to-restart). Each step is a
    profiler span, ``StepTraceAnnotation("train_step", step_num=step)``,
    holding the spans ``data``, ``dispatch``, ``sync`` and ``checkpoint``,
    so a logged straggler is found in a profile by its step number.
  * elastic scaling — restore() re-shards onto whatever mesh the restarted
    job has (see CheckpointManager.restore); nothing in the loop assumes
    the device count of the previous incarnation.
"""
from __future__ import annotations

import signal
import time
from dataclasses import dataclass, field
from typing import Any, Callable, List, Optional

import jax
from jax.profiler import StepTraceAnnotation, TraceAnnotation

from ..checkpoint.manager import CheckpointManager
from ..optim import OptimizerConfig
from .step import init_train_state, make_train_step


@dataclass
class TrainerConfig:
    total_steps: int = 100
    checkpoint_every: int = 50
    checkpoint_dir: str = "/tmp/repro_ckpt"
    keep_checkpoints: int = 3
    log_every: int = 10
    straggler_factor: float = 3.0
    straggler_abort: bool = False
    microbatches: int = 1


@dataclass
class StragglerWatchdog:
    factor: float = 3.0
    ewma: Optional[float] = None
    alpha: float = 0.1
    events: List[dict] = field(default_factory=list)

    def observe(self, step: int, dt: float) -> bool:
        slow = self.ewma is not None and dt > self.factor * self.ewma
        if slow:
            self.events.append({"step": step, "dt": dt, "ewma": self.ewma})
        # EWMA excludes straggler steps so one hiccup doesn't mask the next
        if not slow:
            self.ewma = dt if self.ewma is None else (
                (1 - self.alpha) * self.ewma + self.alpha * dt
            )
        return slow


class Trainer:
    def __init__(self, cfg: TrainerConfig, loss_fn: Callable,
                 init_fn: Callable, opt_cfg: OptimizerConfig,
                 data, jit_kwargs: Optional[dict] = None):
        self.cfg = cfg
        self.data = data
        self.ckpt = CheckpointManager(cfg.checkpoint_dir,
                                      keep=cfg.keep_checkpoints)
        step_fn = make_train_step(loss_fn, opt_cfg,
                                  microbatches=cfg.microbatches)
        # Donate the train state: the loop reassigns
        # ``state, _ = train_step(state, batch)`` and never reads the old
        # state again, so XLA aliases params/opt moments in place instead
        # of holding two copies across the step (no-op on CPU). Explicit
        # jit_kwargs still override — pass donate_argnums=() to opt out.
        # Proved by the `donation` pass (src/repro/analysis/).
        jit_kwargs = dict(jit_kwargs) if jit_kwargs else {}
        jit_kwargs.setdefault("donate_argnums", (0,))
        self.train_step = jax.jit(step_fn, **jit_kwargs)
        self.init_fn = init_fn
        self.watchdog = StragglerWatchdog(factor=cfg.straggler_factor)
        self._preempted = False
        self.metrics_history: List[dict] = []

    def _install_preemption_hook(self):
        def handler(signum, frame):
            self._preempted = True

        try:
            signal.signal(signal.SIGTERM, handler)
        except ValueError:
            pass  # not on main thread (tests)

    def run(self, rng, start_state: Any = None) -> Any:
        self._install_preemption_hook()
        start_step = 0
        target = (
            start_state if start_state is not None
            else self._abstract_state(rng)
        )
        ckpt_step, ckpt_state = self.ckpt.restore_latest(target)
        if ckpt_step is not None:
            start_step, state = ckpt_step, ckpt_state
            print(f"[trainer] resumed from step {start_step}")
        elif start_state is not None:
            state = start_state
        else:
            state = init_train_state(rng, self.init_fn)

        step = start_step
        while step < self.cfg.total_steps:
            with StepTraceAnnotation("train_step", step_num=step):
                with TraceAnnotation("data"):
                    batch = self.data.batch(step)
                t0 = time.perf_counter()
                with TraceAnnotation("dispatch"):
                    state, metrics = self.train_step(state, batch)
                with TraceAnnotation("sync"):
                    jax.block_until_ready(metrics["total_loss"])
                dt = time.perf_counter() - t0
                slow = self.watchdog.observe(step, dt)
                if slow:
                    print(f"[watchdog] straggler step {step}: {dt:.3f}s "
                          f"(ewma {self.watchdog.ewma:.3f}s)")
                    if self.cfg.straggler_abort:
                        with TraceAnnotation("checkpoint"):
                            self.ckpt.save(step, state, blocking=True)
                        raise RuntimeError("straggler abort -> restart")
                step += 1
                if step % self.cfg.log_every == 0:
                    self._log(step, metrics, dt)
                if step % self.cfg.checkpoint_every == 0:
                    with TraceAnnotation("checkpoint"):
                        self.ckpt.save_async(step, state)
            if self._preempted:
                print(f"[trainer] preempted at step {step}; checkpointing")
                self._save(step, state)
                return state
        self._save(step, state)
        return state

    def _save(self, step: int, state):
        """Blocking save after any async one in flight."""
        with TraceAnnotation("checkpoint"):
            self.ckpt.wait()
            self.ckpt.save(step, state, blocking=True)

    def _log(self, step: int, metrics: dict, dt: float):
        # A telemetry-enabled loss_fn (lm_loss(telemetry=True)) nests the
        # model-interior stats pytree under metrics["telemetry"]; flatten
        # it to scalars next to the scalar metrics (serve/telemetry.py
        # owns the naming).
        telem = metrics.pop("telemetry", None)
        m = {k: float(v) for k, v in metrics.items()}
        if telem is not None:
            from ..serve.telemetry import flatten_telemetry
            m.update({
                f"telemetry_{k}": v for k, v in
                flatten_telemetry(jax.device_get(telem)).items()
            })
        m["step"] = step
        m["step_time"] = dt
        self.metrics_history.append(m)
        print(f"[trainer] step {step}: loss={m.get('total_loss'):.4f}"
              f" lr={m.get('lr', 0):.2e} dt={dt:.3f}s")

    def _abstract_state(self, rng):
        return jax.eval_shape(
            lambda r: init_train_state(r, self.init_fn), rng
        )
