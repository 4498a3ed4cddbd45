"""Runs one cell once: set-up, the measured window, the traced reading of
the per-layer metrics, the correctness check, and the result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The last line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), and last the key ``checks``: each compared number with its
limit. The same numbers close standard error. With no accelerator, or
fewer chips than the cell asks for, the run exits 1 and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

from . import spec as spec_mod

SCRATCH = ".bench"  # inside the checkout, git-ignored
# A --trace 1 run traces at most this much of its window: the trace of a
# longer one takes minutes to reduce and says nothing more per layer.
TRACE_SECONDS = 10.0


class NoChip(RuntimeError):
    pass


@dataclass
class Window:
    calls: int
    units: int
    seconds: float
    compiles: int


@dataclass
class Outcome:
    """What a driver hands back: end-to-end values besides ``setup_s``,
    the counts of the run, the compared numbers, and what per-layer
    readers read (``record``)."""
    metrics: dict
    attempted: int
    failed: int
    numbers: dict
    memory_peak_bytes: int
    record: dict = field(default_factory=dict)


class Context:
    def __init__(self, cell, seed: int, seconds: float, trace: bool,
                 t_start: float):
        self.cell, self.seed, self.trace = cell, seed, trace
        self.seconds = min(seconds, TRACE_SECONDS) if trace else seconds
        self.t_start = t_start
        self.config, self.traffic = cell.config, cell.traffic
        self.setup_s = None
        self.window_result = None
        self.trace_dir = None
        self._compiles = self._compiles0 = 0
        self._t0 = 0.0

    def scratch(self, *parts: str) -> str:
        path = os.path.join(self.cell.root, SCRATCH, *parts)
        os.makedirs(path, exist_ok=True)
        return path

    def on_compile(self, event, duration_secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self._compiles += 1

    def start_window(self) -> float:
        """Ends set-up and starts the measured window (under ``--trace 1``
        the profiler with it); returns its start on the host clock. The
        Python collector is off in the window: what set-up made is frozen
        out of its reach, and nothing the window makes is collected until
        it closes."""
        import jax

        gc.collect()
        gc.freeze()
        gc.disable()
        self.setup_s = time.perf_counter() - self.t_start
        if self.trace:
            self.trace_dir = os.path.join(self.scratch("trace"),
                                          self.cell.name)
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            jax.profiler.start_trace(self.trace_dir)
        self._compiles0 = self._compiles
        self._t0 = time.perf_counter()
        return self._t0

    def end_window(self, calls: int, units: int,
                   seconds: float | None = None) -> Window:
        """Closes the window: ``units`` of work in ``calls`` calls over
        ``seconds`` (by default, until now)."""
        import jax

        if seconds is None:
            seconds = time.perf_counter() - self._t0
        gc.enable()
        gc.unfreeze()
        if self.trace:
            jax.profiler.stop_trace()
        self.window_result = Window(calls, units, seconds,
                                    self._compiles - self._compiles0)
        return self.window_result

    def window(self, call) -> Window:
        """Calls ``call(i)`` (which waits for its own result and returns
        the units of work it completed) until ``seconds`` have passed."""
        units = calls = 0
        t0 = self.start_window()
        try:
            while True:
                units += call(calls)
                calls += 1
                t = time.perf_counter()
                if t - t0 >= self.seconds:
                    break
        finally:
            if self.window_result is None:
                self.end_window(calls, units, time.perf_counter() - t0)
        return self.window_result


def memory_peak_bytes() -> int:
    import jax

    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


def enable_compile_cache(root: str) -> str:
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR``, else the
    fixed ``<checkout>/.jax_cache``; every program is cached, however
    small or quick to compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.abspath(root), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def judge(numbers: dict, limits: dict) -> tuple:
    """``correct`` and the checks line: every number at or under its
    limit. A number the limits file lacks, or one that is not finite,
    fails."""
    checks, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name, {}).get("limit")
        good = (limit is not None and value == value
                and abs(value) != float("inf") and value <= limit)
        ok = ok and good
        checks[name] = {"value": value, "limit": limit}
    return ok and bool(numbers), checks


def _device_info(devices, peak: int) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run(argv=None, t_start: float | None = None, root: str | None = None,
        require_chip: bool = True) -> dict:
    """One run of one cell; returns the result object (raises NoChip)."""
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = root or os.path.dirname(os.path.dirname(spec_mod.BENCH_DIR))
    cell = spec_mod.resolve(root, args.workload)
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(f"the program is not at {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
    # libtpu logs to /tmp/tpu_logs unless told otherwise: keep them in
    # the checkout, beside the trace
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(root, SCRATCH,
                                                      "tpu_logs"))
    enable_compile_cache(root)

    import jax
    from jax import monitoring

    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform} devices")
    if len(devices) < cell.chips:
        raise NoChip(f"the cell needs {cell.chips} chips, JAX found "
                     f"{len(devices)}")
    from .peaks import peaks_for

    peaks = peaks_for(devices[0].device_kind)
    ctx = Context(cell, args.seed, args.seconds, bool(args.trace), t_start)
    monitoring.register_event_duration_secs_listener(ctx.on_compile)
    driver = importlib.import_module(
        f"chipbench.drivers.{cell.traffic['driver']}")
    out = driver.run(ctx)
    gc.collect()
    win = ctx.window_result
    correct, checks = judge(out.numbers, cell.limits)
    result = {"correct": correct, "attempted": out.attempted,
              "failed": out.failed}
    device = _device_info(devices, out.memory_peak_bytes)
    if args.trace:
        from . import trace as trace_mod

        red = trace_mod.reduce_dir(ctx.trace_dir, win.seconds,
                                   n_devices=cell.chips)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        rec = dict(out.record, trace=red, peaks=peaks, window=win,
                   config=cell.config, traffic=cell.traffic)
        metrics = {}
        for m in cell.per_layer:
            value = spec_mod.metric_reader(cell, m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result.update(metrics=metrics, device=device,
                      breakdown=red["breakdown"])
    else:
        values = dict(out.metrics, setup_s=ctx.setup_s)
        result.update(metrics={
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}, device=device)
    result["checks"] = checks
    print(f"window calls={win.calls} units={win.units} "
          f"seconds={win.seconds!r} compiles_in_window={win.compiles} "
          f"setup_s={ctx.setup_s!r}", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return result


def main(argv=None, t_start: float | None = None) -> int:
    try:
        result = run(argv, t_start)
    except NoChip as e:
        print(f"chip benchmark: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0
