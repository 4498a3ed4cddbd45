"""Reduces a ``--trace 1`` run's profiler trace to what the metrics read.

Reads the ``.xplane.pb`` that ``jax.profiler.trace`` wrote, with
``jax.profiler.ProfileData``. Device planes are ``/device:TPU:<n>``; their
``XLA Ops`` line holds one event per HLO operation run (a ``while`` holds
its body's operations, which are events of their own). Gives:

* busy: the union of the operation intervals, per device, averaged;
* the time and count of Pallas kernels (``custom_call_target=
  "tpu_custom_call"``), in all and by the shapes of their operands: the
  program names none of its kernels yet, so a reader tells them apart by
  those shapes;
* the breakdown: the operations with the most self time, and the device's
  idle gaps summed by what the host's Python thread was doing in them.
"""
from __future__ import annotations

import collections
import glob
import os
import re

PALLAS = 'custom_call_target="tpu_custom_call"'
_KIND = re.compile(r"\s([a-z][a-z0-9_-]*)\(")
_SHAPE = re.compile(r"[a-z0-9]+\[[0-9,]*\]")
_OPERANDS = re.compile(r"custom-call\((.*?)\), custom_call_target")
TOP = 10


def find_xplane(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def short_name(hlo: str) -> str:
    """``fusion.659 fusion f32[6,128,1536,384]`` from an HLO op's text."""
    head, _, rest = hlo.partition(" = ")
    kind = _KIND.search(rest)
    shape = _SHAPE.search(rest)
    kind = kind.group(1) if kind else "?"
    if PALLAS in rest:
        kind += ":tpu_custom_call"
    return f"{head.lstrip('%')} {kind} {shape.group(0) if shape else ''}"


def operand_shapes(hlo: str) -> str:
    """``bf16[4,256,384] f32[384,128]``: the operand shapes of a custom
    call's HLO text, without layouts."""
    m = _OPERANDS.search(hlo)
    return " ".join(_SHAPE.findall(m.group(1))) if m else ""


def union(intervals: list) -> list:
    """Merged, sorted ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events: list) -> dict:
    """Self time of each op name: its duration less that of the ops nested
    inside it (the body of a ``while``, say)."""
    out = collections.Counter()
    stack = []  # [end, name, child time]
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, n, child = stack.pop()
            out[n] -= child
        if stack:
            stack[-1][2] += e - s
        out[name] += e - s
        stack.append([e, name, 0])
    while stack:
        end, n, child = stack.pop()
        out[n] -= child
    return out


def _host_spans(pd) -> list:
    """``(start, end, name)`` of the Python thread's events on the host."""
    best = []
    for plane in pd.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            evs = [(e.start_ns, e.end_ns, e.name) for e in line.events]
            if (sum(n.startswith("$") for _, _, n in evs)
                    > sum(n.startswith("$") for _, _, n in best)):
                best = evs
    return best


def _attribute(gap: tuple, host: list) -> str:
    """The innermost host event that covers the gap's middle."""
    mid = (gap[0] + gap[1]) / 2
    inner = [(e - s, n) for s, e, n in host if s <= mid <= e]
    return min(inner)[1] if inner else "no host event"


def reduce(pd, window_s: float, n_devices: int) -> dict:
    per_device, busy, ops = [], [], collections.Counter()
    pallas_s, pallas_n, n_events = 0.0, 0, 0
    by_shapes = collections.defaultdict(lambda: [0.0, 0])
    for plane in pd.planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        evs = [(e.start_ns, e.end_ns, e.name) for line in plane.lines
               if line.name == "XLA Ops" for e in line.events]
        if not evs:
            continue
        n_events += len(evs)
        merged = union([(s, e) for s, e, _ in evs])
        per_device.append(merged)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for name, t in self_times(evs).items():
            ops[short_name(name)] += t * 1e-9
        for s, e, name in evs:
            if PALLAS in name:
                pallas_s += (e - s) * 1e-9
                pallas_n += 1
                entry = by_shapes[operand_shapes(name)]
                entry[0] += (e - s) * 1e-9
                entry[1] += 1
    gaps = collections.Counter()
    if per_device:
        host = _host_spans(pd)
        merged = per_device[0]
        for (_, a), (b, _) in zip(merged, merged[1:]):
            gaps[_attribute((a, b), host)] += (b - a) * 1e-9
    n = max(len(busy), 1)
    return {
        "busy_s": sum(busy) / n,
        "window_s": window_s,
        "n_devices": n_devices,
        "device_events": n_events,
        "pallas_s": pallas_s / n,
        "pallas_calls": pallas_n,
        "pallas_by_operands": {k: [t / n, c] for k, (t, c) in
                               by_shapes.items()},
        "breakdown": {
            "device_ops": [[k, v / n] for k, v in ops.most_common(TOP)],
            "idle_gaps": [[k, v] for k, v in gaps.most_common(TOP)],
        },
    }


def reduce_dir(trace_dir: str, window_s: float, n_devices: int) -> dict:
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(find_xplane(trace_dir)), window_s,
                  n_devices)
