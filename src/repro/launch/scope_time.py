"""Device time by layer scope: a profile joined with the compiled program.

The program names its layers with ``jax.named_scope`` (``SCOPES``) and its
Pallas kernels with ``pallas_call(name=...)`` (``KERNELS``); the list and
what each name covers are in docs/observability.md. A profiler trace's
device ops carry only their HLO instruction text, and the compiled module's
text carries each instruction's ``op_name`` metadata, which holds the
scopes. ``device_time_by_scope`` joins the two on (module name, instruction
name) and sums self time:

* by outermost scope (``TOP``, or "" for none), by leaf scope path
  (``encoder/moe/dispatch``) and by phase: ``forward``, ``backward``
  (under ``transpose(jvp(...))``), ``recompute`` (a rematerialised
  forward, ``rematted_computation``) and ``optimizer``;
* by kernel name, for the Pallas ops;
* what falls under no leaf scope (no scope at all, or only ``encoder`` or
  ``optimizer``), by instruction name.

Get the HLO text from the jitted program the profile ran::

    open("step.hlo", "w").write(jitted.lower(*args).compile().as_text())

and run ``python -m repro.launch.scope_time <trace dir> step.hlo
[more.hlo ...]``, which prints the sums as JSON, in seconds averaged over
the trace's devices.
"""
from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import re
import sys
from typing import Dict, Iterable, List, NamedTuple, Tuple

TOP = ("embed", "encoder", "final_norm", "head", "loss", "optimizer")
SCOPES = TOP + ("norm", "attention", "ssm", "mlp", "moe", "dispatch",
                "experts", "combine", "clip", "adamw")
# top scopes whose time belongs to their sub-scopes
_CONTAINERS = ("encoder", "optimizer")
KERNELS = ("softmoe_routing_fwd", "softmoe_routing_health",
           "softmoe_combine_online", "softmoe_combine_fwd",
           "softmoe_dispatch_bwd_dx", "softmoe_dispatch_bwd_dphi",
           "softmoe_combine_bwd_dx", "softmoe_combine_bwd_dys_dphi",
           "paged_decode_attend", "mha_full_fwd")

_MODULE = re.compile(r"^HloModule ([^\s,]+)", re.M)
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) ")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")


class OpEvent(NamedTuple):
    """One device op of a profile: the device it ran on, its module's
    name (``jit_step`` or ``jit_step(<fingerprint>)``), its instruction
    (``fusion.3`` or the op's whole HLO text) and its interval."""
    device: str
    module: str
    op: str
    start_ns: int
    end_ns: int


def _instruction(op: str) -> str:
    return op.partition(" = ")[0].strip().lstrip("%")


def _module_name(name: str) -> str:
    return re.sub(r"\(-?\d+\)$", "", name)


def parse_hlo(hlo_text: str) -> Dict[Tuple[str, str], str]:
    """``{(module, instruction): op_name}`` of compiled HLO text, which may
    hold several modules.

    XLA makes some instructions without an ``op_name``: a multi-output
    fusion, or an op it hoists out of a loop (the f32 -> bf16 cast of a
    scanned weight stack). Such a fusion takes the most common ``op_name``
    of the instructions it fuses; any other such instruction takes its
    first user's, when all its users lie under the same scopes."""
    out = {}
    starts = [(m.start(), m.group(1)) for m in _MODULE.finditer(hlo_text)]
    for i, (pos, module) in enumerate(starts):
        end = starts[i + 1][0] if i + 1 < len(starts) else len(hlo_text)
        names, calls = {}, {}
        users = collections.defaultdict(list)
        fused = collections.defaultdict(collections.Counter)
        comp = None
        for line in hlo_text[pos:end].splitlines():
            head = _COMPUTATION.match(line)
            if head:
                comp = head.group(1)
                continue
            m = _INSTR.match(line)
            if not m:
                continue
            name, rest = m.groups()
            op = _OP_NAME.search(rest)
            names[name] = op.group(1) if op else ""
            if op:
                fused[comp][op.group(1)] += 1
            called = _CALLS.search(rest)
            if called:
                calls[name] = called.group(1)
            for ref in dict.fromkeys(_REF.findall(rest)):
                users[ref].append(name)
        for name, comp in calls.items():
            if not names[name] and fused[comp]:
                names[name] = fused[comp].most_common(1)[0][0]
        changed = True
        while changed:
            changed = False
            for name in [n for n, op in names.items() if not op]:
                known = [names[u] for u in users[name] if names.get(u)]
                if known and len({classify(k)[0] for k in known}) == 1:
                    names[name] = known[0]
                    changed = True
        out.update(((module, k), v) for k, v in names.items())
    return out


def _unwrap(part: str) -> Tuple[str, bool]:
    """``transpose(jvp(encoder))`` -> (``encoder``, True: transposed)."""
    transposed = False
    while True:
        m = _WRAPPED.match(part)
        if not m:
            return part, transposed
        transposed |= part.startswith("transpose(")
        part = m.group(1)


def classify(op_name: str) -> Tuple[str, str, str]:
    """(scope path, phase, kernel name) of one op's ``op_name``.

    The path joins the ``SCOPES`` the op is under, outermost first
    (``encoder/moe/dispatch``); "" when it is under none."""
    scopes, kernel, phase = [], "", "forward"
    for part in op_name.split("/"):
        name, transposed = _unwrap(part)
        if transposed and phase == "forward":
            phase = "backward"
        if name == "rematted_computation":
            phase = "recompute"
        if name in SCOPES:
            scopes.append(name)
        elif name in KERNELS:
            kernel = name
    if "optimizer" in scopes:
        phase = "optimizer"
    return "/".join(scopes), phase, kernel


def self_times(events: Iterable[OpEvent]) -> List[Tuple[OpEvent, float]]:
    """Each event with its duration in seconds less that of the events
    nested in it on its device (a ``while`` holds its body's ops)."""
    out = []
    by_device = collections.defaultdict(list)
    for e in events:
        by_device[e.device].append(e)
    for evs in by_device.values():
        stack = []  # [event, child ns]
        for e in sorted(evs, key=lambda e: (e.start_ns, -e.end_ns)):
            while stack and stack[-1][0].end_ns <= e.start_ns:
                done, child = stack.pop()
                out.append((done, (done.end_ns - done.start_ns - child)
                            * 1e-9))
            if stack:
                stack[-1][1] += e.end_ns - e.start_ns
            stack.append([e, 0])
        out.extend((done, (done.end_ns - done.start_ns - child) * 1e-9)
                   for done, child in stack)
    return out


def device_time_by_scope(events: Iterable[OpEvent], hlo_text: str) -> dict:
    """Self time of a profile's device ops by scope, phase and kernel,
    joined with the compiled HLO text of the modules that ran them.

    Seconds, summed over the events and divided by the number of devices.
    Ops of a module the text does not hold, or of an instruction it does
    not name, are summed in ``unmatched`` by module."""
    events = list(events)
    n = max(len({e.device for e in events}), 1)
    names = parse_hlo(hlo_text)
    top = collections.Counter()
    phase_s = collections.Counter()
    kernels = collections.Counter()
    unmatched = collections.Counter()
    by_scope = collections.defaultdict(collections.Counter)
    no_leaf = collections.defaultdict(collections.Counter)
    for e, t in self_times(events):
        key = (_module_name(e.module), _instruction(e.op))
        if key not in names:
            unmatched[key[0]] += t / n
            continue
        path, phase, kernel = classify(names[key])
        first = path.split("/")[0]
        top[first] += t / n
        phase_s[phase] += t / n
        if kernel:
            kernels[kernel] += t / n
        if path.split("/")[-1] in _CONTAINERS + ("",):
            no_leaf[path][key[1]] += t / n
        else:
            by_scope[path][phase] += t / n
    return {
        "total_s": sum(top.values()),
        "by_top": dict(top.most_common()),
        "by_phase": dict(phase_s.most_common()),
        "by_scope": {k: dict(v.most_common()) for k, v in sorted(
            by_scope.items(), key=lambda kv: -sum(kv[1].values()))},
        "no_leaf": {k: dict(v.most_common()) for k, v in no_leaf.items()},
        "kernels": dict(kernels.most_common()),
        "unmatched": dict(unmatched.most_common()),
    }


def op_events(profile) -> list:
    """The ``OpEvent``s of a ``jax.profiler.ProfileData``: every device
    plane's ``XLA Ops``, each in the module whose ``XLA Modules`` event
    holds its start."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = sorted((e.start_ns, e.end_ns, e.name)
                      for e in lines.get("XLA Modules", []))
        starts = [m[0] for m in mods]
        for e in lines.get("XLA Ops", []):
            i = bisect.bisect_right(starts, e.start_ns) - 1
            module = mods[i][2] if i >= 0 and e.start_ns < mods[i][1] else ""
            out.append(OpEvent(plane.name, module, e.name, e.start_ns,
                               e.end_ns))
    return out


def _xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(files, key=os.path.getmtime)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) < 2:
        print(__doc__.strip(), file=sys.stderr)
        return 2
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(_xplane(argv[0]))
    hlo = "\n".join(open(p).read() for p in argv[1:])
    print(json.dumps(device_time_by_scope(op_events(profile), hlo),
                     indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
