"""Finds everything that belongs to one cell by the names in BENCHMARK.json.

A cell names a configuration and a traffic mix. The configuration's file is
the one BENCHMARK.json gives; the traffic mix is ``traffic/<traffic>.json``;
the limits of the cell's correctness check are ``limits/<cell>.json``; each
per-layer metric is read by ``metrics/<metric>.py``. A later cell, mix,
configuration or metric is added as new files plus an entry, with no edit
to anything here.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass, field

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list = field(default_factory=list)  # metric entries
    per_layer: list = field(default_factory=list)
    bench_dir: str = BENCH_DIR
    root: str = ""


def load(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(root: str, name: str) -> Cell:
    """The cell called ``name``, with its files read. Raises KeyError for
    a name BENCHMARK.json does not have, OSError for a missing file."""
    bench = load(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = configs[w["config"]]
    bench_dir = os.path.join(root, bench["paths"][0])
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    return Cell(
        name=name, chips=w["chips"], config_name=conf["name"],
        config=_read_json(os.path.join(root, conf["file"])),
        traffic=_read_json(os.path.join(bench_dir, "traffic",
                                        w["traffic"] + ".json")),
        limits=_read_json(os.path.join(bench_dir, "limits",
                                       name + ".json")),
        end_to_end=e2e, per_layer=layer, bench_dir=bench_dir, root=root)


def metric_reader(cell: Cell, metric: str):
    """``read(record) -> float | None`` from ``metrics/<metric>.py``."""
    path = os.path.join(cell.bench_dir, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
