"""Harness discovery, the generators, the result line and the exit codes."""
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(os.path.dirname(BENCH))
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import tiny_tree  # noqa: E402
from chipbench import harness, peaks, spec, weights  # noqa: E402

KEYS = ["correct", "attempted", "failed", "metrics", "device"]


@pytest.fixture(autouse=True)
def _own_compile_cache():
    yield from tiny_tree.restore_compile_cache()


def test_every_cell_of_the_benchmark_resolves():
    bench = spec.load(REPO)
    for w in bench["workloads"]:
        cell = spec.resolve(REPO, w["name"])
        assert cell.end_to_end and cell.per_layer
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        for m in cell.per_layer:
            assert callable(spec.metric_reader(cell, m["name"]))
        assert set(cell.limits)


def test_unknown_cell():
    with pytest.raises(KeyError):
        spec.resolve(REPO, "no-such.cell")


def test_a_cell_added_as_files_is_found(tmp_path):
    """A new configuration, mix, limits file and per-layer metric, added as
    files plus BENCHMARK.json entries, with no edit to an existing file."""
    root = tiny_tree.make(str(tmp_path))
    bench = os.path.join(root, "benchmarks", "chip")
    shutil.copy(os.path.join(bench, "configs", "soft-moe-s16-128e.json"),
                os.path.join(bench, "configs", "other.json"))
    with open(os.path.join(bench, "traffic", "long_train.json"), "w") as f:
        json.dump({"driver": "vit_train", "check_steps": 3}, f)
    with open(os.path.join(bench, "limits", "other.long.json"), "w") as f:
        json.dump({"loss_gap": {"limit": 1.0}}, f)
    with open(os.path.join(bench, "metrics", "steps_seen.py"), "w") as f:
        f.write("def read(rec):\n    return float(rec['calls'])\n")
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        b = json.load(f)
    b["configs"].append(dict(b["configs"][0], name="other",
                             file="benchmarks/chip/configs/other.json"))
    b["workloads"].append({"name": "other.long", "config": "other",
                           "traffic": "long_train", "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "steps_seen", "unit": "steps",
                           "better": "higher", "source": "program_counter",
                           "layer": "trainer", "moves": "images_per_s",
                           "workloads": ["other.long"]})
    b["end_to_end"][0]["workloads"].append("other.long")
    with open(path, "w") as f:
        json.dump(b, f)
    cell = spec.resolve(root, "other.long")
    assert cell.config_name == "other" and cell.traffic["check_steps"] == 3
    assert [m["name"] for m in cell.per_layer] == ["steps_seen"]
    assert spec.metric_reader(cell, "steps_seen")({"calls": 7}) == 7.0


def test_images_are_made_from_the_seed():
    import jax.numpy as jnp

    def batch(seed, step):
        return weights.images(weights.seed_key(seed, 1), step, 4, 16, 48, 10)

    a, b = batch(2**33 + 1, 0), batch(2**33 + 1, 0)
    assert jnp.array_equal(a["patches"], b["patches"])
    assert jnp.array_equal(a["labels"], b["labels"])
    # seeds past 32 bits differ from their low 32 bits; steps differ
    assert not jnp.array_equal(a["patches"], batch(1, 0)["patches"])
    assert not jnp.array_equal(a["patches"], batch(2**33 + 1, 1)["patches"])
    assert a["labels"].min() >= 0 and a["labels"].max() < 10
    rows = a["patches"].reshape(4, -1)
    assert len({tuple(r[:8].tolist()) for r in rows}) == 4


def test_weights_are_made_from_the_seed():
    shapes = {"w": (3, 4), "norm": {"scale": (4,)}}
    p = weights.make_params(shapes, 5)
    q = weights.make_params(shapes, 5)
    assert (p["w"] == q["w"]).all() and (p["norm"]["scale"] != 0).all()
    r = weights.make_params(shapes, 2**32 + 5)
    assert not (p["w"] == r["w"]).all()


def test_kept_calls_are_drawn_from_the_seed():
    from chipbench.drivers import vit_infer

    a, b = vit_infer.kept(7, 8), vit_infer.kept(7, 8)
    assert (a == b).all() and a[0]
    assert 0.08 < a.mean() < 0.17
    assert not (a == vit_infer.kept(8, 8)).all()


@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return tiny_tree.make(str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture()
def cpu_peaks(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", tiny_tree.CPU_PEAKS)


@pytest.mark.parametrize("traced", [0, 1])
def test_result_line(tiny_root, cpu_peaks, traced):
    res = harness.run(["--workload", "s16-128e.train", "--seed",
                       str(2**31 + 11), "--seconds", "0.5", "--trace",
                       str(traced)], root=tiny_root, require_chip=False)
    want = KEYS + (["breakdown"] if traced else []) + ["checks"]
    assert list(res) == want
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    dev = res["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if traced:
        assert {"busy_s", "window_s"} <= set(dev)
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        assert "mfu.train" in res["metrics"]
    else:
        assert set(res["metrics"]) == {"images_per_s", "setup_s"}
        assert all(m["value"] > 0 for m in res["metrics"].values())
    assert set(res["checks"]) == {"loss_gap", "grad1_gap", "change3_gap"}
    json.dumps(res)


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "s16-128e.train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_exits_nonzero_with_no_result(tiny_root):
    out = _run_py(tiny_root)
    assert out.returncode == 1, out.stderr[-2000:]
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """A directory that holds only BENCHMARK.json and the benchmark's own
    files has no program to run."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip")
    out = _run_py(str(tmp_path))
    assert out.returncode != 0
    assert out.stdout.strip() == ""
