"""A checkout in a temporary directory holding the benchmark at a tiny size,
for the CPU tests: the benchmark's files, the program (``src``, linked), and a
BENCHMARK.json whose cells run a two-block Soft-MoE ViT."""
from __future__ import annotations

import copy
import json
import os
import shutil
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(BENCH_DIR))

TINY = {
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 2,
    "head_dim": 32, "intermediate_size": 128, "image_size": 16,
    "patch_size": 4, "num_patches": 16, "patch_dim": 48, "num_classes": 10,
    "num_experts": 4, "slots_per_expert": 1,
}
LIMITS = {
    "s16-128e.train": {"loss_gap": 0.01, "grad1_gap": 0.05,
                       "change3_gap": 0.05},
    "s16-128e.infer": {"logits_gap": 0.05},
}
if os.path.join(REPO, "src") not in sys.path:
    sys.path.insert(0, os.path.join(REPO, "src"))
CPU_PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10,
             "source": "stand-in for CPU tests"}


def _edit(path: str, **kw):
    with open(path) as f:
        d = json.load(f)
    for k, v in kw.items():
        if isinstance(v, dict) and isinstance(d.get(k), dict):
            d[k] = dict(d[k], **v)
        else:
            d[k] = v
    with open(path, "w") as f:
        json.dump(d, f)


def make(tmp: str, use_kernel: bool = False, batch: int = 8, size=None,
         limits=None) -> str:
    """Builds the tree under ``tmp``; returns its root. ``size`` and
    ``limits`` replace ``TINY`` and ``LIMITS`` in part."""
    root = os.path.join(tmp, "checkout")
    bench = os.path.join(root, "benchmarks", "chip")
    shutil.copytree(BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    conf_path = os.path.join(bench, "configs", "soft-moe-s16-128e.json")
    with open(conf_path) as f:
        conf = json.load(f)
    conf.update(copy.deepcopy(TINY), use_kernel=use_kernel, **(size or {}))
    conf["trainer"]["batch"] = batch
    with open(conf_path, "w") as f:
        json.dump(conf, f)
    for cell, lim in LIMITS.items():
        lim = dict(lim, **(limits or {}).get(cell, {}))
        with open(os.path.join(bench, "limits", cell + ".json"), "w") as f:
            json.dump({k: {"limit": v} for k, v in lim.items()}, f)
    with open(os.path.join(bench, "traffic", "batch_infer.json")) as f:
        infer = json.load(f)
    infer.update(batch=8, pool=2, keep_every=2)
    with open(os.path.join(bench, "traffic", "batch_infer.json"), "w") as f:
        json.dump(infer, f)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


_CACHE_KEYS = ("jax_compilation_cache_dir",
               "jax_persistent_cache_min_compile_time_secs",
               "jax_persistent_cache_min_entry_size_bytes")


def restore_compile_cache():
    """Generator for a fixture: a run points JAX's persistent cache at its
    checkout; the settings are put back afterwards, so other tests in the
    same process see none of it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    old = {k: getattr(jax.config, k) for k in _CACHE_KEYS}
    yield
    for k, v in old.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
