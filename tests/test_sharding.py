"""Sharding rules: expected specs per param family, divisibility fallback,
logical-axis resolution, and (in a subprocess with 8 fake devices) the
compressed cross-pod gradient reduce."""
import os
import subprocess
import sys

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.distributed import ShardingOptions, param_specs
from repro.models import lm_init


def _specs_for(name, opts=None):
    cfg = reduced(get_config(name))
    params = jax.eval_shape(lambda r: lm_init(r, cfg), jax.random.PRNGKey(0))
    # fsdp_min_size=0: reduced-config params are tiny; tests assert the
    # rule structure, not the size heuristic
    opts = opts or ShardingOptions(fsdp_min_size=0)
    return param_specs(params, opts), cfg


def _find(specs, *path):
    node = specs
    for p in path:
        node = node[p]
    return node


def test_attention_and_mlp_rules():
    specs, _ = _specs_for("llama3-8b")
    seg0 = specs["segments"][0]
    # stacked layer axis never sharded; heads over model; FSDP over data
    assert _find(seg0, "attn", "wq") == (None, "data", "model", None)
    assert _find(seg0, "attn", "wo") == (None, "model", None, "data")
    assert _find(seg0, "mlp", "w_gate") == (None, "data", "model")
    assert _find(seg0, "mlp", "w_down") == (None, "model", "data")
    assert _find(specs, "embed", "table") == ("model", "data")
    assert _find(seg0, "norm1", "scale") == (None, None)


def test_expert_parallel_rules():
    specs, cfg = _specs_for("granite-moe-1b-a400m")
    moe_seg = specs["segments"][1]  # reduced() moves MoE to second half
    assert _find(moe_seg, "moe", "experts", "w_gate") == (
        None, "model", "data", None
    )
    assert _find(moe_seg, "moe", "router") == (None, "data", None)


def test_soft_moe_phi_rule():
    specs, _ = _specs_for("llama3-8b+soft")
    moe_seg = specs["segments"][1]  # second_half segment
    assert _find(moe_seg, "moe", "phi") == (None, "data", "model", None)
    assert _find(moe_seg, "moe", "scale") == (None,)


def test_fsdp_off():
    specs, _ = _specs_for(
        "llama3-8b", ShardingOptions(fsdp=False, fsdp_min_size=0)
    )
    seg0 = specs["segments"][0]
    assert _find(seg0, "mlp", "w_gate") == (None, None, "model")


def test_tp_off():
    specs, _ = _specs_for(
        "llama3-8b", ShardingOptions(tensor_parallel=False,
                                     expert_parallel=False,
                                     fsdp_min_size=0)
    )
    seg0 = specs["segments"][0]
    assert _find(seg0, "mlp", "w_gate") == (None, "data", None)


def test_divisibility_fallback():
    """hymba has 25 heads — not divisible by a 16-wide model axis; the
    sharding must fall back to replicated on that axis, not crash."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro.configs import get_config
from repro.distributed.sharding import tree_shardings, ShardingOptions
from repro.models import lm_init
cfg = get_config("hymba-1.5b")
params = jax.eval_shape(lambda r: lm_init(r, cfg), jax.random.PRNGKey(0))
mesh = jax.make_mesh((2, 4), ("data", "model"))
sh = tree_shardings(mesh, params, ShardingOptions())
wq = sh["segments"][0]["attn"]["wq"]
# 25 heads % 4 != 0 -> replicated on model axis
assert "model" not in str(wq.spec), wq.spec
print("OK")
"""
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=".",
    )
    assert "OK" in r.stdout, r.stderr[-2000:]


def test_compressed_pod_reduce_subprocess():
    """int8+EF pod all-reduce == exact mean within quantization error
    (8 fake devices: 2 pods x 2 data x 2 model)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.optim import ef_state_init, pod_allreduce_compressed, pod_allreduce_mean

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
g = {"w": jax.random.normal(jax.random.PRNGKey(0), (8, 16))}
# different gradient per pod: shard over pod so each pod holds its own
specs = {"w": P("pod", None)}
gs = {"w": jax.device_put(g["w"], NamedSharding(mesh, P("pod", None)))}
err = ef_state_init(gs)
exact = pod_allreduce_mean(gs, mesh, specs)
approx, new_err = pod_allreduce_compressed(gs, err, mesh, specs)
d = float(jnp.abs(exact["w"] - approx["w"]).max())
scale = float(jnp.abs(gs["w"]).max())
assert d < 0.02 * scale + 1e-6, (d, scale)
# error feedback state is nonzero (residual carried)
assert float(jnp.abs(new_err["w"]).max()) > 0
print("OK")
"""
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=".",
    )
    assert "OK" in r.stdout, (r.stdout, r.stderr[-3000:])

