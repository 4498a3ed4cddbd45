"""Production mesh construction.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; the `pod` axis carries
only data parallelism (gradient all-reduce), matching the slower inter-pod
links.

Every axis is ``AxisType.Auto``: the models place activations with
``with_sharding_constraint`` (``distributed/api.py``) and leave the rest to
GSPMD, which JAX allows only on Auto axes (``jax.make_mesh`` defaults to
Explicit ones).

Defined as functions (never module-level constants) so importing this
module never touches jax device state.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Tuple[int, ...], axes: Tuple[str, ...]):
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: Optional[int] = None):
    """Smallest valid mesh on whatever devices exist (tests / examples)."""
    n = jax.device_count()
    model = model or 1
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))
