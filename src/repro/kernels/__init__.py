"""Pallas TPU kernels for the Soft-MoE hot path (dispatch/combine, fused
forward AND flash-style backward, with pure-jnp oracles in ref.py — see
soft_moe_kernels.py), for paged decode attention over the serving
block pool (paged_attention_kernels.py) and for full-sequence encoder
attention (attention_kernels.py); tuning.py holds block-size /
interpret policy for all of them."""
from . import ops, ref, tuning  # noqa: F401
from .paged_attention_kernels import paged_decode_attend  # noqa: F401
from .soft_moe_kernels import (  # noqa: F401
    combine_apply_pallas,
    combine_bwd_pallas,
    combine_online_pallas,
    combine_pallas,
    dispatch_bwd_pallas,
    dispatch_pallas,
    routing_fwd_pallas,
    routing_health_pallas,
)
from .tuning import (  # noqa: F401
    KernelConfig,
    autotune,
    config_from_moe,
    default_config,
    paged_config,
)
