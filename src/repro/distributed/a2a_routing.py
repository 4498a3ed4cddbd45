"""All-to-all expert-parallel token routing (the §Perf C5 design).

The scatter/gather tokens-choice implementation is GSPMD-hostile under
expert parallelism: the compiler falls back to all-gathering routed
buffers (~791GB/step measured at deepseek-v2-lite:train_4k — EXPERIMENTS
§Perf C0). The production pattern is explicit: each device holds a token
shard, decides expert assignments locally, and exchanges exactly the
routed token payload with the expert-owner devices via all_to_all —
per device ≈ tokens·top_k·d bytes each way per layer, ~100× less.

This module implements that exchange as a shard_map collective with a
fixed per-destination capacity (XLA needs static shapes; overflow tokens
drop exactly like capacity-constrained tokens-choice):

  1. per-device: bucket local tokens by destination device
     (expert_id // experts_per_device) into (devices, cap, d) send
     buffers;
  2. one jax.lax.all_to_all exchanges buffers;
  3. each device applies its LOCAL experts to everything it received;
  4. a second all_to_all returns outputs; combine with gate weights.

Validated on 8 fake devices in tests/test_a2a_routing.py against the
single-device reference. Integration into the pjit train step (partial-
manual shard_map over `model` inside the MoE layer) is the recorded
next step in EXPERIMENTS §Perf.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _bucket_by_device(x, expert_idx, gate, num_devices: int,
                      experts_per_device: int, cap: int):
    """x: (t, d) local tokens; expert_idx/gate: (t, k). Returns send
    buffers (devices, cap, d), their (local) expert slots (devices, cap),
    origin token ids (devices, cap) and validity mask."""
    t, d = x.shape
    k = expert_idx.shape[1]
    dest = expert_idx // experts_per_device  # (t, k) device id
    local_e = expert_idx % experts_per_device
    flat_dest = dest.reshape(-1)
    # position of each (token,choice) within its destination bucket
    onehot = jax.nn.one_hot(flat_dest, num_devices, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos = (pos * onehot).sum(-1)  # (t*k,)
    keep = pos < cap
    pos_c = jnp.clip(pos, 0, cap - 1)

    send = jnp.zeros((num_devices, cap, d), x.dtype)
    send = send.at[flat_dest, pos_c].add(
        jnp.where(keep[:, None], jnp.repeat(x, k, axis=0), 0)
    )
    send_e = jnp.zeros((num_devices, cap), jnp.int32)
    send_e = send_e.at[flat_dest, pos_c].max(
        jnp.where(keep, local_e.reshape(-1), 0)
    )
    valid = jnp.zeros((num_devices, cap), bool)
    valid = valid.at[flat_dest, pos_c].max(keep)
    return send, send_e, valid, (flat_dest, pos_c, keep)


def a2a_route_and_compute(x, router_w, expert_fn, *, axis_name: str,
                          num_experts: int, top_k: int,
                          capacity_factor: float = 2.0):
    """Runs inside shard_map: x (t_local, d) token shard; router_w (d, E)
    replicated; expert_fn(local_expert_id, tokens) applies THIS device's
    expert. Returns (t_local, d) combined outputs."""
    nd = jax.lax.axis_size(axis_name)
    epd = num_experts // nd
    t, d = x.shape
    cap = max(int(capacity_factor * top_k * t / nd), 1)

    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    gate, expert_idx = jax.lax.top_k(probs, top_k)

    send, send_e, valid, (flat_dest, pos_c, keep) = _bucket_by_device(
        x, expert_idx, gate, nd, epd, cap
    )
    # exchange: (devices, cap, d) -> received (devices, cap, d), where
    # axis 0 now indexes the SOURCE device.
    recv = jax.lax.all_to_all(send, axis_name, 0, 0, tiled=False)
    recv_e = jax.lax.all_to_all(send_e, axis_name, 0, 0, tiled=False)
    recv_v = jax.lax.all_to_all(valid, axis_name, 0, 0, tiled=False)

    # apply local experts: mask per local expert id
    out = jnp.zeros_like(recv, dtype=x.dtype)
    flat = recv.reshape(nd * cap, d)
    fe = recv_e.reshape(-1)
    fv = recv_v.reshape(-1)
    acc = jnp.zeros_like(flat)
    for le in range(epd):
        sel = (fe == le) & fv
        y = expert_fn(le, flat)
        acc = acc + jnp.where(sel[:, None], y, 0)
    out = acc.reshape(nd, cap, d)

    # return trip
    back = jax.lax.all_to_all(out, axis_name, 0, 0, tiled=False)
    # combine: gather each (token, choice)'s output and weight by gate
    flat_out = back[flat_dest, pos_c]  # (t*k, d)
    flat_out = jnp.where(keep[:, None], flat_out, 0)
    gate_n = gate / jnp.clip(gate.sum(-1, keepdims=True), 1e-9)
    y = (flat_out.reshape(t, top_k, d)
         * gate_n[..., None].astype(flat_out.dtype)).sum(1)
    return y.astype(x.dtype)


def make_a2a_moe(mesh, num_experts: int, top_k: int, d_model: int,
                 capacity_factor: float = 2.0, axis_name: str = "model"):
    """shard_map-wrapped MoE layer: tokens sharded over `axis_name`,
    experts owned by device (expert weights pre-sharded outside)."""

    def fn(x, router_w, expert_gate, expert_up, expert_down):
        # expert_* carry only THIS device's experts: (epd, d, ff) etc.
        def expert_fn(le, toks):
            g = jax.nn.silu(toks @ expert_gate[le].astype(toks.dtype))
            u = toks @ expert_up[le].astype(toks.dtype)
            return (g * u) @ expert_down[le].astype(toks.dtype)

        return a2a_route_and_compute(
            x, router_w, expert_fn, axis_name=axis_name,
            num_experts=num_experts, top_k=top_k,
            capacity_factor=capacity_factor,
        )

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(
            P(axis_name, None),  # tokens sharded
            P(),  # router replicated
            P(axis_name, None, None),  # experts sharded over devices
            P(axis_name, None, None),
            P(axis_name, None, None),
        ),
        out_specs=P(axis_name, None),
    )
