"""Fused Soft-MoE routing Pallas TPU kernels — forward AND backward.

Why a kernel: the jnp path materializes the (m × S) logits in HBM *twice*
(once per softmax direction) plus the two weight tensors — at B/16 scale
(m=4096 tokens, S=4096 slots) that is 4 × 64MB of HBM traffic per layer
per sequence that never needs to exist. Every kernel below streams over
the contraction dimension and keeps only (block × d) tiles resident in
VMEM; the (m × S) logits/weights exist only tile-wise, never in HBM.

Forward (single-pass shared logits):

  * ``routing_fwd``: ONE logits pass produces the dispatch output and both
    softmax directions' statistics. For each slot block it streams token
    blocks, runs the online softmax over TOKENS (the D direction) while
    accumulating the slot mix X~ = DᵀX, and folds the same logits tile
    into running per-token (max, denom) over SLOTS (the C direction).
    The seed kernels computed the identical ``l2norm(X) @ Phi_n`` product
    twice (once in dispatch, once in combine) just to derive each
    direction's statistics — the statistics matmul work is halved.
  * ``combine_apply``: consumes the saved per-token (max, denom), so it
    re-materializes exp-logit tiles with **no online rescaling** and
    weights the expert outputs: Y = C Ys.
  * ``combine_online``: standalone combine (no precomputed stats) that
    additionally EMITS its final (max, denom) — the backward residuals.

Backward (flash-style, the dq/dkv split of flash attention): logits tiles
are recomputed from the saved online-softmax ``(max, denom)`` residuals —
O(m + S) floats per direction instead of the (m × S) softmax re-derivation
``jax.vjp``-of-ref would do. Softmax VJP per direction:

  dispatch  dL = D ⊙ (dD − σ),  σ_s = g_s · X~_s        (rowdot of grads
  combine   dL = C ⊙ (dC − ρ),  ρ_i = g_i · Y_i          and fwd outputs)

  * ``dispatch_bwd_dx`` / ``combine_bwd_dx``: token-block major, slot
    blocks inner; accumulate dX (plus the raw D·g term and the l2-norm
    chain applied once at the end of the row of blocks).
  * ``dispatch_bwd_dphi`` / ``combine_bwd_dys_dphi``: slot-block major
    OUTERMOST with (batch, token) inner so the dPhi tile accumulates over
    batch AND tokens in consecutive grid steps (one VMEM-resident
    accumulator, one HBM write per slot block).

Batching: one kernel launch covers (b, m, d) via a leading batch grid
axis (no ``jax.vmap`` over ``pallas_call``); the phi tile's index map
ignores the batch axis, so phi blocks are fetched once and reused across
the batch. The batch grid axis is PURELY parallel — every online-softmax
accumulator (dispatch per-slot and combine per-token (max, denom)) is
indexed by b and reduces only over that row's tokens/slots, so each
sequence's routing is computed exactly as if it were served alone. This
is the per-sequence normalization invariant batch-invariant serving
leans on (ref.py single-sequence oracle; row-independence tests in
tests/test_kernels.py).

Tiling: d stays whole inside a block (the dot needs full rows); token and
slot block sizes come from ``tuning.KernelConfig`` (defaults 128 — minor
dims multiples of 128 for MXU alignment). See ``kernels/README.md`` for
the VMEM budget per kernel, the residual layout, and a block-size table.

Phi arrives pre-normalized (scale * l2norm(phi) is O(d·S), done once
outside); X is l2-normalized inside the kernel (it is re-read every pass —
normalizing outside would double-read X from HBM).

Softmax stats travel as lane-dense ``(b, 1, n)`` rows (``_stat_spec``)
and the full-length per-token scratch as one ``(1, bt)`` row per token
block: Mosaic refuses a ``(1, blk)`` block of a ``(b, n)`` array and a
1-D scratch written at an offset it cannot prove tile-aligned.

On the CPU the tests run these kernels in interpret mode against ref.py
(forward allclose, gradients allclose to the ref VJP), which applies none
of Mosaic's tiling rules; tests/test_tpu_compile.py compiles them for a
described v5e, and chip_smoke.py runs them on one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .tuning import KernelConfig

_NEG = -1e30
_EPS = 1e-6  # must match ref.l2_normalize


def _l2n(x, eps=_EPS):
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=1, keepdims=True))
    return x * (1.0 / (norm + eps))


def _dot(a, b, dims, dt):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=dt)


def _logits_tile(x_ref, phi_ref, dt):
    """(x block, phi block) -> (raw x, l2norm x, logits) tiles in acc dtype."""
    x = x_ref[0].astype(dt)  # (bt, d)
    xn = _l2n(x)
    phi = phi_ref[...].astype(dt)  # (d, bs)
    logits = _dot(xn, phi, ((1,), (0,)), dt)  # (bt, bs)
    return x, xn, logits


def _l2n_bwd(x, dxn, dt):
    """VJP of _l2n at raw-token tile x: dX given d(l2norm X)."""
    n = jnp.sqrt(jnp.sum(jnp.square(x), axis=1))  # (bt,)
    r = 1.0 / (n + _EPS)
    inv_n = jnp.where(n > 0, 1.0 / jnp.maximum(n, _EPS), jnp.zeros_like(n))
    proj = jnp.sum(x * dxn, axis=1)  # (bt,)
    return r[:, None] * dxn - (r * r * inv_n * proj)[:, None] * x


def _vec(ref):
    """A stat block ``(1, 1, n)`` or row scratch ``(1, n)`` as an (n,)
    vector."""
    return ref[...].reshape(-1)


def _put(ref, v):
    ref[...] = v.reshape(ref.shape).astype(ref.dtype)


def _pad_to(x, size, axis, value=0.0):
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _stat_in(v, size, dt, value=0.0):
    """(b, n) softmax stats -> padded (b, 1, size) kernel operand."""
    return _pad_to(v.astype(dt), size, axis=1, value=value)[:, None, :]


def _stat_spec(blk, index_map):
    """Stat operands are (b, 1, n) lane-dense rows; a block is (1, 1, blk).
    Mosaic tiles the last two block dims by (8, 128): a (1, blk) block of a
    (b, n) array is refused unless b == 1, and (1, blk) of a (b, 1, n) one
    is accepted for blk % 128 == 0 or blk == n."""
    return pl.BlockSpec((1, 1, blk), index_map)


def _grid_sizes(m, s, cfg: KernelConfig):
    bt, bs = cfg.block_tokens, cfg.block_slots
    return bt, bs, pl.cdiv(m, bt) * bt, pl.cdiv(s, bs) * bs


# ---------------------------------------------------------------------------
# forward: single-pass routing (dispatch output + both directions' stats)
# ---------------------------------------------------------------------------


def _routing_fwd_kernel(x_ref, phi_ref, slots_ref, dmx_ref, dden_ref,
                        cmx_ref, cden_ref, acc, smx, sden, cmx_all, cden_all,
                        *, m_valid, s_valid, bt, bs, dt):
    js, jt = pl.program_id(1), pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(jt == 0)
    def _init_slot_block():
        acc[...] = jnp.zeros_like(acc)
        smx[...] = jnp.full_like(smx, _NEG)
        sden[...] = jnp.zeros_like(sden)

    cmx_tok, cden_tok = cmx_all.at[jt], cden_all.at[jt]

    @pl.when(js == 0)
    def _init_token_stats():
        cmx_tok[...] = jnp.full(cmx_tok.shape, _NEG, dt)
        cden_tok[...] = jnp.zeros(cden_tok.shape, dt)

    x, _xn, logits = _logits_tile(x_ref, phi_ref, dt)
    row = jt * bt + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
    col = js * bs + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    lg_d = jnp.where(row < m_valid, logits, _NEG)  # dispatch: mask pad tokens
    lg_c = jnp.where(col < s_valid, logits, _NEG)  # combine: mask pad slots

    # dispatch direction: online softmax over tokens (inner jt loop)
    m_old = _vec(smx)
    m_new = jnp.maximum(m_old, lg_d.max(axis=0))  # (bs,)
    corr = jnp.exp(m_old - m_new)
    p = jnp.exp(lg_d - m_new[None, :])  # (bt, bs)
    _put(sden, _vec(sden) * corr + p.sum(axis=0))
    # acc: (bs, d) += pᵀ @ x  (raw x — the paper mixes unnormalized tokens)
    acc[...] = acc[...] * corr[:, None] + _dot(p, x, ((0,), (0,)), dt)
    _put(smx, m_new)

    # combine direction: online (max, denom) over slots (outer js loop);
    # running values land in the full-length O(m) scratch (one (1, bt) row
    # per token block, indexed on its leading axis) and are written out
    # every visit (the (jb, jt) output block is revisited per js, so the
    # buffer cannot be trusted to persist — last write wins).
    cm_old = _vec(cmx_tok)
    cm_new = jnp.maximum(cm_old, lg_c.max(axis=1))  # (bt,)
    ccorr = jnp.exp(cm_old - cm_new)
    cden_new = _vec(cden_tok) * ccorr + jnp.exp(
        lg_c - cm_new[:, None]).sum(axis=1)
    _put(cmx_tok, cm_new)
    _put(cden_tok, cden_new)
    _put(cmx_ref, cm_new)
    _put(cden_ref, cden_new)

    @pl.when(jt == nt - 1)
    def _finish_slot_block():
        slots_ref[0] = (acc[...] / _vec(sden)[:, None]).astype(slots_ref.dtype)
        _put(dmx_ref, _vec(smx))
        _put(dden_ref, _vec(sden))


def routing_fwd_pallas(x, phi_n, cfg: KernelConfig = KernelConfig()):
    """x: (b, m, d); phi_n: (d, S) pre-normalized.

    Returns ``(slots, (d_mx, d_den), (c_mx, c_den))`` with slots (b, S, d),
    dispatch stats (b, S) and combine stats (b, m) — one logits pass.
    """
    b, m, d = x.shape
    s = phi_n.shape[1]
    bt, bs, m_pad, s_pad = _grid_sizes(m, s, cfg)
    dt = cfg.acc()
    x = _pad_to(x, m_pad, axis=1)
    phi_n = _pad_to(phi_n, s_pad, axis=1)
    grid = (b, s_pad // bs, m_pad // bt)
    out_shapes = (
        jax.ShapeDtypeStruct((b, s_pad, d), x.dtype),  # slots
        jax.ShapeDtypeStruct((b, 1, s_pad), dt),  # dispatch max
        jax.ShapeDtypeStruct((b, 1, s_pad), dt),  # dispatch denom
        jax.ShapeDtypeStruct((b, 1, m_pad), dt),  # combine max
        jax.ShapeDtypeStruct((b, 1, m_pad), dt),  # combine denom
    )
    sstat = _stat_spec(bs, lambda jb, js, jt: (jb, 0, js))
    tstat = _stat_spec(bt, lambda jb, js, jt: (jb, 0, jt))
    slots, dmx, dden, cmx, cden = pl.pallas_call(
        functools.partial(_routing_fwd_kernel, m_valid=m, s_valid=s,
                          bt=bt, bs=bs, dt=dt),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bt, d), lambda jb, js, jt: (jb, jt, 0)),
            pl.BlockSpec((d, bs), lambda jb, js, jt: (0, js)),
        ],
        out_specs=(
            pl.BlockSpec((1, bs, d), lambda jb, js, jt: (jb, js, 0)),
            sstat, sstat, tstat, tstat,
        ),
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((bs, d), dt),  # slot-mix accumulator
            pltpu.VMEM((1, bs), dt),  # dispatch running max
            pltpu.VMEM((1, bs), dt),  # dispatch running denom
            pltpu.VMEM((m_pad // bt, 1, bt), dt),  # combine running max
            pltpu.VMEM((m_pad // bt, 1, bt), dt),  # combine running denom
        ],
        name="softmoe_routing_fwd",
        interpret=cfg.resolve_interpret(),
    )(x, phi_n)
    return (slots[:, :s], (dmx[:, 0, :s], dden[:, 0, :s]),
            (cmx[:, 0, :m], cden[:, 0, :m]))


# ---------------------------------------------------------------------------
# routing health: Fig. 9 statistics from the saved softmax stats
# ---------------------------------------------------------------------------


def _routing_health_kernel(x_ref, phi_ref, dmx_ref, dden_ref, cmx_ref,
                           cden_ref, dent_ref, imp_ref, cent_ref,
                           contrib_ref, dent_acc, imp_acc, cent_all,
                           contrib_all, *, m_valid, s_valid, bt, bs, dt):
    js, jt = pl.program_id(1), pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(jt == 0)
    def _init_slot_block():
        dent_acc[...] = jnp.zeros_like(dent_acc)
        imp_acc[...] = jnp.zeros_like(imp_acc)

    cent_tok, contrib_tok = cent_all.at[jt], contrib_all.at[jt]

    @pl.when(js == 0)
    def _init_token_acc():
        cent_tok[...] = jnp.zeros(cent_tok.shape, dt)
        contrib_tok[...] = jnp.zeros(contrib_tok.shape, dt)

    _x, _xn, logits = _logits_tile(x_ref, phi_ref, dt)
    row = jt * bt + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
    col = js * bs + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    valid_col = col < s_valid
    lg_d = jnp.where(row < m_valid, logits, _NEG)
    lg_c = jnp.where(valid_col, logits, _NEG)

    # dispatch direction: exact weights from the saved per-slot (max, denom)
    # — log-weights come straight from the shifted logits, so the w·log w
    # entropy terms never take log(0) (masked entries: w = exp(-1e30) = 0
    # times a finite shifted logit). Pad-slot columns carry garbage weights
    # (their stats are (0, 1) padding); they are masked out of the
    # token-contribution row sums and sliced off the per-slot outputs.
    ln_d = lg_d - _vec(dmx_ref)[None, :] - jnp.log(_vec(dden_ref))[None, :]
    d_w = jnp.exp(ln_d)
    _put(dent_acc, _vec(dent_acc) - jnp.sum(d_w * ln_d, axis=0))
    contrib_new = _vec(contrib_tok) + jnp.sum(
        jnp.where(valid_col, d_w, 0.0), axis=1)
    _put(contrib_tok, contrib_new)
    _put(contrib_ref, contrib_new)

    # combine direction: per-token entropy (full-length scratch, written out
    # every visit — last write wins, same as the fwd kernel's stats) and
    # per-slot importance (pad-token rows masked out of the column sums).
    ln_c = lg_c - _vec(cmx_ref)[:, None] - jnp.log(_vec(cden_ref))[:, None]
    c_w = jnp.exp(ln_c)
    cent_new = _vec(cent_tok) - jnp.sum(c_w * ln_c, axis=1)
    _put(cent_tok, cent_new)
    _put(cent_ref, cent_new)
    _put(imp_acc, _vec(imp_acc) + jnp.sum(
        jnp.where(row < m_valid, c_w, 0.0), axis=0))

    @pl.when(jt == nt - 1)
    def _finish_slot_block():
        _put(dent_ref, _vec(dent_acc))
        _put(imp_ref, _vec(imp_acc))


def routing_health_pallas(x, phi_n, d_stats, c_stats,
                          cfg: KernelConfig = KernelConfig()):
    """Routing-health statistics for telemetry/inspection (paper Fig. 9).

    Recomputes logits tile-wise against the saved online-softmax
    ``(max, denom)`` residuals — the backward kernels' trick — and reduces
    them in one pass to O(m + S) outputs; the (m × S) weight tensors never
    exist in HBM:

    Returns ``(disp_entropy (b, S), importance (b, S), comb_entropy (b, m),
    token_contrib (b, m))`` — per-slot dispatch-softmax entropy over
    tokens, per-slot combine mass (column sums of C — normalizing by its
    min gives the expert importance spread), per-token combine-softmax
    entropy over slots, and per-token dispatch mass (row sums of D — the
    paper's token contribution; zero means a dropped token, which Soft MoE
    forbids by construction).
    """
    b, m, d = x.shape
    s = phi_n.shape[1]
    bt, bs, m_pad, s_pad = _grid_sizes(m, s, cfg)
    dt = cfg.acc()
    dmx, dden = d_stats
    cmx, cden = c_stats
    x = _pad_to(x, m_pad, axis=1)
    phi_n = _pad_to(phi_n, s_pad, axis=1)
    # (max=0, denom=1) stat padding keeps every padded tile finite; padded
    # rows/columns are masked out of all four reductions above.
    dmx = _stat_in(dmx, s_pad, dt)
    dden = _stat_in(dden, s_pad, dt, value=1.0)
    cmx = _stat_in(cmx, m_pad, dt)
    cden = _stat_in(cden, m_pad, dt, value=1.0)
    sstat = _stat_spec(bs, lambda jb, js, jt: (jb, 0, js))
    tstat = _stat_spec(bt, lambda jb, js, jt: (jb, 0, jt))
    dent, imp, cent, contrib = pl.pallas_call(
        functools.partial(_routing_health_kernel, m_valid=m, s_valid=s,
                          bt=bt, bs=bs, dt=dt),
        grid=(b, s_pad // bs, m_pad // bt),
        in_specs=[
            pl.BlockSpec((1, bt, d), lambda jb, js, jt: (jb, jt, 0)),
            pl.BlockSpec((d, bs), lambda jb, js, jt: (0, js)),
            sstat, sstat, tstat, tstat,
        ],
        out_specs=(sstat, sstat, tstat, tstat),
        out_shape=(
            jax.ShapeDtypeStruct((b, 1, s_pad), dt),
            jax.ShapeDtypeStruct((b, 1, s_pad), dt),
            jax.ShapeDtypeStruct((b, 1, m_pad), dt),
            jax.ShapeDtypeStruct((b, 1, m_pad), dt),
        ),
        scratch_shapes=[
            pltpu.VMEM((1, bs), dt),  # dispatch entropy accumulator
            pltpu.VMEM((1, bs), dt),  # combine importance accumulator
            pltpu.VMEM((m_pad // bt, 1, bt), dt),  # combine entropy
            pltpu.VMEM((m_pad // bt, 1, bt), dt),  # token contribution
        ],
        name="softmoe_routing_health",
        interpret=cfg.resolve_interpret(),
    )(x, phi_n, dmx, dden, cmx, cden)
    return (dent[:, 0, :s], imp[:, 0, :s], cent[:, 0, :m],
            contrib[:, 0, :m])


# ---------------------------------------------------------------------------
# forward: combine  y = C Ys   (stats-given and online variants)
# ---------------------------------------------------------------------------


def _combine_kernel(x_ref, phi_ref, ys_ref, cmx_ref, cden_ref, out_ref,
                    *rest, s_valid, bs, dt, online):
    if online:  # emits final stats instead of consuming them
        out_ref, cmx_out, cden_out = cmx_ref, cden_ref, out_ref
        acc, mx, den = rest
    else:
        (acc,) = rest
    js = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(js == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        if online:
            mx[...] = jnp.full_like(mx, _NEG)
            den[...] = jnp.zeros_like(den)

    _x, _xn, logits = _logits_tile(x_ref, phi_ref, dt)
    col = js * bs + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    lg_c = jnp.where(col < s_valid, logits, _NEG)
    ys = ys_ref[0].astype(dt)  # (bs, d)

    if online:
        m_old = _vec(mx)
        m_new = jnp.maximum(m_old, lg_c.max(axis=1))  # (bt,)
        corr = jnp.exp(m_old - m_new)
        p = jnp.exp(lg_c - m_new[:, None])
        _put(den, _vec(den) * corr + p.sum(axis=1))
        acc[...] = acc[...] * corr[:, None] + _dot(p, ys, ((1,), (0,)), dt)
        _put(mx, m_new)
    else:
        # exact final (max, denom) saved by routing_fwd: p ≤ 1, no rescale
        p = jnp.exp(lg_c - _vec(cmx_ref)[:, None])
        acc[...] = acc[...] + _dot(p, ys, ((1,), (0,)), dt)

    @pl.when(js == ns - 1)
    def _finish():
        d_final = _vec(den) if online else _vec(cden_ref).astype(dt)
        out_ref[0] = (acc[...] / d_final[:, None]).astype(out_ref.dtype)
        if online:
            _put(cmx_out, _vec(mx))
            _put(cden_out, _vec(den))


def _combine_call(x, phi_n, ys, c_stats, cfg: KernelConfig):
    b, m, d = x.shape
    s = phi_n.shape[1]
    bt, bs, m_pad, s_pad = _grid_sizes(m, s, cfg)
    dt = cfg.acc()
    online = c_stats is None
    x = _pad_to(x, m_pad, axis=1)
    phi_n = _pad_to(phi_n, s_pad, axis=1)
    ys = _pad_to(ys, s_pad, axis=1)
    grid = (b, m_pad // bt, s_pad // bs)
    in_specs = [
        pl.BlockSpec((1, bt, d), lambda jb, jt, js: (jb, jt, 0)),
        pl.BlockSpec((d, bs), lambda jb, jt, js: (0, js)),
        pl.BlockSpec((1, bs, d), lambda jb, jt, js: (jb, js, 0)),
    ]
    stat_spec = _stat_spec(bt, lambda jb, jt, js: (jb, 0, jt))
    y_spec = pl.BlockSpec((1, bt, d), lambda jb, jt, js: (jb, jt, 0))
    y_shape = jax.ShapeDtypeStruct((b, m_pad, d), x.dtype)
    if online:
        out = pl.pallas_call(
            functools.partial(_combine_kernel, s_valid=s, bs=bs, dt=dt,
                              online=True),
            grid=grid,
            in_specs=in_specs,
            out_specs=(y_spec, stat_spec, stat_spec),
            out_shape=(y_shape,
                       jax.ShapeDtypeStruct((b, 1, m_pad), dt),
                       jax.ShapeDtypeStruct((b, 1, m_pad), dt)),
            scratch_shapes=[
                pltpu.VMEM((bt, d), dt),
                pltpu.VMEM((1, bt), dt),
                pltpu.VMEM((1, bt), dt),
            ],
            name="softmoe_combine_online",
            interpret=cfg.resolve_interpret(),
        )(x, phi_n, ys)
        y, cmx, cden = out
        return y[:, :m], (cmx[:, 0, :m], cden[:, 0, :m])
    cmx, cden = c_stats
    cmx = _stat_in(cmx, m_pad, dt)
    cden = _stat_in(cden, m_pad, dt, value=1.0)
    y = pl.pallas_call(
        functools.partial(_combine_kernel, s_valid=s, bs=bs, dt=dt,
                          online=False),
        grid=grid,
        in_specs=in_specs + [stat_spec, stat_spec],
        out_specs=y_spec,
        out_shape=y_shape,
        scratch_shapes=[pltpu.VMEM((bt, d), dt)],
        name="softmoe_combine_fwd",
        interpret=cfg.resolve_interpret(),
    )(x, phi_n, ys, cmx, cden)
    return y[:, :m], c_stats


def combine_apply_pallas(x, phi_n, ys, c_stats,
                         cfg: KernelConfig = KernelConfig()):
    """Combine with precomputed per-token stats from routing_fwd."""
    return _combine_call(x, phi_n, ys, c_stats, cfg)[0]


def combine_online_pallas(x, phi_n, ys, cfg: KernelConfig = KernelConfig()):
    """Standalone combine; returns (y, (c_mx, c_den)) — stats are the
    backward residuals."""
    return _combine_call(x, phi_n, ys, None, cfg)


# ---------------------------------------------------------------------------
# backward: dispatch  (dX token-major; dPhi slot-major)
# ---------------------------------------------------------------------------


def _dispatch_bwd_tile(x_ref, phi_ref, g_ref, dmx_ref, dden_ref, sig_ref,
                       *, jt, m_valid, bt, dt):
    """Shared tile math: recompute D from residual stats, softmax-VJP."""
    x, xn, logits = _logits_tile(x_ref, phi_ref, dt)
    row = jt * bt + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 0)
    lg_d = jnp.where(row < m_valid, logits, _NEG)
    d_w = jnp.exp(lg_d - _vec(dmx_ref)[None, :]) / _vec(dden_ref)[None, :]
    g = g_ref[0].astype(dt)  # (bs, d)
    d_dw = _dot(x, g, ((1,), (1,)), dt)  # (bt, bs) = x · g_s
    d_lg = d_w * (d_dw - _vec(sig_ref)[None, :])
    return x, xn, d_w, d_lg, g


def _dispatch_bwd_dx_kernel(x_ref, phi_ref, g_ref, dmx_ref, dden_ref,
                            sig_ref, dx_ref, acc_raw, acc_n,
                            *, m_valid, bt, dt):
    jt, js = pl.program_id(1), pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(js == 0)
    def _init():
        acc_raw[...] = jnp.zeros_like(acc_raw)
        acc_n[...] = jnp.zeros_like(acc_n)

    x, _xn, d_w, d_lg, g = _dispatch_bwd_tile(
        x_ref, phi_ref, g_ref, dmx_ref, dden_ref, sig_ref,
        jt=jt, m_valid=m_valid, bt=bt, dt=dt)
    acc_raw[...] = acc_raw[...] + _dot(d_w, g, ((1,), (0,)), dt)  # D @ g
    phi = phi_ref[...].astype(dt)
    acc_n[...] = acc_n[...] + _dot(d_lg, phi, ((1,), (1,)), dt)  # dL @ phiᵀ

    @pl.when(js == ns - 1)
    def _finish():
        dx = acc_raw[...] + _l2n_bwd(x, acc_n[...], dt)
        dx_ref[0] = dx.astype(dx_ref.dtype)


def _dispatch_bwd_dphi_kernel(x_ref, phi_ref, g_ref, dmx_ref, dden_ref,
                              sig_ref, dphi_ref, acc_p, *, m_valid, bt, dt):
    jb, jt = pl.program_id(1), pl.program_id(2)
    nb, nt = pl.num_programs(1), pl.num_programs(2)

    @pl.when((jb == 0) & (jt == 0))
    def _init():
        acc_p[...] = jnp.zeros_like(acc_p)

    _x, xn, _d_w, d_lg, _g = _dispatch_bwd_tile(
        x_ref, phi_ref, g_ref, dmx_ref, dden_ref, sig_ref,
        jt=jt, m_valid=m_valid, bt=bt, dt=dt)
    acc_p[...] = acc_p[...] + _dot(xn, d_lg, ((0,), (0,)), dt)  # xnᵀ @ dL

    @pl.when((jb == nb - 1) & (jt == nt - 1))
    def _finish():
        dphi_ref[...] = acc_p[...].astype(dphi_ref.dtype)


def dispatch_bwd_pallas(x, phi_n, g_slots, d_stats, slots,
                        cfg: KernelConfig = KernelConfig()):
    """Flash backward of routing/dispatch. Returns (dx, dphi_n).

    x: (b, m, d); phi_n: (d, S); g_slots/slots: (b, S, d);
    d_stats: per-slot (max, denom), each (b, S).
    """
    b, m, d = x.shape
    s = phi_n.shape[1]
    bt, bs, m_pad, s_pad = _grid_sizes(m, s, cfg)
    dt = cfg.acc()
    dmx, dden = d_stats
    # σ_s = g_s · X~_s — the dispatch softmax-VJP row term, O(S·d) outside
    # the kernel (never (m × S)).
    sigma = jnp.sum(g_slots.astype(dt) * slots.astype(dt), axis=-1)  # (b, S)
    x_p = _pad_to(x, m_pad, axis=1)
    phi_p = _pad_to(phi_n, s_pad, axis=1)
    g_p = _pad_to(g_slots, s_pad, axis=1)
    # pad stats with (max=0, denom=1): padded-column D tiles stay finite and
    # are multiplied only by zero-padded g/σ, so they never contribute.
    dmx_p = _stat_in(dmx, s_pad, dt)
    dden_p = _stat_in(dden, s_pad, dt, value=1.0)
    sig_p = _stat_in(sigma, s_pad, dt)
    args = (x_p, phi_p, g_p, dmx_p, dden_p, sig_p)

    x_spec_t = pl.BlockSpec((1, bt, d), lambda jb, jt, js: (jb, jt, 0))
    sstat_t = _stat_spec(bs, lambda jb, jt, js: (jb, 0, js))
    dx = pl.pallas_call(
        functools.partial(_dispatch_bwd_dx_kernel, m_valid=m, bt=bt, dt=dt),
        grid=(b, m_pad // bt, s_pad // bs),
        in_specs=[
            x_spec_t,
            pl.BlockSpec((d, bs), lambda jb, jt, js: (0, js)),
            pl.BlockSpec((1, bs, d), lambda jb, jt, js: (jb, js, 0)),
            sstat_t, sstat_t, sstat_t,
        ],
        out_specs=x_spec_t,
        out_shape=jax.ShapeDtypeStruct((b, m_pad, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, d), dt), pltpu.VMEM((bt, d), dt)],
        name="softmoe_dispatch_bwd_dx",
        interpret=cfg.resolve_interpret(),
    )(*args)

    sstat_s = _stat_spec(bs, lambda js, jb, jt: (jb, 0, js))
    dphi = pl.pallas_call(
        functools.partial(_dispatch_bwd_dphi_kernel, m_valid=m, bt=bt, dt=dt),
        grid=(s_pad // bs, b, m_pad // bt),
        in_specs=[
            pl.BlockSpec((1, bt, d), lambda js, jb, jt: (jb, jt, 0)),
            pl.BlockSpec((d, bs), lambda js, jb, jt: (0, js)),
            pl.BlockSpec((1, bs, d), lambda js, jb, jt: (jb, js, 0)),
            sstat_s, sstat_s, sstat_s,
        ],
        out_specs=pl.BlockSpec((d, bs), lambda js, jb, jt: (0, js)),
        out_shape=jax.ShapeDtypeStruct((d, s_pad), phi_n.dtype),
        scratch_shapes=[pltpu.VMEM((d, bs), dt)],
        name="softmoe_dispatch_bwd_dphi",
        interpret=cfg.resolve_interpret(),
    )(*args)
    return dx[:, :m], dphi[:, :s]


# ---------------------------------------------------------------------------
# backward: combine  (dX token-major; dYs + dPhi slot-major)
# ---------------------------------------------------------------------------


def _combine_bwd_tile(x_ref, phi_ref, ys_ref, g_ref, cmx_ref, cden_ref,
                      rho_ref, *, js, s_valid, bs, dt):
    """Shared tile math: recompute C from residual stats, softmax-VJP."""
    _x, xn, logits = _logits_tile(x_ref, phi_ref, dt)
    col = js * bs + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    lg_c = jnp.where(col < s_valid, logits, _NEG)
    c_w = jnp.exp(lg_c - _vec(cmx_ref)[:, None]) / _vec(cden_ref)[:, None]
    g = g_ref[0].astype(dt)  # (bt, d)
    ys = ys_ref[0].astype(dt)  # (bs, d)
    d_cw = _dot(g, ys, ((1,), (1,)), dt)  # (bt, bs) = g_i · ys_s
    d_lg = c_w * (d_cw - _vec(rho_ref)[:, None])
    return xn, c_w, d_lg, g


def _combine_bwd_dx_kernel(x_ref, phi_ref, ys_ref, g_ref, cmx_ref, cden_ref,
                           rho_ref, dx_ref, acc_n, *, s_valid, bs, dt):
    js = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(js == 0)
    def _init():
        acc_n[...] = jnp.zeros_like(acc_n)

    _xn, _c_w, d_lg, _g = _combine_bwd_tile(
        x_ref, phi_ref, ys_ref, g_ref, cmx_ref, cden_ref, rho_ref,
        js=js, s_valid=s_valid, bs=bs, dt=dt)
    phi = phi_ref[...].astype(dt)
    acc_n[...] = acc_n[...] + _dot(d_lg, phi, ((1,), (1,)), dt)

    @pl.when(js == ns - 1)
    def _finish():
        x = x_ref[0].astype(dt)
        dx_ref[0] = _l2n_bwd(x, acc_n[...], dt).astype(dx_ref.dtype)


def _combine_bwd_dys_dphi_kernel(x_ref, phi_ref, ys_ref, g_ref, cmx_ref,
                                 cden_ref, rho_ref, dys_ref, dphi_ref,
                                 acc_y, acc_p, *, s_valid, bs, dt):
    js, jb, jt = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nb, nt = pl.num_programs(1), pl.num_programs(2)

    @pl.when(jt == 0)
    def _init_dys():
        acc_y[...] = jnp.zeros_like(acc_y)

    @pl.when((jb == 0) & (jt == 0))
    def _init_dphi():
        acc_p[...] = jnp.zeros_like(acc_p)

    xn, c_w, d_lg, g = _combine_bwd_tile(
        x_ref, phi_ref, ys_ref, g_ref, cmx_ref, cden_ref, rho_ref,
        js=js, s_valid=s_valid, bs=bs, dt=dt)
    acc_y[...] = acc_y[...] + _dot(c_w, g, ((0,), (0,)), dt)  # Cᵀ @ g
    acc_p[...] = acc_p[...] + _dot(xn, d_lg, ((0,), (0,)), dt)  # xnᵀ @ dL

    @pl.when(jt == nt - 1)
    def _finish_dys():
        dys_ref[0] = acc_y[...].astype(dys_ref.dtype)

    @pl.when((jb == nb - 1) & (jt == nt - 1))
    def _finish_dphi():
        dphi_ref[...] = acc_p[...].astype(dphi_ref.dtype)


def combine_bwd_pallas(x, phi_n, ys, g, c_stats, y,
                       cfg: KernelConfig = KernelConfig()):
    """Flash backward of combine. Returns (dx, dphi_n, dys).

    x/g/y: (b, m, d); phi_n: (d, S); ys: (b, S, d);
    c_stats: per-token (max, denom), each (b, m).
    """
    b, m, d = x.shape
    s = phi_n.shape[1]
    bt, bs, m_pad, s_pad = _grid_sizes(m, s, cfg)
    dt = cfg.acc()
    cmx, cden = c_stats
    # ρ_i = g_i · Y_i — the combine softmax-VJP row term, O(m·d) outside.
    rho = jnp.sum(g.astype(dt) * y.astype(dt), axis=-1)  # (b, m)
    x_p = _pad_to(x, m_pad, axis=1)
    phi_p = _pad_to(phi_n, s_pad, axis=1)
    ys_p = _pad_to(ys, s_pad, axis=1)
    g_p = _pad_to(g, m_pad, axis=1)
    # (max=0, denom=1) padding keeps padded-row C tiles finite; they meet
    # only zero-padded g/ρ rows, so dL and every accumulator stay exact.
    cmx_p = _stat_in(cmx, m_pad, dt)
    cden_p = _stat_in(cden, m_pad, dt, value=1.0)
    rho_p = _stat_in(rho, m_pad, dt)
    args = (x_p, phi_p, ys_p, g_p, cmx_p, cden_p, rho_p)

    x_spec_t = pl.BlockSpec((1, bt, d), lambda jb, jt, js: (jb, jt, 0))
    tstat_t = _stat_spec(bt, lambda jb, jt, js: (jb, 0, jt))
    dx = pl.pallas_call(
        functools.partial(_combine_bwd_dx_kernel, s_valid=s, bs=bs, dt=dt),
        grid=(b, m_pad // bt, s_pad // bs),
        in_specs=[
            x_spec_t,
            pl.BlockSpec((d, bs), lambda jb, jt, js: (0, js)),
            pl.BlockSpec((1, bs, d), lambda jb, jt, js: (jb, js, 0)),
            x_spec_t, tstat_t, tstat_t, tstat_t,
        ],
        out_specs=x_spec_t,
        out_shape=jax.ShapeDtypeStruct((b, m_pad, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bt, d), dt)],
        name="softmoe_combine_bwd_dx",
        interpret=cfg.resolve_interpret(),
    )(*args)

    x_spec_s = pl.BlockSpec((1, bt, d), lambda js, jb, jt: (jb, jt, 0))
    tstat_s = _stat_spec(bt, lambda js, jb, jt: (jb, 0, jt))
    dys, dphi = pl.pallas_call(
        functools.partial(_combine_bwd_dys_dphi_kernel, s_valid=s, bs=bs,
                          dt=dt),
        grid=(s_pad // bs, b, m_pad // bt),
        in_specs=[
            x_spec_s,
            pl.BlockSpec((d, bs), lambda js, jb, jt: (0, js)),
            pl.BlockSpec((1, bs, d), lambda js, jb, jt: (jb, js, 0)),
            x_spec_s, tstat_s, tstat_s, tstat_s,
        ],
        out_specs=(
            pl.BlockSpec((1, bs, d), lambda js, jb, jt: (jb, js, 0)),
            pl.BlockSpec((d, bs), lambda js, jb, jt: (0, js)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, s_pad, d), ys.dtype),
            jax.ShapeDtypeStruct((d, s_pad), phi_n.dtype),
        ),
        scratch_shapes=[pltpu.VMEM((bs, d), dt), pltpu.VMEM((d, bs), dt)],
        name="softmoe_combine_bwd_dys_dphi",
        interpret=cfg.resolve_interpret(),
    )(*args)
    return dx[:, :m], dphi[:, :s], dys[:, :s]


# ---------------------------------------------------------------------------
# single-sequence back-compat wrappers (2D in / 2D out)
# ---------------------------------------------------------------------------


def _cfg_2d(bt, bs, interpret):
    return KernelConfig(block_tokens=bt, block_slots=bs, interpret=interpret)


def dispatch_pallas(x, phi_n, *, bt: int = 128, bs: int = 128,
                    interpret=None):
    """x: (m, d); phi_n: (d, S) pre-normalized. Returns slots (S, d)."""
    slots, _, _ = routing_fwd_pallas(x[None], phi_n, _cfg_2d(bt, bs,
                                                             interpret))
    return slots[0]


def combine_pallas(x, phi_n, ys, *, bt: int = 128, bs: int = 128,
                   interpret=None):
    """x: (m, d); phi_n: (d, S); ys: (S, d) expert outputs -> y (m, d)."""
    y, _ = combine_online_pallas(x[None], phi_n, ys[None],
                                 _cfg_2d(bt, bs, interpret))
    return y[0]
