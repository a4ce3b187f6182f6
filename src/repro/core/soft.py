"""Differentiable readability: sigmoid relaxations of the integer metrics.

The exact engine (:func:`repro.core.engine.evaluate_batched_body`) counts
with hard indicators — ``d2 < (2r)^2`` for node occlusion, the strict
ordinate reversal ``(yl_i < yl_j) & (yr_i > yr_j)`` for edge crossing —
so ``jax.grad`` through it is identically zero: the counts are piecewise
constant in the coordinates.  This module is the *soft companion*: the
SAME plan metadata, the SAME cell/strip bucketing
(:func:`repro.core.grid.gather_ragged_buckets` over the plan's occupancy
tiers), the same orientation vote — but every hard comparison ``a < b``
becomes ``sigmoid((b - a) / tau)``, so :func:`soft_scores` is
differentiable end-to-end and a gradient step moves vertices *along the
engine's own decompositions*.

The contract (see ``docs/search.md``):

* **Exact numbers are the reported numbers.**  Nothing here changes any
  ``evaluate*`` path; the search driver (:mod:`repro.search.gradient`)
  descends soft losses but re-scores candidates with the exact engine
  and reports only those.
* **Temperature is traced, not static.**  ``tau`` enters the program as
  a device scalar, so an annealing schedule never retraces
  (:func:`trace_count` proves it, mirroring ``engine.trace_count``).
  Sigmoid widths are ``temperature`` x the metric's natural scale: the
  occlusion indicator relaxes over squared distances with ``tau =
  temperature * (2r)^2``, the reversal indicator over boundary ordinates
  with ``tau = temperature * 2r``.
* **Soft -> exact as temperature -> 0** on layouts without exact ties
  (an exactly tied comparison — coincident ordinates, a pair exactly at
  distance 2r — converges to 1/2 per sigmoid where the strict exact
  comparison says 0; grid-aligned and collinear families hit this, and
  ``tests/test_soft.py`` covers both regimes).
* **Gradients are finite on degenerate layouts** (duplicate positions,
  zero-length edges, E=0, collinear): every ``arctan2`` / ``sqrt`` on
  the soft path runs through double-``where``-guarded variants
  (:func:`repro.core.geometry.segment_theta_safe`,
  :func:`~repro.core.geometry.directed_angle_safe`, :func:`_safe_sqrt`)
  whose forward values are bit-identical and whose partials are zero
  instead of NaN at the singular point.  (A NaN partial would poison the
  whole backward pass: JAX's VJPs multiply cotangents into partials, and
  ``0 * NaN = NaN``.)

``M_a`` and ``M_l`` need no sigmoid — they are already continuous in the
coordinates — so their "soft" versions are the exact formulas routed
through the guarded primitives (identical forward values).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import engine
from repro.core import grid as gridlib
from repro.core.min_angle import minimum_angle_batched

# Traced-once proof counter, mirroring engine.trace_count(): an annealing
# loop that feeds a new temperature every step must not bump this.
_trace_count = 0


def trace_count() -> int:
    """How many times :func:`soft_scores` has been traced."""
    return _trace_count


class SoftScores(NamedTuple):
    """Differentiable per-layout scores (``(B,)`` float fields).

    Count-valued fields (``node_occlusion``, ``edge_crossing``) are soft
    expected counts — floats that approach the exact integer counts as
    temperature -> 0.  ``overflow`` is the hard int bucketing-drop
    counter (same meaning as the exact result's; not differentiable) so
    a search loop can detect capacity starvation between exact
    re-scores.  Fields are ``None`` when the plan's metric subset
    pruned them.
    """

    node_occlusion: jax.Array = None
    minimum_angle: jax.Array = None
    edge_length_variation: jax.Array = None
    edge_crossing: jax.Array = None
    edge_crossing_angle: jax.Array = None
    overflow: jax.Array = None


class SoftWeights(NamedTuple):
    """Per-metric weights of :func:`soft_loss` (traced leaves — changing
    a weight never retraces).  Each term is already normalized to a
    [0, 1]-ish scale before weighting (see :func:`soft_loss`)."""

    node_occlusion: float = 1.0
    minimum_angle: float = 1.0
    edge_length_variation: float = 1.0
    edge_crossing: float = 1.0
    edge_crossing_angle: float = 1.0


def _safe_sqrt(x):
    """``sqrt`` with the double-``where`` guard: identical forward values
    (``sqrt(0) = 0``), zero gradient at 0 instead of ``inf``."""
    positive = x > 0
    return jnp.where(positive, jnp.sqrt(jnp.where(positive, x, 1.0)), 0.0)


# ---------------------------------------------------------------------------
# soft node occlusion (the exact batched gridded counter, sigmoid indicator)
# ---------------------------------------------------------------------------

def _soft_occlusion(plan, pos, vertex_valid, tau):
    """Soft N_c over the plan's occlusion grid: the exact batched
    counter's bucketing and half-neighbourhood sweep with the hard
    ``d2 < (2r)^2`` indicator relaxed to ``sigmoid((thresh - d2) / tau)``
    (``tau`` traced).  Returns ``((B,) soft count, (B,) overflow)``."""
    B, V = pos.shape[0], pos.shape[1]
    nx, ny, cap = plan.grid_nx, plan.grid_ny, plan.cell_cap
    n_cells = nx * ny
    origin, size = plan.grid_origin, plan.grid_cell_size
    gridlib.CALL_COUNTS["cell_builds"] += 1
    _, _, cid = gridlib.cell_indices(pos, plan.radius, origin, nx, ny,
                                     cell_size=size)
    vmask = None
    if vertex_valid is not None:
        vmask = jnp.broadcast_to(vertex_valid, (B, V))
    x, y, bval, _, overflow = gridlib.gather_ragged_buckets(
        cid, n_cells, np.arange(n_cells, dtype=np.int64) * cap,
        np.full(n_cells, cap, np.int64), pos[..., 0], pos[..., 1],
        valid=vmask)
    x = x.reshape(B * n_cells, cap)
    y = y.reshape(B * n_cells, cap)
    bval = bval.reshape(B * n_cells, cap)

    nbr = gridlib.neighbour_bucket_ids(nx, ny)
    nbr_f = jnp.where(
        nbr[None] >= 0,
        nbr[None] + jnp.arange(B, dtype=jnp.int32)[:, None, None] * n_cells,
        -1).reshape(B * n_cells, 4)
    nbr_ok = nbr_f >= 0
    nbr_idx = jnp.maximum(nbr_f, 0)
    thresh = jnp.asarray((2.0 * plan.radius) ** 2, pos.dtype)

    rows = B * n_cells
    cell_block = min(plan.cell_block, rows)
    n_blocks = -(-rows // cell_block)
    pad_rows = n_blocks * cell_block

    def padr(a, fill):
        extra = pad_rows - rows
        if extra == 0:
            return a
        return jnp.concatenate(
            [a, jnp.full((extra,) + a.shape[1:], fill, a.dtype)])

    xp, yp, vp = padr(x, 0.0), padr(y, 0.0), padr(bval, False)
    nip, nop = padr(nbr_idx, 0), padr(nbr_ok, False)

    def block_fn(b0):
        sl = lambda a: lax.dynamic_slice_in_dim(a, b0, cell_block, axis=0)
        bx, by, bv = sl(xp), sl(yp), sl(vp)
        ni, no = sl(nip), sl(nop)
        tri = jnp.arange(cap)[:, None] < jnp.arange(cap)[None, :]
        d2 = ((bx[:, :, None] - bx[:, None, :]) ** 2
              + (by[:, :, None] - by[:, None, :]) ** 2)
        smask = bv[:, :, None] & bv[:, None, :] & tri[None]
        w = jax.nn.sigmoid((thresh - d2) / tau)
        same = jnp.sum(jnp.where(smask, w, 0.0), axis=(1, 2))
        cx = x[ni].reshape(cell_block, -1)
        cy = y[ni].reshape(cell_block, -1)
        cv = (bval[ni] & no[:, :, None]).reshape(cell_block, -1)
        c2 = ((bx[:, :, None] - cx[:, None, :]) ** 2
              + (by[:, :, None] - cy[:, None, :]) ** 2)
        cmask = bv[:, :, None] & cv[:, None, :]
        wc = jax.nn.sigmoid((thresh - c2) / tau)
        cross = jnp.sum(jnp.where(cmask, wc, 0.0), axis=(1, 2))
        return same + cross

    # remat the block: lax.map's VJP otherwise stacks every block's
    # (cell_block, cap, cap) pairwise intermediates as scan residuals,
    # making the backward pass an order of magnitude slower than the
    # forward — recomputing the block during the backward sweep keeps
    # residuals at the (already materialized) bucket inputs
    starts = jnp.arange(0, pad_rows, cell_block, dtype=jnp.int32)
    per_row = lax.map(jax.checkpoint(block_fn), starts).reshape(pad_rows)[:rows]
    return per_row.reshape(B, n_cells).sum(axis=1), overflow


# ---------------------------------------------------------------------------
# soft reversal sweep (the exact tiered sweep, sigmoid reversal indicator)
# ---------------------------------------------------------------------------

def soft_reversal_block(yl, yr, theta, v, u, valid, *, ideal, tau,
                        with_angle: bool = True):
    """Soft version of :func:`repro.core.engine.fused_reversal_block`
    over a ``(rows, cap)`` bucket block, per-row reduction.

    The hard reversal ``(yl_i < yl_j) & (yr_i > yr_j)`` becomes
    ``sigmoid((yl_j - yl_i) / tau) * sigmoid((yr_i - yr_j) / tau)``; the
    shared-endpoint exclusion and validity masks are identical (bool,
    not differentiated — pair *membership* comes from the exact
    bucketing, only the indicator is relaxed).  The diagonal needs no
    special case: a segment shares endpoints with itself, so the shared
    mask kills it exactly as in the hard sweep.  Returns per-row
    ``((rows,) soft count, (rows,) soft deviation sum)``.
    """
    sig = jax.nn.sigmoid
    w = (sig((yl[:, None, :] - yl[:, :, None]) / tau)
         * sig((yr[:, :, None] - yr[:, None, :]) / tau))
    shared = ((v[:, :, None] == v[:, None, :]) |
              (v[:, :, None] == u[:, None, :]) |
              (u[:, :, None] == v[:, None, :]) |
              (u[:, :, None] == u[:, None, :]))
    mask = ~shared & valid[:, :, None] & valid[:, None, :]
    wm = jnp.where(mask, w, 0.0)
    cnt = jnp.sum(wm, axis=(1, 2))
    if not with_angle:
        return cnt, jnp.zeros(yl.shape[0], yl.dtype)
    ideal = jnp.asarray(ideal, yl.dtype)
    d = jnp.abs(theta[:, :, None] - theta[:, None, :])
    a_c = jnp.minimum(d, jnp.pi - d)
    dev = jnp.abs(ideal - a_c) / ideal
    dev_sum = jnp.sum(wm * dev, axis=(1, 2))
    return cnt, dev_sum


def _soft_reversal_rows(yl, yr, th, v, u, ok, *, ideal, tau,
                        with_angle: bool, row_block: int):
    """Blocked per-row soft sweep (the soft twin of
    ``engine._reversal_rows``; ``tau`` is a traced closure value)."""
    rows, cap = yl.shape
    row_block = max(1, min(row_block, (1 << 26) // max(cap * cap, 1), rows))
    n_blocks = -(-rows // row_block)
    pad = n_blocks * row_block

    def padc(a, fill):
        extra = pad - rows
        if extra == 0:
            return a
        return jnp.concatenate(
            [a, jnp.full((extra,) + a.shape[1:], fill, a.dtype)])

    yl, yr, th = padc(yl, 0.0), padc(yr, 0.0), padc(th, 0.0)
    v, u, ok = padc(v, -1), padc(u, -2), padc(ok, False)

    def block_fn(b0):
        sl = lambda a: lax.dynamic_slice_in_dim(a, b0, row_block, axis=0)
        return soft_reversal_block(sl(yl), sl(yr), sl(th), sl(v), sl(u),
                                   sl(ok), ideal=ideal, tau=tau,
                                   with_angle=with_angle)

    # remat (see _soft_occlusion): without it the scan VJP stacks
    # (row_block, cap, cap) residuals per block and the reversal
    # backward runs ~40x its forward
    starts = jnp.arange(0, pad, row_block, dtype=jnp.int32)
    counts, devs = lax.map(jax.checkpoint(block_fn), starts)
    return counts.reshape(pad)[:rows], devs.reshape(pad)[:rows]


def _soft_tiered_strip_stats(plan, axis_i, segs, B, *, tau,
                             with_angle: bool):
    """Soft twin of ``engine._tiered_strip_stats``: same one-sort gather
    bucketing over the same occupancy-tier layout, soft sweep.  Returns
    ``((B,) soft count, (B,) soft dev sum, (B,) dropped)``."""
    n_strips = plan.n_strips
    strip_off, strip_cap, total, slabs = engine._tier_layout(plan, axis_i)
    yl, yr, th, v, u, ok, _, dropped = gridlib.gather_ragged_buckets(
        segs.strip, n_strips, strip_off, strip_cap,
        segs.yl, segs.yr, segs.theta, segs.v, segs.u, valid=segs.valid)

    gridlib.CALL_COUNTS["reversal_sweeps"] += 1
    cnt = jnp.zeros(B, yl.dtype)
    dev = jnp.zeros(B, yl.dtype)
    row_block = min(plan.strip_block, n_strips)
    for off, n_t, cap_t in slabs:
        sl = lambda a: (a[:, off:off + n_t * cap_t]
                        .reshape(B * n_t, cap_t))
        rc, rd = _soft_reversal_rows(sl(yl), sl(yr), sl(th), sl(v), sl(u),
                                     sl(ok), ideal=plan.ideal, tau=tau,
                                     with_angle=with_angle,
                                     row_block=row_block)
        cnt = cnt + rc.reshape(B, n_t).sum(axis=1)
        dev = dev + rd.reshape(B, n_t).sum(axis=1)
    return cnt, dev, dropped


# ---------------------------------------------------------------------------
# guarded M_l (continuous already; sqrt guards only)
# ---------------------------------------------------------------------------

def _soft_edge_length_variation(pos, edges, edge_valid):
    """``edge_length_variation_batched`` with every ``sqrt`` and division
    double-``where``-guarded: identical forward values, finite gradients
    on zero-length edges and all-duplicate layouts."""
    d = pos[:, edges[:, 0]] - pos[:, edges[:, 1]]          # (B, E, 2)
    lengths = _safe_sqrt(jnp.sum(d * d, axis=-1))          # (B, E)
    if edge_valid is None:
        edge_valid = jnp.ones(edges.shape[0], dtype=bool)
    ev = jnp.broadcast_to(edge_valid, lengths.shape)
    n_e = jnp.maximum(jnp.sum(ev, axis=1), 1)
    l_mu = jnp.sum(jnp.where(ev, lengths, 0.0), axis=1) / n_e
    sq = jnp.where(ev, (lengths - l_mu[:, None]) ** 2, 0.0)
    denom = n_e * jnp.maximum(l_mu, 1e-30) ** 2
    ok = denom > 0
    ratio = jnp.sum(sq, axis=1) / jnp.where(ok, denom, 1.0)
    l_a = jnp.where(ok, _safe_sqrt(ratio), 0.0)
    return jnp.where(n_e > 1, l_a / jnp.sqrt(jnp.maximum(n_e - 1, 1)), 0.0)


# ---------------------------------------------------------------------------
# the soft companion of evaluate_batched_body
# ---------------------------------------------------------------------------

def soft_scores(plan, batch_pos, edges, temperature, *,
                n_valid_vertices=None, n_valid_edges=None) -> SoftScores:
    """Differentiable scores of ``(B, V, 2)`` layouts under ``plan``.

    The soft companion of
    :func:`repro.core.engine.evaluate_batched_body`: same plan, same
    bucketing, same padding contract (the optional traced ``n_valid_*``
    scalars mask padded tails), but every count is a sigmoid-relaxed
    expectation and every primitive is gradient-safe, so
    ``jax.grad(lambda p: soft_scores(plan, p, ...).edge_crossing.sum())``
    is finite on any input — duplicates, E=0 (pad ``edges`` to one
    masked row, the engine's usual degenerate contract), collinear.

    ``temperature`` is a traced positive scalar (see the module
    docstring for the per-metric widths); annealing never retraces.
    Like the body it shadows, this function is meant to be traced inside
    a caller's jit (the search driver's step function) — it is not
    jitted here.
    """
    global _trace_count
    if isinstance(batch_pos, jax.core.Tracer):
        _trace_count += 1
    pos = jnp.asarray(batch_pos, plan.dtype)
    edges = jnp.asarray(edges, jnp.int32)
    B = pos.shape[0]
    tau = jnp.asarray(temperature, plan.dtype)
    vertex_valid, edge_valid = engine._valid_masks(
        pos.shape[1], edges.shape[0], n_valid_vertices, n_valid_edges)
    m = plan.metrics
    out = {}
    overflow = jnp.zeros(B, jnp.int32)

    if "node_occlusion" in m:
        tau_occ = tau * jnp.asarray((2.0 * plan.radius) ** 2, plan.dtype)
        cnt, ov = _soft_occlusion(plan, pos, vertex_valid, tau_occ)
        overflow = overflow + ov
        out["node_occlusion"] = cnt
    if "minimum_angle" in m:
        m_a, _ = minimum_angle_batched(pos, edges, edge_valid=edge_valid,
                                       safe_grad=True)
        out["minimum_angle"] = m_a
    if "edge_length_variation" in m:
        out["edge_length_variation"] = _soft_edge_length_variation(
            pos, edges, edge_valid)

    want_ec = "edge_crossing" in m
    want_eca = "edge_crossing_angle" in m
    if want_ec or want_eca:
        tau_rev = tau * jnp.asarray(2.0 * plan.radius, plan.dtype)
        stats = []
        for axis_i, (axis, (max_segments, cap)) in enumerate(
                zip(plan.axes, plan.strip_plans)):
            segs = gridlib.build_strip_segments_batched(
                pos, edges, plan.n_strips, max_segments, axis=axis,
                edge_valid=edge_valid, safe_theta=True)
            cnt, dev, drop = _soft_tiered_strip_stats(
                plan, axis_i, segs, B, tau=tau_rev, with_angle=want_eca)
            stats.append((cnt, dev, drop + segs.overflow))
        # the exact body's best-orientation vote, on the soft counts
        # (converges to the exact vote as tau -> 0 away from count ties;
        # the selected branch carries the gradient).  Its E_ca branch on
        # ``count > 0`` is smooth here: a sigmoid-weighted count is 0
        # only where every pair is masked out, and then the deviation sum
        # and both their gradients are 0 as well
        crossing, ec_ov = engine._select_orientation(stats, want_ec,
                                                     want_eca)
        crossing.pop("crossing_count_for_angle", None)
        out.update(crossing)
        overflow = overflow + ec_ov

    return SoftScores(overflow=overflow, **out)


def soft_loss(plan, batch_pos, edges, temperature, *, weights=None,
              n_valid_vertices=None, n_valid_edges=None):
    """Per-layout scalar losses ``(B,)``: lower is better, 0 is perfect.

    Each metric contributes ``1 - normalized`` in the sense of
    :meth:`repro.core.scores.ReadabilityScores.normalized` (counts over
    their pair budgets, ``M_l`` squashed by ``1/(1 + M_l)``), so with
    unit weights minimizing the loss is maximizing the mean normalized
    readability — the objective the search driver's exact re-scoring
    ranks by.  ``weights`` is a :class:`SoftWeights` (traced leaves;
    reweighting never retraces).
    """
    s = soft_scores(plan, batch_pos, edges, temperature,
                    n_valid_vertices=n_valid_vertices,
                    n_valid_edges=n_valid_edges)
    w = SoftWeights() if weights is None else weights
    dtype = jnp.asarray(batch_pos).dtype
    if dtype not in (jnp.float32, jnp.float64, jnp.bfloat16):
        dtype = jnp.float32
    nv = batch_pos.shape[1] if n_valid_vertices is None else n_valid_vertices
    ne = edges.shape[0] if n_valid_edges is None else n_valid_edges
    nv = jnp.asarray(nv, dtype)
    ne = jnp.asarray(ne, dtype)
    vpairs = jnp.maximum(nv * (nv - 1) / 2, 1.0)
    epairs = jnp.maximum(ne * (ne - 1) / 2, 1.0)
    loss = jnp.zeros(jnp.asarray(batch_pos).shape[0], dtype)
    if s.node_occlusion is not None:
        loss = loss + w.node_occlusion * s.node_occlusion / vpairs
    if s.minimum_angle is not None:
        loss = loss + w.minimum_angle * (1.0 - s.minimum_angle)
    if s.edge_length_variation is not None:
        m_l = s.edge_length_variation
        loss = loss + w.edge_length_variation * m_l / (1.0 + m_l)
    if s.edge_crossing is not None:
        loss = loss + w.edge_crossing * s.edge_crossing / epairs
    if s.edge_crossing_angle is not None:
        loss = loss + w.edge_crossing_angle * (1.0 - s.edge_crossing_angle)
    return loss
