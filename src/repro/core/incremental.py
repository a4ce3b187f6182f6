"""Incremental re-evaluation for dynamic layouts (ROADMAP: dynamic graphs).

When an interactive front-end drags a handful of vertices per frame,
re-running the full fused program recomputes every grid cell and every
strip from scratch even though almost none of their *membership* changed.
This module keeps the plan's bucketed decompositions **resident on
device** — the cell-occupancy tables, per-cell occlusion partials, the
per-strip segment tables with per-strip (count, deviation) partials, and
the per-vertex minimum-angle deviations — and re-derives only the dirty
subset when :meth:`repro.launch.session.EvalSession.update` moves a
small vertex set.

Dirty-set rule
--------------
* **cells** — the union of the moved vertices' old and new grid cells;
  owner rows that must re-count are those cells plus every cell whose
  half-neighbourhood sweep reads a dirty cell (the backward offsets of
  :data:`repro.core.grid.HALF_NEIGHBOURHOOD`).
* **strips** — per orientation, the union of the old and new strip spans
  of every *affected edge* (an edge with a moved endpoint).
* **min angle** — the moved vertices and their graph neighbours.

Bit-identity
------------
The repo's central invariant extends to this path: the integer metrics
(``node_occlusion``, ``edge_crossing``, ``crossing_count_for_angle``)
are **bit-identical** to a from-scratch evaluation.  Two properties
carry the proof:

* every pair count is *set-determined*: the masked sums in
  :func:`repro.core.engine.fused_reversal_block` and the occlusion
  block formula depend only on the set of (valid) members of a bucket,
  never on slot order — so a delta-rebuilt bucket with the same
  membership yields the same count;
* clean partials are *resident*, not recomputed — untouched rows keep
  the primed values, and integer totals are order-independent sums.

Anything that would break membership equality falls back instead of
guessing: bucket overflow during the delta rebuild, a moved vertex
landing outside the planned dirty set, or a changed strip domain
(``lo``/``hi``) all report through ``overflow``/host checks and the
session re-evaluates from scratch (see ``docs/incremental.md``).

Counters
--------
The delta program is built exclusively from non-counting primitives
(:func:`~repro.core.grid.gather_ragged_buckets`, the block formulas),
so even its *trace* bumps none of :data:`repro.core.grid.CALL_COUNTS` —
the counter certificate in ``tests/test_incremental.py`` rests on that.
:func:`prime_state` is a full build and bumps ``cell_builds`` /
``strip_builds`` / ``vertex_sorts`` honestly (host-side, once per call).
"""

from __future__ import annotations

from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core import grid as gridlib
from repro.core.edge_length import edge_length_variation
from repro.core.engine import (ReadabilityPlan, ReadabilityScores,
                               _reversal_rows, _select_orientation,
                               _valid_masks)
from repro.core.geometry import TWO_PI, directed_angle, segment_theta


# ---------------------------------------------------------------------------
# resident state
# ---------------------------------------------------------------------------

class ResidentStrip(NamedTuple):
    """Per-orientation resident strip decomposition (flat layout)."""

    eid: jax.Array    # (n_strips, cap) int32 parent edge per slot
    valid: jax.Array  # (n_strips, cap) bool
    cnt: jax.Array    # (n_strips,) count_dtype per-strip crossing partial
    dev: jax.Array    # (n_strips,) dtype per-strip deviation partial
    lo: jax.Array     # () strip domain lower bound (plan dtype)
    hi: jax.Array     # () strip domain upper bound


class ResidentState(NamedTuple):
    """Device-resident partials of ONE layout under ONE plan.

    Slot *values* (coordinates, boundary ordinates, thetas) are never
    stored — only membership (ids + validity) and the reduced partials.
    Values are re-derived from ``pos`` at use time by the full path's
    formulas, so a delta can never read a stale coordinate.
    Metric-absent fields are ``None`` (stable per plan, so the jit
    treedef is stable too).
    """

    pos: jax.Array            # (vb, 2) padded positions, plan dtype
    cell_vid: Any = None      # (n_cells, cap) int32, invalid slot -> vb
    cell_valid: Any = None    # (n_cells, cap) bool
    occ_partial: Any = None   # (n_cells,) count_dtype
    strips: tuple = ()        # ResidentStrip per plan axis
    ma_dev: Any = None        # (vb,) dtype per-vertex deviation
    inc_nbr: Any = None       # (vb, deg_cap) int32 incidence, -1 pads
    inc_deg: Any = None       # (vb,) int32


# ---------------------------------------------------------------------------
# host-side helpers (incidence, padding, dirty sets)
# ---------------------------------------------------------------------------

def incidence_table(edges, n_v: int, vb: int):
    """Host-built per-vertex incidence: ``(inc_nbr, inc_deg, deg_cap)``.

    ``inc_nbr`` is ``(vb, deg_cap)`` int32 with -1 pads: row v lists the
    opposite endpoints of v's incident edges (a self-loop contributes v
    twice, matching the two half-edges the engine path emits).
    ``deg_cap`` is the power-of-two capacity (floor 2) — plan-hashable
    via ``ReadabilityPlan.resident``.
    """
    edges = np.asarray(edges, np.int32)
    deg = np.zeros(vb, np.int64)
    np.add.at(deg, edges[:, 0], 1)
    np.add.at(deg, edges[:, 1], 1)
    deg_cap = 2
    top = int(deg.max()) if len(edges) else 0
    while deg_cap < top:
        deg_cap *= 2
    inc = np.full((vb, deg_cap), -1, np.int32)
    fill = np.zeros(vb, np.int64)
    for a, b in edges:
        inc[a, fill[a]] = b
        fill[a] += 1
        inc[b, fill[b]] = a
        fill[b] += 1
    return inc, deg.astype(np.int32), deg_cap


def pad_ids(ids, sentinel: int, floor: int = 8) -> np.ndarray:
    """Sort-unique ``ids`` and pad with ``sentinel`` to a power-of-two
    length (bounded retrace variety for the delta jit)."""
    ids = np.unique(np.asarray(ids, np.int64))
    cap = floor
    while cap < len(ids):
        cap *= 2
    out = np.full(cap, sentinel, np.int32)
    out[:len(ids)] = ids
    return out


def affected_edges(edges, moved, n_v: int) -> np.ndarray:
    """Edge ids with >= 1 moved endpoint (host O(E) mask)."""
    am = np.zeros(n_v, bool)
    am[np.asarray(moved, np.int64)] = True
    edges = np.asarray(edges, np.int64)
    return np.nonzero(am[edges[:, 0]] | am[edges[:, 1]])[0]


def owner_cells(dirty, nx: int, ny: int) -> np.ndarray:
    """Dirty cells plus every cell whose half-neighbourhood reads one
    (the backward offsets of the forward sweep)."""
    dirty = np.asarray(dirty, np.int64)
    cx, cy = dirty % nx, dirty // nx
    out = [dirty]
    for dx, dy in ((-1, 0), (0, -1), (-1, -1), (-1, 1)):
        ox, oy = cx + dx, cy + dy
        ok = (ox >= 0) & (ox < nx) & (oy >= 0) & (oy < ny)
        out.append((oy * nx + ox)[ok])
    return np.unique(np.concatenate(out))


# ---------------------------------------------------------------------------
# the full path's formulas, on the resident tables
# ---------------------------------------------------------------------------

def _cell_ids(xy, plan: ReadabilityPlan):
    """Flat cell id per ``(..., 2)`` point on the plan's occlusion grid."""
    return gridlib.cell_indices(xy, plan.radius, plan.grid_origin,
                                plan.grid_nx, plan.grid_ny,
                                cell_size=plan.grid_cell_size)[2]


def _strip_domain(pos, edges, edge_valid, axis: int):
    """(lo, hi) as the strip builder derives them
    (:func:`repro.core.grid.strip_extent`)."""
    lo, hi = gridlib.strip_extent(pos[edges[:, 0], axis],
                                  pos[edges[:, 1], axis], edge_valid)
    return lo[0], hi[0]


def _strip_spans(pos, edges, eids, ok, lo, hi, n_strips: int, axis: int):
    """Per-edge strip span ``(s_first, s_last, n_seg)`` of edges ``eids``
    (:func:`repro.core.grid.strip_spans`, as the strip builder)."""
    e = jnp.clip(eids, 0, edges.shape[0] - 1)
    return gridlib.strip_spans(pos[edges[e, 0], axis],
                               pos[edges[e, 1], axis], lo, hi, n_strips, ok)


def _strip_values(pos, edges, eid, strip, lo, hi, n_strips: int, axis: int):
    """Slot values ``(yl, yr, theta, v, u)`` for (edge, strip) pairs
    (:func:`repro.core.grid.strip_ordinates`, as the strip builder)."""
    e = jnp.clip(eid, 0, edges.shape[0] - 1)
    p = pos[edges[e, 0]]
    q = pos[edges[e, 1]]
    theta = segment_theta(p[:, 0], p[:, 1], q[:, 0], q[:, 1])
    yl, yr = gridlib.strip_ordinates(p[:, axis], p[:, 1 - axis], q[:, axis],
                                     q[:, 1 - axis], strip, lo, hi, n_strips)
    return yl, yr, theta, edges[e, 0], edges[e, 1]


def _occ_rows(row_ids, vid_tab, val_tab, px, py, nbr_idx, nbr_ok, thresh):
    """Per-cell occlusion partial for the given rows — mirror of the
    block formula in
    :func:`repro.core.occlusion.count_occlusions_gridded_batched`
    (same-cell triangle + 4-neighbour cross pairs), reduced per row."""
    n_cells = vid_tab.shape[0]
    ok = row_ids < n_cells
    r = jnp.minimum(row_ids, n_cells - 1)
    bvid = vid_tab[r]
    bv = val_tab[r] & ok[:, None]
    bx, by = px[bvid], py[bvid]
    cap = bvid.shape[1]
    tri = jnp.arange(cap)[:, None] < jnp.arange(cap)[None, :]
    d2 = ((bx[:, :, None] - bx[:, None, :]) ** 2
          + (by[:, :, None] - by[:, None, :]) ** 2)
    smask = bv[:, :, None] & bv[:, None, :] & tri[None]
    same = jnp.sum(jnp.where(smask & (d2 < thresh), 1, 0), axis=(1, 2),
                   dtype=gridlib.count_dtype())
    ni = nbr_idx[r]                                    # (R, 4)
    no = nbr_ok[r] & ok[:, None]
    cvid = vid_tab[ni]                                 # (R, 4, cap)
    rows = r.shape[0]
    cx = px[cvid].reshape(rows, -1)
    cy = py[cvid].reshape(rows, -1)
    cv = (val_tab[ni] & no[:, :, None]).reshape(rows, -1)
    d2c = ((bx[:, :, None] - cx[:, None, :]) ** 2
           + (by[:, :, None] - cy[:, None, :]) ** 2)
    cmask = bv[:, :, None] & cv[:, None, :]
    cross = jnp.sum(jnp.where(cmask & (d2c < thresh), 1, 0), axis=(1, 2),
                    dtype=gridlib.count_dtype())
    return same + cross


def _occ_rows_blocked(row_ids, vid_tab, val_tab, px, py, nbr_idx, nbr_ok,
                      thresh, block: int):
    """Blocked :func:`_occ_rows` for the prime-time full sweep."""
    n = row_ids.shape[0]
    n_cells = vid_tab.shape[0]
    block = max(1, min(block, n))
    pad = -(-n // block) * block
    ids = jnp.concatenate(
        [row_ids, jnp.full(pad - n, n_cells, jnp.int32)]) if pad > n \
        else row_ids

    def block_fn(b0):
        sl = jax.lax.dynamic_slice_in_dim(ids, b0, block)
        return _occ_rows(sl, vid_tab, val_tab, px, py, nbr_idx, nbr_ok,
                         thresh)

    starts = jnp.arange(0, pad, block, dtype=jnp.int32)
    return jax.lax.map(block_fn, starts).reshape(pad)[:n]


def _ma_rows(pos, row_ids, inc_nbr, inc_deg):
    """Per-vertex minimum-angle deviation for the given rows, from the
    resident incidence table.  Same angle values and the same sorted
    neighbour-gap reduction as :func:`repro.core.min_angle.minimum_angle`
    restricted to one vertex's run."""
    vb = pos.shape[0]
    ok = row_ids < vb
    r = jnp.minimum(row_ids, vb - 1)
    nbr = inc_nbr[r]                                   # (R, D)
    deg = inc_deg[r]
    D = nbr.shape[1]
    slot_ok = jnp.arange(D, dtype=jnp.int32)[None, :] < deg[:, None]
    nn = jnp.clip(nbr, 0, vb - 1)
    ang = directed_angle(pos[r, 0][:, None], pos[r, 1][:, None],
                         pos[nn, 0], pos[nn, 1])
    a = jnp.sort(jnp.where(slot_ok, ang, jnp.inf), axis=1)
    if D > 1:
        gaps_ok = (jnp.arange(D - 1, dtype=jnp.int32)[None, :]
                   < deg[:, None] - 1)
        gaps = jnp.where(gaps_ok, a[:, 1:] - a[:, :-1], jnp.inf)
        gap_min = jnp.min(gaps, axis=1)
    else:
        gap_min = jnp.full(r.shape, jnp.inf, a.dtype)
    amin = a[:, 0]
    amax = jnp.take_along_axis(
        a, jnp.clip(deg - 1, 0, D - 1)[:, None], axis=1)[:, 0]
    wrap = TWO_PI - (amax - amin)
    phi_min = jnp.minimum(gap_min, wrap)
    counted = deg >= 1
    ideal = TWO_PI / jnp.maximum(deg, 1)
    return jnp.where(counted & ok, (ideal - phi_min) / ideal, 0.0)


# ---------------------------------------------------------------------------
# prime: one full build of the resident state (jitted, plan-static)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("plan",))
def _prime_fn(plan: ReadabilityPlan, pos, edges, n_v, n_e, inc_nbr, inc_deg):
    pos = jnp.asarray(pos, plan.dtype)
    edges = jnp.asarray(edges, jnp.int32)
    vb, eb = pos.shape[0], edges.shape[0]
    vertex_valid, edge_valid = _valid_masks(vb, eb, n_v, n_e)
    m = plan.metrics
    overflow = jnp.zeros((), jnp.int32)
    px = jnp.concatenate([pos[:, 0], jnp.zeros(1, pos.dtype)])
    py = jnp.concatenate([pos[:, 1], jnp.zeros(1, pos.dtype)])

    cell_vid = cell_valid = occ_partial = None
    vert_cell = jnp.zeros(vb, jnp.int32)
    if "node_occlusion" in m:
        n_cells = plan.grid_nx * plan.grid_ny
        vert_cell = _cell_ids(pos, plan)
        vid, bvalid, _, ov = gridlib.scatter_to_buckets(
            vert_cell, n_cells, plan.cell_cap,
            jnp.arange(vb, dtype=jnp.int32), valid=vertex_valid)
        cell_vid = jnp.where(bvalid, vid, vb)
        cell_valid = bvalid
        nbr = gridlib.neighbour_bucket_ids(plan.grid_nx, plan.grid_ny)
        thresh = jnp.asarray((2.0 * plan.radius) ** 2, pos.dtype)
        occ_partial = _occ_rows_blocked(
            jnp.arange(n_cells, dtype=jnp.int32), cell_vid, cell_valid,
            px, py, jnp.maximum(nbr, 0), nbr >= 0, thresh,
            min(plan.cell_block, n_cells))
        overflow = overflow + ov

    strips = []
    strip_aux = []
    if ("edge_crossing" in m) or ("edge_crossing_angle" in m):
        with_angle = "edge_crossing_angle" in m
        for axis, (max_segments, cap) in zip(plan.axes, plan.strip_plans):
            lo, hi = _strip_domain(pos, edges, edge_valid, axis)
            sf, sl, nseg = _strip_spans(
                pos, edges, jnp.arange(eb, dtype=jnp.int32), edge_valid,
                lo, hi, plan.n_strips, axis)
            offsets = jnp.cumsum(nseg)
            total = offsets[-1]
            starts = offsets - nseg
            slot = jnp.arange(max_segments, dtype=jnp.int32)
            eid = jnp.minimum(gridlib.slot_edge_ids(offsets, max_segments),
                              eb - 1)
            valid = slot < total
            strip = sf[eid] + (slot - starts[eid])
            key = jnp.where(valid, strip, plan.n_strips)
            drop = jnp.maximum(total - max_segments, 0).astype(jnp.int32)
            tab_eid, in_cap, _, ov = gridlib.gather_ragged_buckets(
                key[None], plan.n_strips,
                np.arange(plan.n_strips, dtype=np.int64) * cap,
                np.full(plan.n_strips, cap, np.int64),
                eid[None], valid=valid[None])
            tab_eid = tab_eid.reshape(plan.n_strips, cap)
            tab_ok = in_cap.reshape(plan.n_strips, cap)
            row_strip = jnp.broadcast_to(
                jnp.arange(plan.n_strips, dtype=jnp.int32)[:, None],
                (plan.n_strips, cap))
            yl, yr, th, v, u = _strip_values(
                pos, edges, tab_eid.reshape(-1), row_strip.reshape(-1),
                lo, hi, plan.n_strips, axis)
            shape = (plan.n_strips, cap)
            cnt, dev = _reversal_rows(
                yl.reshape(shape), yr.reshape(shape), th.reshape(shape),
                v.reshape(shape), u.reshape(shape), tab_ok,
                ideal=plan.ideal, with_angle=with_angle,
                row_block=min(plan.strip_block, plan.n_strips))
            strips.append(ResidentStrip(eid=tab_eid, valid=tab_ok,
                                        cnt=cnt, dev=dev, lo=lo, hi=hi))
            strip_aux.append((sf, sl, total, lo, hi))
            overflow = overflow + drop + ov[0]

    ma_dev = None
    if "minimum_angle" in m:
        ma_dev = _ma_rows(pos, jnp.arange(vb, dtype=jnp.int32),
                          inc_nbr, inc_deg)

    state = ResidentState(pos=pos, cell_vid=cell_vid, cell_valid=cell_valid,
                          occ_partial=occ_partial, strips=tuple(strips),
                          ma_dev=ma_dev, inc_nbr=inc_nbr, inc_deg=inc_deg)
    return state, (overflow, vert_cell, tuple(strip_aux))


def prime_state(plan: ReadabilityPlan, pos, edges, n_v: int, n_e: int,
                inc_nbr, inc_deg):
    """Build the resident state (host wrapper; ONE device fetch).

    Returns ``(state, aux)`` with ``aux`` a host dict: ``overflow``
    (int), ``vert_cell`` ((vb,) int32 cell mirror), and per-axis
    ``strips`` tuples ``(s_first, s_last, total, lo, hi)`` (numpy).
    A full build, counted honestly: bumps ``cell_builds`` /
    ``strip_builds`` / ``vertex_sorts`` like the from-scratch path.
    """
    m = plan.metrics
    if "node_occlusion" in m:
        gridlib.CALL_COUNTS["cell_builds"] += 1
    if ("edge_crossing" in m) or ("edge_crossing_angle" in m):
        gridlib.CALL_COUNTS["strip_builds"] += len(plan.axes)
        gridlib.CALL_COUNTS["reversal_sweeps"] += len(plan.axes)
    if "minimum_angle" in m:
        gridlib.CALL_COUNTS["vertex_sorts"] += 1
    state, aux = _prime_fn(plan, pos, edges,
                           jnp.asarray(n_v, jnp.int32),
                           jnp.asarray(n_e, jnp.int32), inc_nbr, inc_deg)
    overflow, vert_cell, strip_aux = jax.device_get(aux)
    return state, {
        "overflow": int(overflow),
        "vert_cell": np.asarray(vert_cell),
        "strips": tuple(
            (np.asarray(sf), np.asarray(sl), int(total),
             np.asarray(lo), np.asarray(hi))
            for sf, sl, total, lo, hi in strip_aux),
    }


# ---------------------------------------------------------------------------
# probe: where do the moved vertices land? (jitted, plan-static)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("plan",))
def _probe_fn(plan: ReadabilityPlan, state: ResidentState, edges, n_e,
              moved, new_xy, aff):
    pos = state.pos
    vb, eb = pos.shape[0], edges.shape[0]
    pos2 = pos.at[moved].set(jnp.asarray(new_xy, pos.dtype), mode="drop")
    new_xyc = jnp.asarray(new_xy, pos.dtype)
    with jax.named_scope("occlusion"):
        new_cid = _cell_ids(new_xyc, plan) \
            if "node_occlusion" in plan.metrics else jnp.zeros(
                moved.shape, jnp.int32)
    edge_valid = jnp.arange(eb, dtype=jnp.int32) < n_e
    out_axes = []
    for axis_i, axis in enumerate(plan.axes if state.strips else ()):
        st = state.strips[axis_i]
        with jax.named_scope(f"strips.build/axis{axis_i}"):
            lo2, hi2 = _strip_domain(pos2, edges, edge_valid, axis)
            sf, sl, nseg = _strip_spans(pos2, edges, aff, aff < eb,
                                        st.lo, st.hi, plan.n_strips, axis)
        out_axes.append((lo2, hi2, sf, sl, nseg))
    return new_cid, tuple(out_axes)


def delta_probe(plan: ReadabilityPlan, state: ResidentState, edges,
                n_e: int, moved_p, new_xy_p, aff_p):
    """Host wrapper around the probe: ONE fetch, numpy outputs."""
    out = _probe_fn(
        plan, state, edges, jnp.asarray(n_e, jnp.int32),
        jnp.asarray(moved_p, jnp.int32),
        jnp.asarray(new_xy_p), jnp.asarray(aff_p, jnp.int32))
    with tracing.span("incremental.probe_fetch"):
        new_cid, axes = jax.device_get(out)
    return {"new_cid": np.asarray(new_cid),
            "axes": tuple((np.asarray(lo2), np.asarray(hi2),
                           np.asarray(sf), np.asarray(sl), np.asarray(ns))
                          for lo2, hi2, sf, sl, ns in axes)}


# ---------------------------------------------------------------------------
# the delta program (jitted, plan-static; non-counting primitives only)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("plan",))
def _delta_fn(plan: ReadabilityPlan, state: ResidentState, edges, n_e,
              moved, new_xy, aff, dirty_cells, owners, dirty_strips,
              dirty_ma):
    pos = state.pos
    vb, eb = pos.shape[0], edges.shape[0]
    edges = jnp.asarray(edges, jnp.int32)
    new_xyc = jnp.asarray(new_xy, pos.dtype)
    pos2 = pos.at[moved].set(new_xyc, mode="drop")
    px = jnp.concatenate([pos2[:, 0], jnp.zeros(1, pos.dtype)])
    py = jnp.concatenate([pos2[:, 1], jnp.zeros(1, pos.dtype)])
    mv_ok = moved < vb
    edge_valid = jnp.arange(eb, dtype=jnp.int32) < n_e
    m = plan.metrics
    out = {}
    overflow = jnp.zeros((), jnp.int32)

    # -- cells: rebuild dirty buckets, re-count owner rows ------------------
    cell_vid2, cell_val2, occ2 = state.cell_vid, state.cell_valid, \
        state.occ_partial
    if "node_occlusion" in m:
        with jax.named_scope("occlusion"):
            n_cells = plan.grid_nx * plan.grid_ny
            cap_c = plan.cell_cap
            dc = dirty_cells
            dc_cap = dc.shape[0]
            dci = jnp.minimum(dc, n_cells - 1)
            rows_vid = state.cell_vid[dci]                     # (dc, cap)
            rows_val = state.cell_valid[dci] & (dc < n_cells)[:, None]
            # survivors: current members minus every copy of a moved vertex
            # (the moved pad sentinel vb hits the spare mask slot, and the
            # vid sentinel vb rows are invalid anyway)
            mm = jnp.zeros(vb + 1, bool).at[moved].set(True)
            keep = rows_val & ~mm[rows_vid]
            local = jnp.broadcast_to(
                jnp.arange(dc_cap, dtype=jnp.int32)[:, None], (dc_cap, cap_c))
            # movers: their new cell, located in the sorted dirty-cell list;
            # a miss means the host dirty set was wrong -> count it lost and
            # let the session fall back rather than under-count
            cid2 = _cell_ids(new_xyc, plan)
            lk = jnp.searchsorted(dc, cid2).astype(jnp.int32)
            found = (lk < dc_cap) & (dc[jnp.minimum(lk, dc_cap - 1)] == cid2)
            lost_cells = jnp.sum(
                jnp.where(mv_ok & ~found, 1, 0)).astype(jnp.int32)
            keys = jnp.concatenate([local.reshape(-1), lk])
            vids = jnp.concatenate([rows_vid.reshape(-1), moved])
            ok = jnp.concatenate([keep.reshape(-1), mv_ok & found])
            nvid, in_cap, _, ovc = gridlib.gather_ragged_buckets(
                keys[None], dc_cap,
                np.arange(dc_cap, dtype=np.int64) * cap_c,
                np.full(dc_cap, cap_c, np.int64), vids[None], valid=ok[None])
            nvid = jnp.where(in_cap[0], nvid[0], vb).reshape(dc_cap, cap_c)
            nok = in_cap[0].reshape(dc_cap, cap_c)
            cell_vid2 = state.cell_vid.at[dc].set(nvid, mode="drop")
            cell_val2 = state.cell_valid.at[dc].set(nok, mode="drop")
            nbr = gridlib.neighbour_bucket_ids(plan.grid_nx, plan.grid_ny)
            thresh = jnp.asarray((2.0 * plan.radius) ** 2, pos.dtype)
            partial = _occ_rows(owners, cell_vid2, cell_val2, px, py,
                                jnp.maximum(nbr, 0), nbr >= 0, thresh)
            occ2 = state.occ_partial.at[owners].set(partial, mode="drop")
            out["node_occlusion"] = jnp.sum(occ2)
            overflow = overflow + ovc[0] + lost_cells

    # -- strips: rebuild dirty strip buckets, re-sweep them -----------------
    want_ec = "edge_crossing" in m
    want_eca = "edge_crossing_angle" in m
    new_strips = []
    if want_ec or want_eca:
        me = jnp.zeros(eb + 1, bool).at[aff].set(True)
        ae_ok = aff < eb
        stats = []
        for axis_i, axis in enumerate(plan.axes):
            with jax.named_scope(f"strips.build/axis{axis_i}"):
                st = state.strips[axis_i]
                cap_s = st.eid.shape[1]
                ds = dirty_strips[axis_i]
                ds_cap = ds.shape[0]
                dsi = jnp.minimum(ds, plan.n_strips - 1)
                rows_eid = st.eid[dsi]                         # (ds, cap)
                rows_val = st.valid[dsi] & (ds < plan.n_strips)[:, None]
                keep = rows_val & ~me[rows_eid]
                local = jnp.broadcast_to(
                    jnp.arange(ds_cap, dtype=jnp.int32)[:, None],
                    (ds_cap, cap_s))
                # every new segment of an affected edge must land in a
                # dirty strip (the host unions old + new spans); count any
                # that don't as lost -> overflow -> fallback
                sf, sl, nseg = _strip_spans(pos2, edges, aff, ae_ok,
                                            st.lo, st.hi, plan.n_strips, axis)
                in_span = (ds[None, :] >= sf[:, None]) & \
                          (ds[None, :] <= sl[:, None])
                cmask = (ae_ok[:, None] & (ds < plan.n_strips)[None, :]
                         & in_span)
                ckey = jnp.broadcast_to(
                    jnp.arange(ds_cap, dtype=jnp.int32)[None, :], cmask.shape)
                ceid = jnp.broadcast_to(aff[:, None], cmask.shape)
                lost = jnp.abs(jnp.sum(nseg)
                               - jnp.sum(cmask.astype(jnp.int32)))
                keys = jnp.concatenate([local.reshape(-1), ckey.reshape(-1)])
                eids = jnp.concatenate([rows_eid.reshape(-1),
                                        ceid.reshape(-1)])
                ok = jnp.concatenate([keep.reshape(-1), cmask.reshape(-1)])
                neid, in_cap, _, ovs = gridlib.gather_ragged_buckets(
                    keys[None], ds_cap,
                    np.arange(ds_cap, dtype=np.int64) * cap_s,
                    np.full(ds_cap, cap_s, np.int64), eids[None],
                    valid=ok[None])
                neid = neid[0].reshape(ds_cap, cap_s)
                nok = in_cap[0].reshape(ds_cap, cap_s)
                eid2 = st.eid.at[ds].set(neid, mode="drop")
                val2 = st.valid.at[ds].set(nok, mode="drop")
                # values for the dirty rows, re-derived from pos2 (invalid
                # slots carry garbage values, masked in the sweep)
                row_strip = jnp.broadcast_to(dsi[:, None], (ds_cap, cap_s))
                yl, yr, th, v, u = _strip_values(
                    pos2, edges, neid.reshape(-1), row_strip.reshape(-1),
                    st.lo, st.hi, plan.n_strips, axis)
            with jax.named_scope(f"strips.sweep/axis{axis_i}/tier0"):
                shape = (ds_cap, cap_s)
                cnt_r, dev_r = _reversal_rows(
                    yl.reshape(shape), yr.reshape(shape), th.reshape(shape),
                    v.reshape(shape), u.reshape(shape), nok,
                    ideal=plan.ideal, with_angle=want_eca,
                    row_block=min(plan.strip_block, ds_cap))
                cnt2 = st.cnt.at[ds].set(cnt_r, mode="drop")
                dev2 = st.dev.at[ds].set(dev_r, mode="drop")
            stats.append((jnp.sum(cnt2), jnp.sum(dev2),
                          ovs[0] + lost.astype(jnp.int32)))
            new_strips.append(ResidentStrip(eid=eid2, valid=val2,
                                            cnt=cnt2, dev=dev2,
                                            lo=st.lo, hi=st.hi))
        crossing, ec_ov = _select_orientation(stats, want_ec, want_eca)
        out.update(crossing)
        overflow = overflow + ec_ov

    # -- min angle: re-derive moved vertices + their neighbours -------------
    ma2 = state.ma_dev
    if "minimum_angle" in m:
        with jax.named_scope("min_angle"):
            dev_rows = _ma_rows(pos2, dirty_ma, state.inc_nbr, state.inc_deg)
            ma2 = state.ma_dev.at[dirty_ma].set(dev_rows, mode="drop")
            counted = state.inc_deg >= 1
            out["minimum_angle"] = (1.0 - jnp.sum(ma2)
                                    / jnp.maximum(jnp.sum(counted), 1))

    # -- edge length variation: O(E) elementwise, recomputed in full --------
    if "edge_length_variation" in m:
        with jax.named_scope("edge_length"):
            out["edge_length_variation"] = edge_length_variation(
                pos2, edges, edge_valid=edge_valid)

    result = ReadabilityScores(overflow=overflow, **out)
    new_state = ResidentState(
        pos=pos2, cell_vid=cell_vid2, cell_valid=cell_val2,
        occ_partial=occ2, strips=tuple(new_strips), ma_dev=ma2,
        inc_nbr=state.inc_nbr, inc_deg=state.inc_deg)
    return result, new_state


def evaluate_delta(plan: ReadabilityPlan, state: ResidentState, edges,
                   n_e: int, moved_p, new_xy_p, aff_p, dirty_cells_p,
                   owners_p, dirty_strips_p, dirty_ma_p):
    """Re-evaluate after a small move, from the resident state.

    All ``*_p`` inputs are host-padded id vectors (:func:`pad_ids`) with
    out-of-range sentinels.  Returns ``(result, new_state)`` with
    ``result`` a device :class:`~repro.core.scores.ReadabilityScores`;
    a non-zero ``result.overflow`` means the delta could not preserve
    membership equality (bucket overflow / dirty-set miss) and the
    caller MUST discard ``new_state`` and re-evaluate from scratch.
    """
    return _delta_fn(
        plan, state, jnp.asarray(edges, jnp.int32),
        jnp.asarray(n_e, jnp.int32), jnp.asarray(moved_p, jnp.int32),
        jnp.asarray(new_xy_p), jnp.asarray(aff_p, jnp.int32),
        jnp.asarray(dirty_cells_p, jnp.int32),
        jnp.asarray(owners_p, jnp.int32),
        tuple(jnp.asarray(d, jnp.int32) for d in dirty_strips_p),
        jnp.asarray(dirty_ma_p, jnp.int32))
