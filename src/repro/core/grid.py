"""Spatial decomposition utilities: the paper's 'grid method' (S3.2).

The paper replaces Spark's all-pairs ``join`` + shuffle with spatial
decomposition:

* node occlusion: a 2r x 2r cell grid (S3.2.1);
* edge crossing / crossing angle: vertical strips of width ``l`` (S3.2.2/3).

TPU adaptation (see DESIGN.md S2): Spark's ``groupBy`` becomes
sort-by-key + dense capacity-padded buckets, so every downstream per-cell
computation is a fixed-shape dense block that the VPU/MXU (and the Pallas
kernels in :mod:`repro.kernels`) can chew through.  Instead of replicating
a vertex into every overlapping cell and running ``distinct`` afterwards
(the paper's approach), each vertex is assigned to the single cell
containing its centre and cells interact with a *half neighbourhood*
(self + E, N, NE, SE) so that every candidate pair is generated exactly
once — no dedup pass, which is the TPU analogue of removing the shuffle.

All functions are jit-compatible given static capacities; helpers to pick
capacities from data live at the bottom (host-side, non-jit).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

# Half-neighbourhood offsets (dx, dy) covering all adjacent unordered cell
# pairs exactly once: same-cell pairs use i<j ordering, cross-cell pairs
# use these four directed offsets.
HALF_NEIGHBOURHOOD = ((1, 0), (0, 1), (1, 1), (1, -1))

# The same unordered pair set with every offset pointing *forward* in
# flat-id order: (1, -1) (SE) is replaced by its mirror (-1, 1) (NW),
# which pairs the same cells from the other endpoint.  With row-major
# flat ids every neighbour then lives at ``c + {1, nx-1, nx, nx+1}`` —
# strictly ahead of ``c`` — so a contiguous-range cell partition needs
# exactly ONE one-sided halo of ``nx + 1`` cells from the next shard.
# (a-b)^2 == (b-a)^2 bitwise in IEEE arithmetic and the per-pair counts
# are integers, so the forward sweep is bit-identical to the
# HALF_NEIGHBOURHOOD sweep.
FORWARD_NEIGHBOURHOOD = ((1, 0), (-1, 1), (0, 1), (1, 1))

# Work counters (python side effects: bump once per eager call / per trace).
# The engine benchmark uses these to certify the fused path really does
# 2 strip builds + 2 reversal sweeps where the unfused path does 4 + 4,
# and the metric-subset tests use them to prove pruned configs never
# build the decompositions they don't need (crossing-only builds zero
# cell buckets; occlusion-only runs zero sweeps; dropping minimum_angle
# skips the vertex-key sort).  ``halo_exchanges`` certifies the
# graph-sharded path's collective budget: exactly ONE boundary-cell
# exchange per evaluation, zero for strip-only metric subsets.
# ``vertex_range_splits`` certifies that the graph-sharded path scores
# M_a by vertex range (each device its own runs of half-edges, one psum)
# and that the single-host paths never take that stage.
CALL_COUNTS = {"strip_builds": 0, "reversal_sweeps": 0, "cell_builds": 0,
               "vertex_sorts": 0, "halo_exchanges": 0,
               "vertex_range_splits": 0}


def reset_call_counts():
    for k in CALL_COUNTS:
        CALL_COUNTS[k] = 0


def count_dtype():
    """Integer dtype for pair-count accumulators.

    The old code wrote ``jnp.sum(..., dtype=jnp.int64)`` which silently
    becomes int32 unless ``jax_enable_x64`` is set — overflow semantics
    were platform-dependent.  This makes the choice explicit: int32 by
    default (counts are bounded by the planned ``cap^2 * n_buckets`` pair
    budget), int64 when the host opted into x64."""
    return jnp.int64 if jax.config.jax_enable_x64 else jnp.int32


class CellBuckets(NamedTuple):
    """Dense capacity-padded buckets of vertices binned into grid cells."""

    x: jax.Array        # (n_cells, cap) float
    y: jax.Array        # (n_cells, cap) float
    valid: jax.Array    # (n_cells, cap) bool
    counts: jax.Array   # (n_cells,) int32 true occupancy (pre-capacity-clip)
    overflow: jax.Array  # () int32: number of vertices dropped by the cap
    nx: int             # static grid width (cells)
    ny: int             # static grid height (cells)


class StripSegments(NamedTuple):
    """Per-strip 'comparable' line segments (paper S3.2.2).

    A segment is an edge restricted to one fully-spanned vertical strip;
    ``yl``/``yr`` are the y coordinates where the edge crosses the strip's
    left/right boundary lines. ``theta`` is the undirected angle of the
    *parent edge*; ``v``/``u`` its endpoints (for the shared-endpoint
    exclusion).
    """

    strip: jax.Array    # (S,) int32 strip index
    yl: jax.Array       # (S,) float
    yr: jax.Array       # (S,) float
    theta: jax.Array    # (S,) float, in [0, pi)
    v: jax.Array        # (S,) int32
    u: jax.Array        # (S,) int32
    valid: jax.Array    # (S,) bool
    overflow: jax.Array  # () int32 segments dropped by max_segments budget
    # parent edge id per segment slot (clipped to [0, E-1]; meaningful
    # only where ``valid``) — the incremental path keys its per-strip
    # dirty-set staleness checks on this
    eid: jax.Array = None  # (S,) int32


class GraphShardSpec(NamedTuple):
    """Static per-device partition of ONE layout's decompositions.

    Shard ``i`` owns strip range ``[i * strips_per_shard, ...)`` and the
    contiguous flat-cell range ``[i * cells_per_shard, ...)``; ranges
    past the end of the real strip/cell counts are empty (masked).  The
    halo is the ``halo_cells`` flat cells immediately after the owned
    range — guaranteed to be a prefix of the next shard's owned range
    because :func:`plan_graph_shards` forces ``cells_per_shard >=
    halo_cells`` — so the forward-neighbourhood sweep needs exactly one
    one-sided exchange.

    M_a splits by vertex ownership: shard ``i`` scores the vertices whose
    run of half-edges (sorted by source vertex) starts in
    ``[i * q, (i + 1) * q)``, ``q = ceil(2E / n_shards)``.  Such a run
    range holds at most ``q + deg_cap - 1`` half-edges, the static window
    each shard reads.  ``deg_cap`` is a power of two at least the graph's
    largest degree (:func:`plan_degree_cap`); 0 means not planned, and
    the window is then all 2E half-edges.  Plain ints: hashable plan data
    (part of :class:`repro.core.engine.ReadabilityPlan`, so a mesh-size
    or degree-bound change is a retrace, never a silent reuse)."""

    n_shards: int
    strips_per_shard: int
    cells_per_shard: int
    halo_cells: int
    deg_cap: int = 0


class SegmentBuckets(NamedTuple):
    """Strip segments regrouped into dense per-strip buckets."""

    yl: jax.Array       # (n_strips, cap)
    yr: jax.Array       # (n_strips, cap)
    theta: jax.Array    # (n_strips, cap)
    v: jax.Array        # (n_strips, cap) int32
    u: jax.Array        # (n_strips, cap) int32
    valid: jax.Array    # (n_strips, cap) bool
    overflow: jax.Array  # () int32


# ---------------------------------------------------------------------------
# generic bucketing (the TPU 'groupBy')
# ---------------------------------------------------------------------------

def rank_within_group(keys: jax.Array) -> jax.Array:
    """For *sorted* integer ``keys``, the 0-based rank of each element
    within its run of equal keys. Vectorized cumcount."""
    n = keys.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    # start index of each element's run: searchsorted of each key in keys
    starts = jnp.searchsorted(keys, keys, side="left").astype(jnp.int32)
    return idx - starts


def scatter_to_buckets(keys: jax.Array, n_buckets: int, cap: int,
                       *values: jax.Array, valid=None):
    """Group ``values`` by integer ``keys`` into dense ``(n_buckets, cap)``
    arrays. Elements beyond ``cap`` per bucket are dropped (counted as
    overflow).  Returns ``(bucketed_values..., valid, counts, overflow)``.
    """
    if valid is None:
        valid = jnp.ones(keys.shape, dtype=bool)
    # Push invalid entries to a trash bucket at index n_buckets.
    keys = jnp.where(valid, keys, n_buckets).astype(jnp.int32)
    order = jnp.argsort(keys, stable=True).astype(jnp.int32)
    skeys = keys[order]
    ranks = rank_within_group(skeys)
    in_cap = (ranks < cap) & (skeys < n_buckets)
    # ONE scatter routes the *source index* to its slot; the value arrays
    # follow by gathers (gathers parallelize where scatters serialize).
    dest = jnp.where(in_cap, skeys * cap + ranks, n_buckets * cap)
    src = jnp.zeros(n_buckets * cap + 1, jnp.int32)
    src = src.at[dest].set(order, mode="drop")[:-1]
    vflat = jnp.zeros(n_buckets * cap + 1, dtype=bool)
    bvalid = vflat.at[dest].set(in_cap, mode="drop")[:-1]
    out_values = []
    for val in values:
        flat = jnp.where(
            bvalid.reshape(bvalid.shape + (1,) * (val.ndim - 1)),
            val[src], jnp.zeros((), val.dtype))
        out_values.append(flat.reshape((n_buckets, cap) + val.shape[1:]))
    bvalid = bvalid.reshape(n_buckets, cap)
    # per-bucket occupancy from the sorted keys (binary search, no
    # scatter-add)
    bounds = jnp.searchsorted(skeys, jnp.arange(n_buckets + 1,
                                                dtype=jnp.int32))
    counts = (bounds[1:] - bounds[:-1]).astype(jnp.int32)
    overflow = jnp.sum(counts) - jnp.sum(bvalid)
    return (*out_values, bvalid, counts, overflow.astype(jnp.int32))


def _sort_groups_batched(keys: jax.Array, n_buckets: int):
    """Stable group-sort of ``(B, M)`` int keys in ``[0, n_buckets]``
    (``n_buckets`` = trash), independently per row.

    Fast path: pack ``(key, index)`` into ONE int32 composite and use the
    single-operand ``jnp.sort`` — XLA CPU sorts a single array ~8x
    faster than the comparator path that ``argsort``/multi-operand
    ``lax.sort`` take, and the low bits hand back the source index for
    free (stability by construction).  Falls back to stable argsort when
    the composite would not fit 31 bits.  Returns ``(idx, skeys)``, both
    ``(B, M)``: the source index and the sorted keys."""
    M = keys.shape[-1]
    kbits = max(int(n_buckets).bit_length(), 1)
    mbits = max(int(M - 1).bit_length(), 1)
    if kbits + mbits <= 31:
        iota = jnp.arange(M, dtype=jnp.int32)
        comp = jnp.sort((keys << mbits) | iota, axis=-1)
        return (comp & ((1 << mbits) - 1)), (comp >> mbits)
    idx = jnp.argsort(keys, axis=-1, stable=True).astype(jnp.int32)
    return idx, jnp.take_along_axis(keys, idx, axis=-1)


def gather_ragged_buckets(keys: jax.Array, n_buckets: int, bucket_offset,
                          bucket_cap, *values: jax.Array, valid=None):
    """Group ``values`` by integer ``keys`` into a *ragged-dense* layout:
    bucket ``k`` owns the slot range ``[bucket_offset[k],
    bucket_offset[k] + bucket_cap[k])`` of a ``(total,)`` row buffer.

    This is :func:`scatter_to_buckets` generalized two ways: per-bucket
    capacities (the occupancy-tiered sweep stores skewed strips at
    different capacities without paying the fullest strip's padding
    everywhere) and a native batch axis — ``keys`` and each value are
    ``(B, M)``, and the whole batch is grouped by ONE sort (where
    ``vmap`` would emit B comparator sorts and B scatters).  There is no
    scatter at all: after the composite sort each bucket's content is a
    *contiguous run* of the sorted row, so slot ``j`` of bucket ``k``
    is ``sorted[start[k] + j]`` — buckets materialize by pure gathers,
    which parallelize where scatters serialize.

    ``bucket_offset`` / ``bucket_cap`` are host-side ``(n_buckets,)``
    integer arrays (plan data; they define one shared slot layout for
    every batch row).  Elements beyond a bucket's capacity are dropped
    and counted.  Returns ``(bucketed_values..., valid, counts,
    overflow)`` with values/valid shaped ``(B, total)``, ``counts``
    ``(B, n_buckets)`` true occupancy, ``overflow`` ``(B,)``.
    """
    import numpy as np

    bucket_offset = np.asarray(bucket_offset, np.int64)
    bucket_cap = np.asarray(bucket_cap, np.int64)
    total = int((bucket_offset + bucket_cap).max()) if len(bucket_cap) else 0
    # host-side slot maps: owning bucket and within-bucket position of
    # every flat slot.  Buckets tile [0, total) but not necessarily in
    # bucket-index order (tiered strip layouts permute them), so walk
    # them in offset order.
    by_off = np.argsort(bucket_offset)
    slot_bucket = np.repeat(by_off.astype(np.int32), bucket_cap[by_off])
    starts = np.repeat(bucket_offset[by_off], bucket_cap[by_off])
    slot_j = (np.arange(total, dtype=np.int64) - starts).astype(np.int32)
    slot_bucket = jnp.asarray(slot_bucket)
    slot_j = jnp.asarray(slot_j)

    B, M = keys.shape
    if valid is None:
        valid = jnp.ones(keys.shape, dtype=bool)
    keys = jnp.where(valid, keys, n_buckets).astype(jnp.int32)
    idx, skeys = _sort_groups_batched(keys, n_buckets)
    probe = jnp.arange(n_buckets + 1, dtype=jnp.int32)
    bounds = jax.vmap(lambda r: jnp.searchsorted(r, probe))(skeys)
    counts = (bounds[:, 1:] - bounds[:, :-1]).astype(jnp.int32)  # (B, K)
    routed = bounds[:, n_buckets].astype(jnp.int32)              # (B,)

    start = bounds[:, :-1][:, slot_bucket]                       # (B, total)
    in_cap = slot_j[None, :] < counts[:, slot_bucket]
    src_sorted = jnp.minimum(start + slot_j[None, :], M - 1)
    src = jnp.take_along_axis(idx, src_sorted, axis=1)
    out_values = []
    for val in values:
        out_values.append(jnp.where(
            in_cap, jnp.take_along_axis(val, src, axis=1),
            jnp.zeros((), val.dtype)))
    placed = jnp.sum(in_cap, axis=1, dtype=jnp.int32)
    overflow = routed - placed
    return (*out_values, in_cap, counts, overflow)


# ---------------------------------------------------------------------------
# occlusion grid (2r x 2r cells)
# ---------------------------------------------------------------------------

def cell_indices(pos: jax.Array, radius, origin, nx: int, ny: int,
                 cell_size=None):
    """Cell (ix, iy) and flat id for each vertex centre.

    ``cell_size`` defaults to the paper's 2r; any size >= 2r keeps the
    half-neighbourhood sweep exact (a pair closer than 2r <= size still
    lands in the same or an adjacent cell), and the planner exploits that
    to keep the cell count proportional to the vertex count — a 2r grid
    over a sparse layout is mostly empty cells whose capacity padding
    dominates the dense sweep.
    """
    size = 2.0 * radius if cell_size is None else cell_size
    ix = jnp.clip(jnp.floor((pos[:, 0] - origin[0]) / size).astype(jnp.int32), 0, nx - 1)
    iy = jnp.clip(jnp.floor((pos[:, 1] - origin[1]) / size).astype(jnp.int32), 0, ny - 1)
    return ix, iy, iy * nx + ix


def build_cell_buckets(pos: jax.Array, radius, origin, nx: int, ny: int,
                       cap: int, valid=None, cell_size=None) -> CellBuckets:
    """Bin vertices into the occlusion grid (paper fig 1 A-1/A-2)."""
    CALL_COUNTS["cell_builds"] += 1
    _, _, cid = cell_indices(pos, radius, origin, nx, ny,
                             cell_size=cell_size)
    x, y, bvalid, counts, overflow = scatter_to_buckets(
        cid, nx * ny, cap, pos[:, 0], pos[:, 1], valid=valid)
    return CellBuckets(x=x, y=y, valid=bvalid, counts=counts,
                       overflow=overflow, nx=nx, ny=ny)


def neighbour_bucket_ids(nx: int, ny: int):
    """For each cell, the flat ids of its half-neighbourhood cells.

    Returns ``(n_cells, 4)`` int32 with -1 where the neighbour falls
    outside the grid. Used to pair bucket ``c`` with ``nbr[c, k]``.
    """
    cx = jnp.arange(nx * ny, dtype=jnp.int32) % nx
    cy = jnp.arange(nx * ny, dtype=jnp.int32) // nx
    ids = []
    for dx, dy in HALF_NEIGHBOURHOOD:
        ox, oy = cx + dx, cy + dy
        ok = (ox >= 0) & (ox < nx) & (oy >= 0) & (oy < ny)
        ids.append(jnp.where(ok, oy * nx + ox, -1))
    return jnp.stack(ids, axis=1)


# ---------------------------------------------------------------------------
# vertical strips for edge crossing (paper S3.2.2)
# ---------------------------------------------------------------------------

def slot_edge_ids(offsets: jax.Array, max_segments: int) -> jax.Array:
    """Parent edge of every segment slot: for inclusive segment offsets
    ``(..., E)`` (a cumsum of non-negative counts, so sorted), return
    ``(..., max_segments)`` int32 equal to
    ``searchsorted(offsets, arange(max_segments), side="right")``.

    The number of offsets ``<= slot`` is a histogram of the offsets over
    ``[0, max_segments)`` followed by a cumsum: one scatter-add of E ones
    and ``max_segments`` summed counts, where the binary search gathers
    ``max_segments * log2 E`` elements in a ``while`` loop.  Offsets at or past ``max_segments``
    land in a dropped last bin.  Exact integers, so identical to the
    search for every slot.
    """
    with jax.named_scope("slot_edges"):
        lead = offsets.shape[:-1]
        rows = offsets.reshape(math.prod(lead), offsets.shape[-1])
        b = jnp.arange(rows.shape[0], dtype=jnp.int32)[:, None]
        hist = jnp.zeros((rows.shape[0], max_segments + 1), jnp.int32)
        hist = hist.at[b, jnp.minimum(rows, max_segments)].add(
            1, indices_are_sorted=True)
        eid = jnp.cumsum(hist[:, :max_segments], axis=-1, dtype=jnp.int32)
        return eid.reshape(*lead, max_segments)


def build_strip_segments(pos: jax.Array, edges: jax.Array, n_strips: int,
                         max_segments: int, *, axis: int = 0,
                         domain=None, edge_valid=None) -> StripSegments:
    """Clip edges into per-strip comparable segments.

    An edge contributes a segment to strip ``s`` iff it crosses *both* of
    the strip's boundary lines (the paper's comparability condition); its
    ``yl``/``yr`` are the crossing ordinates. Edges that never fully span a
    strip (short or axis-parallel ones) contribute nothing — that is the
    enhanced algorithm's (bounded) approximation.

    ``axis=0``: vertical strips over x (paper default). ``axis=1``:
    horizontal strips (used by the 'both orientations' accuracy trick,
    Table 4) — implemented by swapping the roles of x and y.
    """
    from repro.core.geometry import segment_theta

    CALL_COUNTS["strip_builds"] += 1

    p = pos[edges[:, 0]]
    q = pos[edges[:, 1]]
    x1, y1 = p[:, axis], p[:, 1 - axis]
    x2, y2 = q[:, axis], q[:, 1 - axis]
    theta = segment_theta(p[:, 0], p[:, 1], q[:, 0], q[:, 1])
    if edge_valid is None:
        edge_valid = jnp.ones(edges.shape[0], dtype=bool)

    if domain is None:
        lo = jnp.min(jnp.where(edge_valid, jnp.minimum(x1, x2), jnp.inf))
        hi = jnp.max(jnp.where(edge_valid, jnp.maximum(x1, x2), -jnp.inf))
    else:
        lo, hi = domain
    width = jnp.maximum((hi - lo) / n_strips, 1e-30)

    xa = jnp.minimum(x1, x2)
    xb = jnp.maximum(x1, x2)
    # strips fully spanned: s in [ceil((xa-lo)/w), floor((xb-lo)/w) - 1]
    s_first = jnp.ceil((xa - lo) / width).astype(jnp.int32)
    s_last = jnp.floor((xb - lo) / width).astype(jnp.int32) - 1
    s_first = jnp.clip(s_first, 0, n_strips - 1)
    s_last = jnp.clip(s_last, -1, n_strips - 1)
    n_seg = jnp.where(edge_valid, jnp.maximum(0, s_last - s_first + 1), 0)

    offsets = jnp.cumsum(n_seg)                      # inclusive
    total = offsets[-1]
    starts = offsets - n_seg                          # exclusive
    slot = jnp.arange(max_segments, dtype=jnp.int32)
    eid = jnp.minimum(slot_edge_ids(offsets, max_segments),
                      edges.shape[0] - 1)
    valid = slot < total
    s_local = slot - starts[eid]
    strip = s_first[eid] + s_local

    ex1, ey1, ex2, ey2 = x1[eid], y1[eid], x2[eid], y2[eid]
    # y along the edge at the two boundary lines of the strip
    dx = ex2 - ex1
    slope = (ey2 - ey1) / jnp.where(jnp.abs(dx) < 1e-30, 1e-30, dx)
    bl = lo + strip.astype(pos.dtype) * width
    br = bl + width
    yl = ey1 + (bl - ex1) * slope
    yr = ey1 + (br - ex1) * slope

    return StripSegments(
        strip=jnp.where(valid, strip, n_strips),
        yl=yl, yr=yr, theta=theta[eid],
        v=edges[eid, 0], u=edges[eid, 1],
        valid=valid,
        overflow=jnp.maximum(total - max_segments, 0).astype(jnp.int32),
        eid=eid,
    )


def build_strip_segments_batched(pos: jax.Array, edges: jax.Array,
                                 n_strips: int, max_segments: int, *,
                                 axis: int = 0, edge_valid=None,
                                 safe_theta: bool = False) -> StripSegments:
    """Batched :func:`build_strip_segments`: ``(B, V, 2)`` layouts of one
    graph -> :class:`StripSegments` with ``(B, max_segments)`` fields and
    ``(B,)`` overflow.

    Mirrors the single-layout function formula-for-formula (same
    elementwise op sequence, so boundary ordinates round identically and
    integer crossing counts stay bit-compatible with the looped path;
    both find each slot's parent edge with :func:`slot_edge_ids`); only
    the indexing machinery grows a leading batch axis.  Strip ids
    stay *per-layout* (in ``[0, n_strips]``, ``n_strips`` = trash) —
    :func:`gather_ragged_buckets` consumes the ``(B, max_segments)`` key
    rows directly, one sorted row per layout.

    ``safe_theta=True`` swaps the parent-edge angle to
    :func:`~repro.core.geometry.segment_theta_safe`: identical forward
    values, but a finite (zero) gradient on zero-length edges instead of
    ``arctan2(0, 0)``'s NaN partials — the differentiable soft path
    (:mod:`repro.core.soft`) needs this because one NaN partial poisons
    the whole backward pass even under a zero cotangent.  The exact
    paths keep the default (same ops as the single-layout builder).
    """
    from repro.core.geometry import segment_theta, segment_theta_safe

    CALL_COUNTS["strip_builds"] += 1

    B = pos.shape[0]
    p = pos[:, edges[:, 0]]                          # (B, E, 2)
    q = pos[:, edges[:, 1]]
    x1, y1 = p[..., axis], p[..., 1 - axis]
    x2, y2 = q[..., axis], q[..., 1 - axis]
    theta_fn = segment_theta_safe if safe_theta else segment_theta
    theta = theta_fn(p[..., 0], p[..., 1], q[..., 0], q[..., 1])
    if edge_valid is None:
        edge_valid = jnp.ones(edges.shape[0], dtype=bool)
    ev = jnp.broadcast_to(edge_valid, x1.shape)      # one mask, all layouts

    lo = jnp.min(jnp.where(ev, jnp.minimum(x1, x2), jnp.inf),
                 axis=1, keepdims=True)
    hi = jnp.max(jnp.where(ev, jnp.maximum(x1, x2), -jnp.inf),
                 axis=1, keepdims=True)
    # zero valid edges leaves the extent empty (lo = +inf): pin it to a
    # finite dummy so the (fully masked) boundary ordinates below stay
    # finite — ``inf * 0`` would plant forward NaNs that the hard
    # comparisons shrug off but that poison gradients through the soft
    # path (0 cotangent x NaN value is still NaN in the backward pass)
    some = jnp.isfinite(lo)
    lo = jnp.where(some, lo, 0.0)
    hi = jnp.where(some, hi, 1.0)
    width = jnp.maximum((hi - lo) / n_strips, 1e-30)

    xa = jnp.minimum(x1, x2)
    xb = jnp.maximum(x1, x2)
    s_first = jnp.ceil((xa - lo) / width).astype(jnp.int32)
    s_last = jnp.floor((xb - lo) / width).astype(jnp.int32) - 1
    s_first = jnp.clip(s_first, 0, n_strips - 1)
    s_last = jnp.clip(s_last, -1, n_strips - 1)
    n_seg = jnp.where(ev, jnp.maximum(0, s_last - s_first + 1), 0)

    offsets = jnp.cumsum(n_seg, axis=1)              # (B, E) inclusive
    total = offsets[:, -1:]                          # (B, 1)
    starts = offsets - n_seg
    slot = jnp.arange(max_segments, dtype=jnp.int32)
    eid = jnp.minimum(slot_edge_ids(offsets, max_segments),
                      edges.shape[0] - 1)
    valid = slot[None, :] < total
    s_local = slot[None, :] - jnp.take_along_axis(starts, eid, axis=1)
    strip = jnp.take_along_axis(s_first, eid, axis=1) + s_local

    ga = lambda a: jnp.take_along_axis(a, eid, axis=1)
    ex1, ey1, ex2, ey2 = ga(x1), ga(y1), ga(x2), ga(y2)
    dx = ex2 - ex1
    slope = (ey2 - ey1) / jnp.where(jnp.abs(dx) < 1e-30, 1e-30, dx)
    bl = lo + strip.astype(pos.dtype) * width
    br = bl + width
    yl = ey1 + (bl - ex1) * slope
    yr = ey1 + (br - ex1) * slope

    return StripSegments(
        strip=jnp.where(valid, strip, n_strips),
        yl=yl, yr=yr, theta=ga(theta),
        v=edges[eid, 0], u=edges[eid, 1],
        valid=valid,
        overflow=jnp.maximum(total[:, 0] - max_segments, 0).astype(jnp.int32),
        eid=eid,
    )


def bucketize_segments(segs: StripSegments, n_strips: int, cap: int) -> SegmentBuckets:
    """Group comparable segments into dense per-strip buckets (the TPU
    analogue of the paper's per-strip groupBy, fig 1 B-3)."""
    yl, yr, theta, v, u, bvalid, _, overflow = scatter_to_buckets(
        segs.strip, n_strips, cap, segs.yl, segs.yr, segs.theta,
        segs.v, segs.u, valid=segs.valid)
    return SegmentBuckets(yl=yl, yr=yr, theta=theta, v=v, u=u,
                          valid=bvalid, overflow=overflow + segs.overflow)


# ---------------------------------------------------------------------------
# host-side capacity planning (not jit)
# ---------------------------------------------------------------------------

def _round_up(n: int, multiple: int) -> int:
    return int(-(-n // multiple) * multiple)


def occlusion_cell_size(lo, hi, radius, n_points,
                        target_occupancy: float = 8.0) -> float:
    """Pick the occlusion cell size: at least the paper's 2r (exactness),
    but coarse enough that cells average ~``target_occupancy`` vertices.

    A 2r grid over a sparse layout is dominated by empty capacity-padded
    cells (n_cells x cap^2 work); coarsening until occupancy matches the
    padding keeps the dense sweep proportional to the vertex count while
    staying exact (any cell size >= 2r preserves the half-neighbourhood
    coverage argument)."""
    size = 2.0 * float(radius)
    area = float(hi[0] - lo[0]) * float(hi[1] - lo[1])
    if n_points > 0 and area > 0 and target_occupancy > 0:
        size = max(size, (area * target_occupancy / n_points) ** 0.5)
    return size


def plan_occlusion_grid(pos, radius, pad: int = 8, cap_multiple: int = 8,
                        target_occupancy: float = 8.0):
    """Pick grid geometry / capacity from concrete data (host side).

    ``pos`` is ``(V, 2)`` or a batch ``(B, V, 2)``; a batched plan uses a
    shared bounding box and sizes the capacity to the max per-layout
    occupancy.  Returns ``(origin, nx, ny, cap, cell_size)``."""
    import numpy as np

    pos_b = np.asarray(pos)
    if pos_b.ndim == 2:
        pos_b = pos_b[None]
    if pos_b.shape[1] == 0:
        # degenerate V=0 request: a 1x1 grid nothing falls into (the
        # n_valid masks exclude everything anyway) instead of a numpy
        # reduction error on the empty extent
        return (0.0, 0.0), 1, 1, _round_up(pad, cap_multiple), \
            2.0 * float(radius)
    lo = pos_b.reshape(-1, 2).min(axis=0) - 1e-6
    hi = pos_b.reshape(-1, 2).max(axis=0) + 1e-6
    size = occlusion_cell_size(lo, hi, radius, pos_b.shape[1],
                               target_occupancy)
    nx = max(1, int(np.ceil((hi[0] - lo[0]) / size)))
    ny = max(1, int(np.ceil((hi[1] - lo[1]) / size)))
    occ_max = 0
    for p in pos_b:
        ix = np.clip(((p[:, 0] - lo[0]) / size).astype(np.int64), 0, nx - 1)
        iy = np.clip(((p[:, 1] - lo[1]) / size).astype(np.int64), 0, ny - 1)
        occ_max = max(occ_max, int(np.bincount(iy * nx + ix,
                                               minlength=nx * ny).max()))
    cap = _round_up(occ_max + pad, cap_multiple)
    return (float(lo[0]), float(lo[1])), nx, ny, cap, size


def plan_strip_occupancy(pos, edges, n_strips: int, pad: float = 1.25,
                         axis: int = 0):
    """Segment budget + exact per-strip occupancy from concrete data.

    Returns ``(max_segments, per_strip)`` where ``per_strip`` is the
    ``(n_strips,)`` int64 true occupancy (no headroom applied) — the raw
    material for both the flat capacity (:func:`plan_strips`) and the
    occupancy tiers (:func:`plan_strip_tiers`)."""
    import numpy as np

    pos = np.asarray(pos)
    edges = np.asarray(edges)
    if edges.shape[0] == 0:
        # degenerate E=0 request: minimal budget, empty occupancy — the
        # strip build sees only masked-out padded edges downstream
        return _round_up(1 + 64, 128), np.zeros(n_strips, np.int64)
    x = pos[:, axis]
    x1, x2 = x[edges[:, 0]], x[edges[:, 1]]
    lo, hi = x1.min(), x2.max()
    lo = min(lo, x2.min())
    hi = max(hi, x1.max())
    width = max((hi - lo) / n_strips, 1e-30)
    xa, xb = np.minimum(x1, x2), np.maximum(x1, x2)
    s_first = np.clip(np.ceil((xa - lo) / width).astype(np.int64), 0, n_strips - 1)
    s_last = np.clip(np.floor((xb - lo) / width).astype(np.int64) - 1, -1, n_strips - 1)
    n_seg = np.maximum(0, s_last - s_first + 1)
    total = int(n_seg.sum())
    max_segments = _round_up(max(int(total * pad), 1) + 64, 128)
    # exact per-strip occupancy via difference array
    first = s_first[n_seg > 0]
    last = s_last[n_seg > 0]
    diff = np.zeros(n_strips + 1, dtype=np.int64)
    np.add.at(diff, first, 1)
    np.add.at(diff, last + 1, -1)
    per_strip = np.cumsum(diff[:-1])
    return max_segments, per_strip


def plan_strips(pos, edges, n_strips: int, pad: float = 1.25,
                cap_multiple: int = 8, axis: int = 0):
    """Pick max_segments and per-strip capacity from concrete data.

    Both the total segment budget and the per-strip capacity carry the
    ``pad`` headroom factor, so a plan made from one representative
    layout keeps serving perturbed siblings (batched candidates, drifting
    optimization iterates, padded serving traffic) without tripping the
    overflow counter."""
    max_segments, per_strip = plan_strip_occupancy(pos, edges, n_strips,
                                                   pad=pad, axis=axis)
    cap = _round_up(int(per_strip.max() * pad) + 8, cap_multiple)
    return max_segments, cap


def plan_graph_shards(n_strips: int, nx: int, ny: int,
                      n_shards: int, deg_cap: int = 0) -> GraphShardSpec:
    """Partition strips and grid cells contiguously over ``n_shards``.

    ``cells_per_shard`` is clamped to at least ``nx + 1`` (the halo
    width): the forward-neighbourhood sweep of owned cell ``c`` reads at
    most ``c + nx + 1``, so a halo of ``nx + 1`` cells that is a prefix
    of the *next* shard's owned range covers every cross-boundary pair
    with a single one-sided exchange.  Trailing shards whose ranges fall
    past ``n_strips`` / ``nx * ny`` simply own nothing (their masks are
    empty and they contribute zero to every psum)."""
    n_shards = max(1, int(n_shards))
    halo = int(nx) + 1
    strips_per = -(-int(n_strips) // n_shards)
    cells_per = max(-(-(int(nx) * int(ny)) // n_shards), halo)
    return GraphShardSpec(n_shards=n_shards, strips_per_shard=strips_per,
                          cells_per_shard=cells_per, halo_cells=halo,
                          deg_cap=int(deg_cap))


def plan_degree_cap(edges, n_valid_edges=None) -> int:
    """The power of two (floor 2) at least the largest vertex degree of
    ``edges[:n_valid_edges]``: a self-loop counts twice, as its two
    half-edges do.  Host side; sizes the graph-sharded M_a window
    (:class:`GraphShardSpec`)."""
    import numpy as np

    e = np.asarray(edges)
    if n_valid_edges is not None:
        e = e[:int(n_valid_edges)]
    e = e.reshape(-1)
    top = int(np.bincount(e).max()) if e.size else 0
    return _next_pow2(top, floor=2)


def _next_pow2(n: int, floor: int = 8) -> int:
    v = int(floor)
    while v < n:
        v *= 2
    return v


def tiers_from_caps(cap_per_strip, max_tiers: int = 3,
                    cap_multiple: int = 8):
    """Collapse per-strip capacities into <= ``max_tiers`` tiers at pow2
    boundaries.

    Strips are grouped by the pow2 level covering their need (keeping the
    ``max_tiers`` largest distinct levels; strips below the smallest kept
    level join it), but each tier's *capacity* is the rounded max need
    inside the tier, not the pow2 ceiling — so the top tier's cap equals
    the old flat cap and the tiered pair work is never larger than the
    flat sweep's, on uniform inputs included.  Returns ``(caps, counts,
    order)``: tier capacities descending, strips per tier, and the strip
    ids sorted by (tier, strip id) — all plain int tuples, hashable plan
    data."""
    import numpy as np

    need = np.maximum(np.asarray(cap_per_strip, np.int64), 1)
    levels = np.array([_next_pow2(int(c)) for c in need], dtype=np.int64)
    kept = sorted(set(levels.tolist()), reverse=True)[:max_tiers]
    kept_asc = sorted(kept)
    level_s = np.array([min(k for k in kept_asc if k >= l) for l in levels],
                       dtype=np.int64)
    order = np.argsort(-level_s, kind="stable")
    caps, counts = [], []
    for lev in sorted(set(level_s.tolist()), reverse=True):
        member = level_s == lev
        caps.append(_round_up(int(need[member].max()), cap_multiple))
        counts.append(int(member.sum()))
    return tuple(caps), tuple(counts), tuple(int(i) for i in order)


def plan_strip_tiers(per_strip_occupancy, pad: float = 1.25,
                     pad_add: int = 8, max_tiers: int = 3):
    """Occupancy tiers from true per-strip occupancy (host side).

    Real layouts are skewed (power-law graphs concentrate segments in few
    strips); a flat capacity makes every strip pay the fullest strip's
    ``cap^2`` pair tile.  Each strip's needed capacity carries the same
    ``pad`` headroom as :func:`plan_strips`, then strips collapse into
    <= ``max_tiers`` pow2 capacity tiers (static plan data, so shapes
    stay jit-friendly)."""
    import numpy as np

    occ = np.asarray(per_strip_occupancy, np.int64)
    need = np.maximum((occ * pad).astype(np.int64) + pad_add, 8)
    return tiers_from_caps(need, max_tiers=max_tiers)
