"""Fused readability engine: plan once, evaluate many (fast path).

The paper's point is that readability evaluation must be cheap enough to
sit *inside* layout-generation loops.  The old eager per-metric path
paid per-call overhead that defeats that: capacities re-planned on
the host every call, edge crossing and crossing angle each rebuilding the
identical strip decomposition and each rerunning the O(cap^2 * strips)
reversal sweep per orientation, and every metric forcing its own
device->host sync.  (The public front door over this module is
:mod:`repro.api`: an :class:`~repro.core.keys.EvalConfig` maps onto
:func:`plan_readability` via ``EvalConfig.plan_kwargs``, and results are
the shared :class:`~repro.core.scores.ReadabilityScores` pytree.)

This module splits the work:

* **Plan** (:func:`plan_readability`, host side, once per graph
  topology/extent): occlusion-grid dims + capacity, per-orientation strip
  segment budgets + capacities — everything that must be a *static* shape.
  The resulting :class:`ReadabilityPlan` is hashable and is passed to the
  jitted evaluators as a static argument, so re-evaluating under the same
  plan never retraces. Capacities carry padding headroom; if the layout
  drifts far enough to overflow them, the ``overflow`` counter in the
  result says so — replan then.

* **Evaluate** (:func:`evaluate_planned`, jitted, many times): all five
  metrics in ONE traced program with shared decompositions.  Data flow::

      pos ──> cell buckets ────────────────────────────> N_c        (build x1)
      pos ──> strip segments ──> per-strip buckets ──┐
              (per orientation,                      ├─> fused reversal
               built ONCE and shared                 │   sweep ──> (E_c count,
               by E_c *and* E_ca)                    ┘              E_ca dev sum)
      pos ──> half-edge sort ──> M_a;   pos ──> edge lengths ──> M_l

  The per-strip reversal sweep — the dominant O(cap^2 * strips) cost — runs
  once per orientation and yields the crossing count *and* the angle
  deviation sum together (:func:`fused_reversal_block` is the single
  source of truth for that formula; the unfused per-metric paths and the
  ``shard_map`` drivers in :mod:`repro.distributed.gridded` reuse it).
  With ``orientation='both'`` that is 2 strip builds + 2 sweeps where the
  unfused path does 4 + 4. The best orientation is selected with
  ``jnp.where`` on device — no per-orientation host sync — and all scalars
  come back as one device tuple: one transfer instead of five.

* **Batch** (:func:`evaluate_layouts`): a *natively batched* program over
  B candidate layouts of the same graph — one dispatch for a whole
  population, the entry point for layout-optimization loops (see
  ``examples/layout_optimization.py``).  Not a ``vmap``: vmapped stable
  argsort/scatter made the batched path *slower* than a Python loop of
  single-layout jits (0.73x at |V|=1k).  Instead every bucketing step
  (cell grid and strip buckets) groups the whole batch with ONE
  composite-key sort and materializes buckets by pure gathers
  (:func:`repro.core.grid.gather_ragged_buckets` — no scatter at all),
  and ONE reversal sweep per orientation covers the
  ``(B * n_strips, cap)`` rows.  Integer metrics are bit-identical to
  looping the single-layout path.

* **Occupancy tiers**: real layouts are skewed — power-law graphs
  concentrate segments in few strips — and a flat per-strip capacity
  makes every strip pay the fullest strip's dense ``cap^2`` pair tile.
  The plan sorts strips by planned occupancy into <= 3 pow2 capacity
  tiers (:func:`repro.core.grid.plan_strip_tiers`; tier boundaries are
  host-side plan data, so shapes stay static) and both the single-layout
  and batched paths sweep each tier at its own capacity via the ragged
  one-sort gather bucketing (:func:`repro.core.grid.gather_ragged_buckets`).
  :func:`fused_reversal_block` stays the single source of truth for the
  reversal formula; tiering only changes the float summation *order* of
  the E_ca deviation (counts are exact).

``use_kernels=True`` routes the per-strip reversal sweep through the
Pallas TPU kernel (:func:`repro.kernels.ops.strip_reversal_op`) and the
node-occlusion count through the tiled pairwise Pallas kernel
(:func:`repro.kernels.ops.occlusion_count_op`; exact, so it agrees with
the gridded count bit-for-bit) instead of the jnp paths; counts are
identical, the float deviation sum may differ in rounding (different
summation order).  The exact-method Pallas routes
(``segment_crossing``, ``crossing_angle_sum``) hang off
``evaluate_layout(method='exact', use_kernels=True)`` in
:mod:`repro.core.metrics`.

**Padding / bucketing contract** (the serving fast path, see
:mod:`repro.launch.session`): the evaluators accept optional
``n_valid_vertices`` / ``n_valid_edges`` *device* scalars.  When given,
only ``pos[:n_valid_vertices]`` and ``edges[:n_valid_edges]`` exist as
far as every metric is concerned — padded tail vertices are excluded from
the occlusion grid and the M_a mean, padded tail edges from the strip
build, M_a, M_l, and both crossing metrics.  Because the scalars are
traced (not static), ONE plan + ONE jit cache entry serves every graph of
one topology padded up to its shape bucket, whatever its natural size.
Integer metrics (N_c, E_c) are bit-identical between natural-size and
bucket-padded evaluation; float metrics agree to rounding (different
reduction shapes).  Padded vertices should be parked outside the layout
extent (see ``session.PARK``), but correctness rests on the masks, not
the park position.  If a drifting layout outgrows the plan's capacities
the result's ``overflow`` counter reports it —
:func:`replan_on_overflow` then grows the plan (fresh capacities from the
offending layout, floored at ``growth`` x the old ones) for a retry.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import grid as gridlib
from repro.core import crossing_angle as _calib
from repro.core.edge_length import (edge_length_variation,
                                    edge_length_variation_batched)
from repro.core.geometry import TWO_PI, directed_angle
from repro.core.min_angle import minimum_angle, minimum_angle_batched
from repro.core.occlusion import (count_occlusions_gridded,
                                  count_occlusions_gridded_batched)
from repro.core.scores import ReadabilityScores

# The five paper metrics (re-exported by repro.core.metrics).
ALL_METRICS = ("node_occlusion", "minimum_angle", "edge_length_variation",
               "edge_crossing", "edge_crossing_angle")

# The canonical ideal crossing angle (70 deg, Huang et al. 2008) as a
# plan-hashable Python float; the float32 roundtrip of the one constant in
# crossing_angle keeps on-device comparisons bit-compatible with it.
DEFAULT_IDEAL = float(_calib.DEFAULT_IDEAL)

_AXES = {"vertical": (0,), "horizontal": (1,), "both": (0, 1)}

# Number of times the engine's evaluators have been *traced* (not called);
# a second call with the same plan and shapes must not bump this.
_trace_count = 0


def trace_count() -> int:
    """How many times the fused evaluator body has been traced."""
    return _trace_count


@dataclasses.dataclass(frozen=True)
class ReadabilityPlan:
    """Host-side static plan: everything shape-like, hashable, jit-static.

    Built by :func:`plan_readability`; fields mirror what the unfused
    per-metric paths re-derive on every call.
    """

    radius: float
    ideal: float
    n_strips: int
    axes: tuple                 # strip orientations, subset of (0, 1)
    metrics: tuple              # subset of ALL_METRICS
    grid_origin: tuple          # (x0, y0) of the occlusion grid
    grid_nx: int
    grid_ny: int
    cell_cap: int
    grid_cell_size: float       # >= 2*radius (coarsened on sparse layouts)
    strip_plans: tuple          # ((max_segments, cap), ...) aligned w/ axes
    cell_block: int = 512
    strip_block: int = 256
    # occupancy tiers per orientation: ((caps, counts, order), ...) with
    # caps the <=3 pow2 tier capacities (descending), counts the strips
    # per tier, order the strip ids sorted by (tier, id).  () disables
    # tiering (one flat tier at the strip_plans cap).
    strip_tiers: tuple = ()
    # compute dtype of the traced program ("float32" | "bfloat16"); part
    # of the plan so a precision change retraces instead of reusing a
    # cache entry compiled for the other dtype
    precision: str = "float32"
    # graph-axis sharding spec (:class:`repro.core.grid.GraphShardSpec`)
    # when this plan drives ``backend="graph_sharded"``; None on
    # single-host plans.  Hashable plan data, so a mesh-size change is a
    # retrace, never a silent reuse of another mesh's program.
    graph_shard: tuple = None
    # resident-partials metadata for the incremental path
    # (:mod:`repro.core.incremental`): ``("delta", deg_cap)`` with
    # ``deg_cap`` the static per-vertex incidence capacity of the
    # resident min-angle state.  None (the default) on plans that never
    # primed a resident state; replans rebuild from scratch with
    # ``resident=None``, so a replanned layout simply re-primes.
    # Hashable plan data — ``prime_state``/``evaluate_delta`` jit-key
    # on the plan, so a capacity change retraces.
    resident: tuple = None

    @property
    def orientation(self) -> str:
        for name, axes in _AXES.items():
            if axes == self.axes:
                return name
        return str(self.axes)

    @property
    def dtype(self):
        return jnp.bfloat16 if self.precision == "bfloat16" else jnp.float32


# The engine's evaluators return the shared typed pytree; the old name
# stays importable for existing call sites.
EngineResult = ReadabilityScores


# ---------------------------------------------------------------------------
# the fused per-strip reversal pass (single source of truth)
# ---------------------------------------------------------------------------

def fused_reversal_block(yl, yr, theta, v, u, valid, *, ideal,
                         with_angle: bool = True, reduce: str = "all"):
    """Dense reversal sweep over a ``(B, cap)`` block of strip buckets.

    Returns ``(count, deviation_sum)``: the crossing count (order
    reversals between the strip's boundary ordinates, shared endpoints
    excluded) and — fused on the same pair mask — the crossing-angle
    deviation sum ``sum |ideal - a_c| / ideal``.  Every reversal-sweep
    consumer (unfused per-metric paths, the engine, the shard_map
    drivers, and as formula reference the Pallas kernel) goes through
    this function so count and angle can never drift apart.

    ``reduce='all'`` (default) returns scalars; ``reduce='rows'`` returns
    per-strip ``(B,)`` partial sums — the occupancy-tiered and natively
    batched sweeps need per-row sums to reassemble per-layout totals.
    Counts use :func:`repro.core.grid.count_dtype` (explicit int32 unless
    x64 is enabled; the old ``dtype=jnp.int64`` silently degraded to
    int32 anyway).
    """
    axes = (1, 2) if reduce == "rows" else None
    rev = (yl[:, :, None] < yl[:, None, :]) & (yr[:, :, None] > yr[:, None, :])
    shared = ((v[:, :, None] == v[:, None, :]) |
              (v[:, :, None] == u[:, None, :]) |
              (u[:, :, None] == v[:, None, :]) |
              (u[:, :, None] == u[:, None, :]))
    mask = rev & ~shared & valid[:, :, None] & valid[:, None, :]
    cnt = jnp.sum(jnp.where(mask, 1, 0), axis=axes,
                  dtype=gridlib.count_dtype())
    if not with_angle:
        zero = (jnp.zeros(yl.shape[0], yl.dtype) if reduce == "rows"
                else jnp.zeros((), yl.dtype))
        return cnt, zero
    ideal = jnp.asarray(ideal, yl.dtype)
    d = jnp.abs(theta[:, :, None] - theta[:, None, :])
    a_c = jnp.minimum(d, jnp.pi - d)
    dev = jnp.abs(ideal - a_c) / ideal
    dev_sum = jnp.sum(jnp.where(mask, dev, 0.0), axis=axes)
    return cnt, dev_sum


def fused_reversal_stats(buckets: gridlib.SegmentBuckets, *, ideal=1.0,
                         strip_block: int = 256, with_angle: bool = True,
                         use_kernels: bool = False):
    """All-strip reversal stats: ONE sweep -> ``(count, deviation_sum)``.

    Blocked ``lax.map`` over strips by default; ``use_kernels=True``
    dispatches the Pallas per-strip kernel instead.
    """
    gridlib.CALL_COUNTS["reversal_sweeps"] += 1
    if use_kernels:
        from repro.kernels.ops import strip_reversal_op
        return strip_reversal_op(buckets, ideal=float(ideal),
                                 with_angle=with_angle)

    n_strips = buckets.yl.shape[0]
    cap = buckets.yl.shape[1]
    # keep the (strip_block, cap, cap) pair tiles within a fixed element
    # budget — dense graphs can have cap in the thousands
    strip_block = max(1, min(strip_block, (1 << 26) // max(cap * cap, 1)))
    n_blocks = -(-n_strips // strip_block)
    pad = n_blocks * strip_block

    def padc(a, fill):
        extra = pad - n_strips
        if extra == 0:
            return a
        return jnp.concatenate(
            [a, jnp.full((extra,) + a.shape[1:], fill, a.dtype)])

    yl = padc(buckets.yl, 0.0)
    yr = padc(buckets.yr, 0.0)
    th = padc(buckets.theta, 0.0)
    v = padc(buckets.v, -1)
    u = padc(buckets.u, -2)
    ok = padc(buckets.valid, False)

    def block_fn(b0):
        sl = lambda a: lax.dynamic_slice_in_dim(a, b0, strip_block, axis=0)
        return fused_reversal_block(sl(yl), sl(yr), sl(th), sl(v), sl(u),
                                    sl(ok), ideal=ideal,
                                    with_angle=with_angle)

    starts = jnp.arange(0, pad, strip_block, dtype=jnp.int32)
    counts, devs = lax.map(block_fn, starts)
    return jnp.sum(counts), jnp.sum(devs)


# ---------------------------------------------------------------------------
# occupancy-tiered sweep (ragged per-strip capacities, shared by the
# single-layout and natively batched paths)
# ---------------------------------------------------------------------------

def _reversal_rows(yl, yr, th, v, u, ok, *, ideal, with_angle: bool,
                   row_block: int):
    """Blocked per-row reversal sweep: ``(rows, cap)`` buckets ->
    ``((rows,) count, (rows,) dev_sum)`` via :func:`fused_reversal_block`.
    """
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
        return fused_reversal_block(sl(yl), sl(yr), sl(th), sl(v), sl(u),
                                    sl(ok), ideal=ideal,
                                    with_angle=with_angle, reduce="rows")

    starts = jnp.arange(0, pad, row_block, dtype=jnp.int32)
    counts, devs = lax.map(block_fn, starts)
    return counts.reshape(pad)[:rows], devs.reshape(pad)[:rows]


def _tier_layout(plan: "ReadabilityPlan", axis_i: int):
    """Host-side ragged bucket layout for one strip orientation.

    Decodes the plan's occupancy tiers into per-strip (offset, capacity)
    arrays plus per-tier slabs.  Falls back to one flat tier at the
    orientation's planned cap when the tier data is absent or
    inconsistent with ``strip_plans`` (e.g. a hand-edited plan that
    shrank the flat cap — capacity starvation tests rely on the flat cap
    staying authoritative).  Returns ``(strip_offset, strip_cap, total,
    slabs)`` with numpy arrays and ``slabs = ((flat_offset, n_strips_t,
    cap_t), ...)``."""
    n_strips = plan.n_strips
    _, cap = plan.strip_plans[axis_i]
    tiers = (plan.strip_tiers[axis_i]
             if axis_i < len(plan.strip_tiers) else ())
    ok = (len(tiers) == 3 and len(tiers[0]) == len(tiers[1])
          and sum(tiers[1]) == n_strips and len(tiers[2]) == n_strips
          and sorted(tiers[2]) == list(range(n_strips))
          and max(tiers[0]) <= cap)
    caps, counts, order = (tiers if ok else
                           ((cap,), (n_strips,), tuple(range(n_strips))))
    order_np = np.asarray(order, np.int64)
    pos_caps = np.repeat(np.asarray(caps, np.int64),
                         np.asarray(counts, np.int64))
    pos_off = np.concatenate([[0], np.cumsum(pos_caps)])[:-1]
    total = int(pos_caps.sum())
    strip_cap = np.zeros(n_strips, np.int32)
    strip_off = np.zeros(n_strips, np.int32)
    strip_cap[order_np] = pos_caps
    strip_off[order_np] = pos_off
    slabs, off = [], 0
    for c, n in zip(caps, counts):
        slabs.append((off, int(n), int(c)))
        off += int(n) * int(c)
    return strip_off, strip_cap, total, slabs


def _tiered_strip_stats(plan: "ReadabilityPlan", axis_i: int, segs, B: int,
                        *, with_angle: bool):
    """One-sort gather bucketing + occupancy-tiered reversal sweep.

    ``segs`` is a batched :class:`~repro.core.grid.StripSegments` with
    ``(B, max_segments)`` fields (``B=1`` for the single-layout path —
    the batched and looped programs share this code, which is what makes
    their integer metrics bit-identical).  The whole batch is grouped by
    ONE composite-key sort and materialized by gathers
    (:func:`~repro.core.grid.gather_ragged_buckets`; no scatter, no
    vmap), and each capacity tier is swept at its own ``cap_t^2`` pair
    tile instead of every strip paying the fullest strip's.  Returns
    ``((B,) count, (B,) dev_sum, (B,) dropped)``.
    """
    n_strips = plan.n_strips
    strip_off, strip_cap, total, slabs = _tier_layout(plan, axis_i)
    with jax.named_scope(f"strips.build/axis{axis_i}"):
        yl, yr, th, v, u, ok, _, dropped = gridlib.gather_ragged_buckets(
            segs.strip, n_strips, strip_off, strip_cap,
            segs.yl, segs.yr, segs.theta, segs.v, segs.u, valid=segs.valid)

    gridlib.CALL_COUNTS["reversal_sweeps"] += 1
    cnt = jnp.zeros(B, gridlib.count_dtype())
    dev = jnp.zeros(B, yl.dtype)
    row_block = min(plan.strip_block, n_strips)
    for tier, (off, n_t, cap_t) in enumerate(slabs):
        sl = lambda a: (a[:, off:off + n_t * cap_t]
                        .reshape(B * n_t, cap_t))
        with jax.named_scope(f"strips.sweep/axis{axis_i}/tier{tier}"):
            rc, rd = _reversal_rows(sl(yl), sl(yr), sl(th), sl(v), sl(u),
                                    sl(ok), ideal=plan.ideal,
                                    with_angle=with_angle,
                                    row_block=row_block)
            cnt = cnt + rc.reshape(B, n_t).sum(axis=1)
            dev = dev + rd.reshape(B, n_t).sum(axis=1)
    return cnt, dev, dropped


# ---------------------------------------------------------------------------
# planning (host side, once per graph topology/extent)
# ---------------------------------------------------------------------------

def plan_readability(pos, edges, *, radius: float = 0.5, ideal_angle=None,
                     n_strips: int = 64, orientation: str = "both",
                     metrics=ALL_METRICS, cell_block: int = 512,
                     strip_block: int = 256, tier_strips: bool = True,
                     precision: str = "float32") -> ReadabilityPlan:
    """Build a :class:`ReadabilityPlan` from concrete data (host side).

    ``pos`` may be ``(V, 2)`` or a batch ``(B, V, 2)`` — a batched plan
    sizes every capacity to cover all B layouts, for
    :func:`evaluate_layouts`.  Planning is the only numpy round-trip;
    everything downstream stays on device.

    ``tier_strips=False`` disables the occupancy tiers: every strip gets
    the flat top cap.  The flat cap's headroom is uniform, so it
    tolerates layouts whose occupancy *shifts between strips* (drifting
    same-topology traffic) much longer before overflowing — the serving
    session plans flat for exactly that reason, trading the tiered
    sweep's padded-pair savings for a zero-replan steady state.
    """
    pos = np.asarray(pos, np.float32)
    edges = np.asarray(edges, np.int32)
    pos_b = pos[None] if pos.ndim == 2 else pos
    metrics = tuple(metrics)
    ideal = float(DEFAULT_IDEAL if ideal_angle is None else ideal_angle)

    if "node_occlusion" in metrics:
        origin, nx, ny, cell_cap, cell_size = gridlib.plan_occlusion_grid(
            pos_b, radius)
    else:
        origin, nx, ny, cell_cap, cell_size = (0.0, 0.0), 1, 1, 8, 1.0

    axes = _AXES[orientation]
    strip_plans, strip_tiers = [], []
    if ("edge_crossing" in metrics) or ("edge_crossing_angle" in metrics):
        for axis in axes:
            max_segments = 0
            occ = np.zeros(n_strips, np.int64)
            for p in pos_b:
                ms, per_strip = gridlib.plan_strip_occupancy(
                    p, edges, n_strips, axis=axis)
                max_segments = max(max_segments, ms)
                occ = np.maximum(occ, per_strip)
            tiers = gridlib.plan_strip_tiers(occ)
            # the flat cap IS the top tier's cap, so the tiered layout
            # never exceeds what strip_plans advertises
            strip_plans.append((max_segments, tiers[0][0]))
            strip_tiers.append(tiers if tier_strips else ())

    return ReadabilityPlan(
        radius=float(radius), ideal=ideal, n_strips=int(n_strips),
        axes=axes, metrics=metrics, grid_origin=origin, grid_nx=nx,
        grid_ny=ny, cell_cap=cell_cap, grid_cell_size=float(cell_size),
        strip_plans=tuple(strip_plans), strip_tiers=tuple(strip_tiers),
        cell_block=int(cell_block), strip_block=int(strip_block),
        precision=str(precision))


# ---------------------------------------------------------------------------
# fused evaluation (one traced program, all metrics)
# ---------------------------------------------------------------------------

def _evaluate(plan: ReadabilityPlan, pos, edges, use_kernels: bool,
              n_valid_vertices=None, n_valid_edges=None) -> EngineResult:
    global _trace_count
    if isinstance(pos, jax.core.Tracer):
        _trace_count += 1
    pos = jnp.asarray(pos, plan.dtype)
    edges = jnp.asarray(edges, jnp.int32)
    vertex_valid = None
    if n_valid_vertices is not None:
        vertex_valid = (jnp.arange(pos.shape[0], dtype=jnp.int32)
                        < jnp.asarray(n_valid_vertices, jnp.int32))
    edge_valid = None
    if n_valid_edges is not None:
        edge_valid = (jnp.arange(edges.shape[0], dtype=jnp.int32)
                      < jnp.asarray(n_valid_edges, jnp.int32))
    m = plan.metrics
    out = {}
    overflow = jnp.zeros((), jnp.int32)

    if "node_occlusion" in m:
        with jax.named_scope("occlusion"):
            if use_kernels:
                # exact tiled pairwise Pallas kernel: same count as the
                # grid (paper Table 3: enhanced N_c has 0% error), no
                # capacities to overflow
                from repro.kernels.ops import occlusion_count_op
                cnt = occlusion_count_op(pos, plan.radius,
                                         valid=vertex_valid)
            else:
                cnt, ov = count_occlusions_gridded(
                    pos, plan.radius, plan.grid_origin, plan.grid_nx,
                    plan.grid_ny, plan.cell_cap, valid=vertex_valid,
                    cell_block=min(plan.cell_block,
                                   plan.grid_nx * plan.grid_ny),
                    cell_size=plan.grid_cell_size)
                overflow = overflow + ov
        out["node_occlusion"] = cnt
    if "minimum_angle" in m:
        with jax.named_scope("min_angle"):
            m_a, _ = minimum_angle(pos, edges, edge_valid=edge_valid)
        out["minimum_angle"] = m_a
    if "edge_length_variation" in m:
        with jax.named_scope("edge_length"):
            out["edge_length_variation"] = edge_length_variation(
                pos, edges, edge_valid=edge_valid)

    want_ec = "edge_crossing" in m
    want_eca = "edge_crossing_angle" in m
    if want_ec or want_eca:
        stats = []
        for axis_i, (axis, (max_segments, cap)) in enumerate(
                zip(plan.axes, plan.strip_plans)):
            # strip build + bucketing happen ONCE per orientation; the one
            # fused sweep serves both E_c and E_ca
            with jax.named_scope(f"strips.build/axis{axis_i}"):
                segs = gridlib.build_strip_segments(
                    pos, edges, plan.n_strips, max_segments, axis=axis,
                    edge_valid=edge_valid)
            if use_kernels:
                # the Pallas kernel sweeps the flat (n_strips, cap) layout
                # (it pads cap to lane multiples anyway, so tiering would
                # buy nothing)
                with jax.named_scope(f"strips.build/axis{axis_i}"):
                    buckets = gridlib.bucketize_segments(
                        segs, plan.n_strips, cap)
                with jax.named_scope(f"strips.sweep/axis{axis_i}/tier0"):
                    cnt, dev = fused_reversal_stats(
                        buckets, ideal=plan.ideal,
                        strip_block=min(plan.strip_block, plan.n_strips),
                        with_angle=want_eca, use_kernels=True)
                stats.append((cnt, dev, buckets.overflow))
            else:
                # occupancy-tiered sweep, as the B=1 case of the batched
                # program (shared code keeps looped == batched bit-exact)
                segs1 = segs._replace(
                    strip=segs.strip[None], yl=segs.yl[None],
                    yr=segs.yr[None], theta=segs.theta[None],
                    v=segs.v[None], u=segs.u[None], valid=segs.valid[None])
                cnt, dev, drop = _tiered_strip_stats(
                    plan, axis_i, segs1, 1, with_angle=want_eca)
                stats.append((cnt[0], dev[0], drop[0] + segs.overflow))
        with jax.named_scope("crossing.select"):
            if len(stats) == 1:
                (ec_count, best_dev, ec_ov) = stats[0]
                best_count = ec_count
            else:
                (c0, d0, o0), (c1, d1, o1) = stats
                ec_count = jnp.maximum(c0, c1)
                ec_ov = jnp.maximum(o0, o1)
                # orientation with the most crossings = best-covered
                # estimate (Table 4); strictly-greater keeps axis-0 on
                # ties, matching the unfused path — selected on device,
                # zero host syncs.
                take1 = c1 > c0
                best_count = jnp.where(take1, c1, c0)
                best_dev = jnp.where(take1, d1, d0)
            if want_ec:
                out["edge_crossing"] = ec_count
            if want_eca:
                out["edge_crossing_angle"] = jnp.where(
                    best_count > 0,
                    1.0 - best_dev / jnp.maximum(best_count, 1), 1.0)
                out["crossing_count_for_angle"] = best_count
        # the strip decomposition is shared by E_c and E_ca, so its
        # dropped segments count once, as the max over orientations —
        # a starved *losing* orientation corrupts the best-orientation
        # vote too, so its drops must still trip the replan signal
        overflow = overflow + ec_ov

    return EngineResult(overflow=overflow, **out)


def evaluate_once(plan: ReadabilityPlan, pos, edges, *,
                  n_valid_vertices=None, n_valid_edges=None,
                  use_kernels: bool = False) -> EngineResult:
    """One fused evaluation, eagerly (no jit cache entry).

    Same program as :func:`evaluate_planned` minus the compilation: the
    right call when the plan is fresh-per-layout (the ``backend="eager"``
    path of :class:`repro.api.Evaluator`), where jitting would recompile
    on every call and grow the jit cache without bound."""
    return _evaluate(plan, pos, edges, use_kernels,
                     n_valid_vertices, n_valid_edges)


def _evaluate_planned(plan, pos, edges, n_valid_vertices=None,
                      n_valid_edges=None, use_kernels=False):
    return _evaluate(plan, pos, edges, use_kernels,
                     n_valid_vertices, n_valid_edges)


def evaluate_batched_body(plan: ReadabilityPlan, batch_pos, edges,
                          n_valid_vertices=None,
                          n_valid_edges=None) -> EngineResult:
    """The natively batched engine program: ``(B, V, 2)`` in one pass.

    No per-layout dispatch: each bucketing step groups the whole batch
    with ONE composite-key sort and materializes buckets by gathers
    (vmapped argsort/scatter is what made ``evaluate_layouts`` slower
    than a Python loop), and the occupancy-tiered reversal sweep covers
    ``(B * n_strips_t, cap_t)`` rows per tier.  Integer metrics are
    bit-identical to looping
    :func:`_evaluate` over the batch members (same decompositions, same
    pair formulas, order-independent integer sums).

    This function is the ONE source of truth for the batched program:
    the single-host jit (:func:`evaluate_layouts`) traces it whole, and
    the mesh-sharded driver
    (:func:`repro.distributed.batched.evaluate_layouts_sharded`) traces
    it per shard on the batch-axis slice — every per-layout value is
    computed by per-layout-independent code (each bucketing sort is
    per-row, each sweep reduction per-layout), which is what makes the
    sharded composition bit-identical on integer metrics for free.
    """
    global _trace_count
    if isinstance(batch_pos, jax.core.Tracer):
        _trace_count += 1
    pos = jnp.asarray(batch_pos, plan.dtype)
    edges = jnp.asarray(edges, jnp.int32)
    B = pos.shape[0]
    vertex_valid = None
    if n_valid_vertices is not None:
        vertex_valid = (jnp.arange(pos.shape[1], dtype=jnp.int32)
                        < jnp.asarray(n_valid_vertices, jnp.int32))
    edge_valid = None
    if n_valid_edges is not None:
        edge_valid = (jnp.arange(edges.shape[0], dtype=jnp.int32)
                      < jnp.asarray(n_valid_edges, jnp.int32))
    m = plan.metrics
    out = {}
    overflow = jnp.zeros(B, jnp.int32)

    if "node_occlusion" in m:
        with jax.named_scope("occlusion"):
            cnt, ov = count_occlusions_gridded_batched(
                pos, plan.radius, plan.grid_origin, plan.grid_nx,
                plan.grid_ny, plan.cell_cap, valid=vertex_valid,
                cell_block=min(plan.cell_block, plan.grid_nx * plan.grid_ny),
                cell_size=plan.grid_cell_size)
        overflow = overflow + ov
        out["node_occlusion"] = cnt
    if "minimum_angle" in m:
        with jax.named_scope("min_angle"):
            m_a, _ = minimum_angle_batched(pos, edges, edge_valid=edge_valid)
        out["minimum_angle"] = m_a
    if "edge_length_variation" in m:
        with jax.named_scope("edge_length"):
            out["edge_length_variation"] = edge_length_variation_batched(
                pos, edges, edge_valid=edge_valid)

    want_ec = "edge_crossing" in m
    want_eca = "edge_crossing_angle" in m
    if want_ec or want_eca:
        stats = []
        for axis_i, (axis, (max_segments, cap)) in enumerate(
                zip(plan.axes, plan.strip_plans)):
            with jax.named_scope(f"strips.build/axis{axis_i}"):
                segs = gridlib.build_strip_segments_batched(
                    pos, edges, plan.n_strips, max_segments, axis=axis,
                    edge_valid=edge_valid)
            cnt, dev, drop = _tiered_strip_stats(
                plan, axis_i, segs, B, with_angle=want_eca)
            stats.append((cnt, dev, drop + segs.overflow))
        with jax.named_scope("crossing.select"):
            if len(stats) == 1:
                (ec_count, best_dev, ec_ov) = stats[0]
                best_count = ec_count
            else:
                (c0, d0, o0), (c1, d1, o1) = stats
                ec_count = jnp.maximum(c0, c1)
                ec_ov = jnp.maximum(o0, o1)
                take1 = c1 > c0
                best_count = jnp.where(take1, c1, c0)
                best_dev = jnp.where(take1, d1, d0)
            if want_ec:
                out["edge_crossing"] = ec_count
            if want_eca:
                out["edge_crossing_angle"] = jnp.where(
                    best_count > 0,
                    1.0 - best_dev / jnp.maximum(best_count, 1), 1.0)
                out["crossing_count_for_angle"] = best_count
        overflow = overflow + ec_ov

    return EngineResult(overflow=overflow, **out)


# in-repo callers predating the public name (shared per-shard body)
_evaluate_batched = evaluate_batched_body


# ---------------------------------------------------------------------------
# graph-axis sharding: ONE layout spatially partitioned across a mesh
# ---------------------------------------------------------------------------

def _shard_occlusion(plan: ReadabilityPlan, pos, vertex_valid, shard,
                     axis_name):
    """This shard's slice of the occlusion sweep: owned-cell buckets, one
    one-sided halo exchange, forward-neighbourhood pair count.

    Each shard buckets only the vertices whose cell falls in its owned
    contiguous flat-cell range (same one-sort gather bucketing and the
    same keep-first-``cap`` drop rule as the single-host path, so kept
    sets match per cell).  The forward-neighbourhood offsets
    (:data:`repro.core.grid.FORWARD_NEIGHBOURHOOD`) read at most
    ``nx + 1`` cells ahead, all covered by the halo slab received from
    the next shard — the owner-cell rule: every cross-boundary pair is
    counted by the shard owning its lower-flat-id cell, exactly once.
    Returns local ``(count, overflow)`` (pre-psum).
    """
    from repro.distributed.collectives import halo_exchange

    spec = plan.graph_shard
    nx, ny = plan.grid_nx, plan.grid_ny
    n_cells = nx * ny
    per_c, H, cap = spec.cells_per_shard, spec.halo_cells, plan.cell_cap
    origin, size = plan.grid_origin, plan.grid_cell_size

    gridlib.CALL_COUNTS["cell_builds"] += 1
    ix = jnp.clip(jnp.floor((pos[:, 0] - origin[0]) / size)
                  .astype(jnp.int32), 0, nx - 1)
    iy = jnp.clip(jnp.floor((pos[:, 1] - origin[1]) / size)
                  .astype(jnp.int32), 0, ny - 1)
    cid = iy * nx + ix                                     # (V,)
    c0 = (shard * per_c).astype(jnp.int32)
    local = cid - c0
    own = (local >= 0) & (local < per_c)
    if vertex_valid is not None:
        own = own & vertex_valid
    x, y, bval, _, overflow = gridlib.gather_ragged_buckets(
        local[None], per_c, np.arange(per_c, dtype=np.int64) * cap,
        np.full(per_c, cap, np.int64), pos[None, :, 0], pos[None, :, 1],
        valid=own[None])
    x = x.reshape(per_c, cap)
    y = y.reshape(per_c, cap)
    bval = bval.reshape(per_c, cap)

    # ONE one-sided exchange: the halo (the H cells after the owned
    # range) is a prefix of the NEXT shard's owned range by plan
    # construction (cells_per_shard >= halo_cells), so its bucket rows
    # arrive ready-made.  Wrap-around/past-the-grid halo rows are
    # killed by the global-id mask.
    with jax.named_scope("graph_shard.halo"):
        hx, hy, hv = halo_exchange((x[:H], y[:H], bval[:H]), axis_name)
    halo_gid = c0 + per_c + jnp.arange(H, dtype=jnp.int32)
    hv = hv & (halo_gid < n_cells)[:, None]
    xt = jnp.concatenate([x, hx])
    yt = jnp.concatenate([y, hy])
    vt = jnp.concatenate([bval, hv])

    # forward-neighbourhood ids, local to the concatenated table
    lidx = jnp.arange(per_c, dtype=jnp.int32)
    gcid = c0 + lidx
    gx, gy = gcid % nx, gcid // nx
    exists = gcid < n_cells
    ids, oks = [], []
    for dx, dy in gridlib.FORWARD_NEIGHBOURHOOD:
        ids.append(lidx + dy * nx + dx)
        oks.append(exists & (gx + dx >= 0) & (gx + dx < nx)
                   & (gy + dy < ny))
    nbr_idx = jnp.clip(jnp.stack(ids, axis=1), 0, per_c + H - 1)
    nbr_ok = jnp.stack(oks, axis=1)                        # (per_c, 4)

    thresh = jnp.asarray((2.0 * plan.radius) ** 2, pos.dtype)
    rows = per_c
    cell_block = max(1, min(plan.cell_block, rows))
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
        same = jnp.sum(jnp.where(smask & (d2 < thresh), 1, 0),
                       dtype=gridlib.count_dtype())
        cx = xt[ni].reshape(cell_block, -1)
        cy = yt[ni].reshape(cell_block, -1)
        cv = (vt[ni] & no[:, :, None]).reshape(cell_block, -1)
        c2 = ((bx[:, :, None] - cx[:, None, :]) ** 2
              + (by[:, :, None] - cy[:, None, :]) ** 2)
        cmask = bv[:, :, None] & cv[:, None, :]
        cross = jnp.sum(jnp.where(cmask & (c2 < thresh), 1, 0),
                        dtype=gridlib.count_dtype())
        return same + cross

    starts = jnp.arange(0, pad_rows, cell_block, dtype=jnp.int32)
    return jnp.sum(lax.map(block_fn, starts)), overflow[0]


def _shard_min_angle(pos, edges, edge_valid, spec, shard):
    """This shard's part of M_a: the deviations ``d_v`` of the vertices
    whose run of half-edges starts in its share of the sorted topology.

    The 2E half-edges ``(src, dst)`` (invalid ones sent to trash vertex
    V, as :func:`~repro.core.min_angle.minimum_angle` does) are sorted by
    ``src`` alone, replicated: the sort reads no positions.  Shard ``i``
    owns the runs whose first half-edge lies in ``[i * q, (i + 1) * q)``,
    ``q = ceil(2E / n_shards)``: its run range ``[p_i, p_{i+1})`` is cut
    at the first run start at or after each multiple of ``q``, found by
    counting the half-edges whose vertex precedes it (no search).  The
    range fits a static window of ``q + deg_cap - 1`` half-edges; a range
    that does not is counted in the returned excess, never dropped
    silently.  In the window: one row gather of positions per endpoint,
    the same :func:`~repro.core.geometry.directed_angle`, one two-key
    sort by (vertex, angle), and a doubling pass that carries each run's
    smallest in-run gap, last angle and last position back to its first
    half-edge.  ``min`` is exact, so every ``d_v`` is the single-layout
    path's; only the order of the final sum differs.  Returns local
    ``(sum of d_v, counted vertices, excess)`` (pre-psum)."""
    gridlib.CALL_COUNTS["vertex_sorts"] += 1
    gridlib.CALL_COUNTS["vertex_range_splits"] += 1
    V, E = pos.shape[0], edges.shape[0]
    n = 2 * E
    if n == 0:
        zero = jnp.zeros((), jnp.int32)
        return jnp.zeros((), jnp.float32), zero, zero
    src = jnp.concatenate([edges[:, 0], edges[:, 1]]).astype(jnp.int32)
    dst = jnp.concatenate([edges[:, 1], edges[:, 0]]).astype(jnp.int32)
    if edge_valid is not None:
        src = jnp.where(jnp.concatenate([edge_valid, edge_valid]), src, V)
    s, d = lax.sort((src, dst), num_keys=1, is_stable=False)

    q = -(-n // spec.n_shards)
    W = n if spec.deg_cap <= 0 else min(n, q + spec.deg_cap - 1)

    def cut(c):
        # first run start at or after c: the half-edges whose vertex is
        # at most the one at c - 1, trash excluded (it sorts last)
        v = jnp.minimum(s[jnp.clip(c - 1, 0, n - 1)], V - 1)
        return jnp.where(c <= 0, 0, jnp.sum(s <= v, dtype=jnp.int32))

    lo = cut(shard * q)
    hi = cut((shard + 1) * q)
    excess = jnp.maximum(hi - lo - W, 0)
    start = jnp.minimum(lo, n - W)
    sw = lax.dynamic_slice_in_dim(s, start, W)
    dw = lax.dynamic_slice_in_dim(d, start, W)
    idx = start + jnp.arange(W, dtype=jnp.int32)
    mine = (idx >= lo) & (idx < hi)

    ps = pos[jnp.clip(sw, 0, V - 1)]                       # (W, 2)
    pd = pos[jnp.clip(dw, 0, V - 1)]
    ang = directed_angle(ps[:, 0], ps[:, 1], pd[:, 0], pd[:, 1])
    key, a = lax.sort((jnp.where(mine, sw, V), ang), num_keys=2,
                      is_stable=False)

    # per position, over the rest of its run: the smallest in-run gap,
    # the last angle and the last position (doubling, no scatter)
    inf = jnp.full((1,), jnp.inf, a.dtype)
    gap = jnp.concatenate(
        [jnp.where(key[1:] == key[:-1], a[1:] - a[:-1], jnp.inf), inf])
    last_a = a
    last_i = jnp.arange(W, dtype=jnp.int32)
    shift = 1
    while shift < W:
        reach = key[shift:] == key[:-shift]
        head = W - shift
        gap = jnp.concatenate([jnp.where(
            reach, jnp.minimum(gap[:head], gap[shift:]), gap[:head]),
            gap[head:]])
        last_a = jnp.concatenate(
            [jnp.where(reach, last_a[shift:], last_a[:head]), last_a[head:]])
        last_i = jnp.concatenate(
            [jnp.where(reach, last_i[shift:], last_i[:head]), last_i[head:]])
        shift *= 2

    first = (key < V) & jnp.concatenate(
        [jnp.ones((1,), bool), key[1:] != key[:-1]])
    deg = last_i - jnp.arange(W, dtype=jnp.int32) + 1
    gap_min = jnp.where(deg >= 2, gap, jnp.inf)
    wrap = TWO_PI - (last_a - a)
    phi_min = jnp.minimum(gap_min, wrap)
    ideal = TWO_PI / jnp.maximum(deg, 1)
    dev = jnp.where(first, (ideal - phi_min) / ideal, 0.0)
    return jnp.sum(dev), jnp.sum(first, dtype=jnp.int32), excess


def evaluate_graph_shard_body(plan: ReadabilityPlan, pos, edges, *,
                              axis_name, n_valid_vertices=None,
                              n_valid_edges=None) -> EngineResult:
    """The per-shard program of ``backend="graph_sharded"``: ONE layout
    spatially partitioned across a mesh (run under ``shard_map`` with
    fully replicated inputs; every device computes its owned slice and
    the outputs are replicated psum totals).

    Division of labour per device ``i`` (ranges from
    ``plan.graph_shard``, a :class:`~repro.core.grid.GraphShardSpec`):

    * **strips** (E_c / E_ca): the strip build is replicated (it is an
      O(E) clip whose domain derives deterministically from the
      replicated layout), then each shard buckets and sweeps only strips
      ``[i * strips_per_shard, ...)`` — embarrassingly parallel, zero
      collectives beyond the final psum of partial (count, deviation)
      sums;
    * **occlusion** (N_c): grid cells partition contiguously with ONE
      one-sided halo exchange for boundary cells (:func:`_shard_occlusion`
      — the owner-cell rule counts each cross-boundary pair exactly
      once);
    * **M_a**: split by vertex ownership (:func:`_shard_min_angle`):
      the topology sort of the 2E half-edges is replicated, then each
      shard gathers, sorts and reduces only the runs of the vertices it
      owns, and ONE psum of ``(sum of d_v, counted vertices)`` joins
      them.  Each ``d_v`` is bit-identical to the single-host path's;
      the final float sum differs in order only;
    * **M_l**: O(E) replicated (the same call the single-host path
      makes, so it is bit-identical).

    Integer metrics are bit-identical to the single-host fused path under
    the same (flat-capacity) plan and invariant to the shard count: kept
    sets match per bucket (same stable keep-first-``cap`` drop rule),
    pair formulas match bitwise, and integer partial sums are
    order-independent under psum.  E_ca's float deviation sum may differ
    in summation order only.

    Named scopes in the op metadata: ``graph_shard.occlusion`` (with
    ``graph_shard.halo`` around the exchange), ``strips.build/axis{i}``
    (the replicated build and this shard's bucketing, named as in the
    fused path so that a change to the shared build reads the same in
    both traces), ``graph_shard.sweep/axis{i}``, ``graph_shard.reduce``
    (the psums), ``min_angle`` (this shard's share of M_a) and
    ``edge_length``.
    """
    global _trace_count
    if isinstance(pos, jax.core.Tracer):
        _trace_count += 1
    if plan.graph_shard is None:
        raise ValueError("evaluate_graph_shard_body needs a plan with "
                         "graph_shard set (see grid.plan_graph_shards)")
    pos = jnp.asarray(pos, plan.dtype)
    edges = jnp.asarray(edges, jnp.int32)
    shard = lax.axis_index(axis_name)
    spec = plan.graph_shard
    vertex_valid = None
    if n_valid_vertices is not None:
        vertex_valid = (jnp.arange(pos.shape[0], dtype=jnp.int32)
                        < jnp.asarray(n_valid_vertices, jnp.int32))
    edge_valid = None
    if n_valid_edges is not None:
        edge_valid = (jnp.arange(edges.shape[0], dtype=jnp.int32)
                      < jnp.asarray(n_valid_edges, jnp.int32))
    m = plan.metrics
    out = {}
    overflow = jnp.zeros((), jnp.int32)

    if "node_occlusion" in m:
        with jax.named_scope("graph_shard.occlusion"):
            cnt, ov = _shard_occlusion(plan, pos, vertex_valid, shard,
                                       axis_name)
        with jax.named_scope("graph_shard.reduce"):
            out["node_occlusion"] = lax.psum(cnt, axis_name)
            overflow = overflow + lax.psum(ov, axis_name)
    if "minimum_angle" in m:
        with jax.named_scope("min_angle"):
            dev, n_counted, excess = _shard_min_angle(pos, edges,
                                                      edge_valid, spec, shard)
        with jax.named_scope("graph_shard.reduce"):
            dev, n_counted, excess = lax.psum((dev, n_counted, excess),
                                              axis_name)
        out["minimum_angle"] = 1.0 - dev / jnp.maximum(n_counted, 1)
        overflow = overflow + excess
    if "edge_length_variation" in m:
        with jax.named_scope("edge_length"):
            out["edge_length_variation"] = edge_length_variation(
                pos, edges, edge_valid=edge_valid)

    want_ec = "edge_crossing" in m
    want_eca = "edge_crossing_angle" in m
    if want_ec or want_eca:
        per_s = spec.strips_per_shard
        s0 = (shard * per_s).astype(jnp.int32)
        stats = []
        for axis_i, (axis, (max_segments, cap)) in enumerate(
                zip(plan.axes, plan.strip_plans)):
            with jax.named_scope(f"strips.build/axis{axis_i}"):
                segs = gridlib.build_strip_segments(
                    pos, edges, plan.n_strips, max_segments, axis=axis,
                    edge_valid=edge_valid)
                lkey = segs.strip - s0
                # segs.valid is load-bearing beyond masking padding: the
                # trash strip id (n_strips) can fall inside the LAST
                # shard's local range when strips_per_shard * n_shards >
                # n_strips
                own = segs.valid & (lkey >= 0) & (lkey < per_s)
                yl, yr, th, v, u, ok, _, drop = \
                    gridlib.gather_ragged_buckets(
                        lkey[None], per_s,
                        np.arange(per_s, dtype=np.int64) * cap,
                        np.full(per_s, cap, np.int64), segs.yl[None],
                        segs.yr[None], segs.theta[None], segs.v[None],
                        segs.u[None], valid=own[None])
            gridlib.CALL_COUNTS["reversal_sweeps"] += 1
            with jax.named_scope(f"graph_shard.sweep/axis{axis_i}"):
                rc, rd = _reversal_rows(
                    yl.reshape(per_s, cap), yr.reshape(per_s, cap),
                    th.reshape(per_s, cap), v.reshape(per_s, cap),
                    u.reshape(per_s, cap), ok.reshape(per_s, cap),
                    ideal=plan.ideal, with_angle=want_eca,
                    row_block=min(plan.strip_block, per_s))
            with jax.named_scope("graph_shard.reduce"):
                cnt = lax.psum(jnp.sum(rc), axis_name)
                dev = lax.psum(jnp.sum(rd), axis_name)
                # segs.overflow is replicated (identical on every
                # device): add it once, outside the psum of the
                # per-shard drops
                ov_ax = lax.psum(drop[0], axis_name) + segs.overflow
            stats.append((cnt, dev, ov_ax))
        if len(stats) == 1:
            (ec_count, best_dev, ec_ov) = stats[0]
            best_count = ec_count
        else:
            (c0_, d0, o0), (c1, d1, o1) = stats
            ec_count = jnp.maximum(c0_, c1)
            ec_ov = jnp.maximum(o0, o1)
            take1 = c1 > c0_
            best_count = jnp.where(take1, c1, c0_)
            best_dev = jnp.where(take1, d1, d0)
        if want_ec:
            out["edge_crossing"] = ec_count
        if want_eca:
            out["edge_crossing_angle"] = jnp.where(
                best_count > 0,
                1.0 - best_dev / jnp.maximum(best_count, 1), 1.0)
            out["crossing_count_for_angle"] = best_count
        overflow = overflow + ec_ov

    return EngineResult(overflow=overflow, **out)


def _evaluate_layouts(plan, batch_pos, edges, n_valid_vertices=None,
                      n_valid_edges=None, use_kernels=False):
    if use_kernels:
        # the Pallas kernels are single-layout tiles; keep the vmapped
        # dispatch for that (TPU-targeted) route
        return jax.vmap(
            lambda p: _evaluate(plan, p, edges, use_kernels,
                                n_valid_vertices, n_valid_edges))(batch_pos)
    return evaluate_batched_body(plan, batch_pos, edges,
                                 n_valid_vertices, n_valid_edges)


evaluate_planned = jax.jit(_evaluate_planned,
                           static_argnames=("plan", "use_kernels"))
evaluate_planned.__doc__ = (
    """All five metrics for one layout under ``plan``, fused + jitted.

    ``evaluate_planned(plan, pos, edges, n_valid_vertices=None,
    n_valid_edges=None, use_kernels=False)`` -> :class:`EngineResult` of
    device scalars (one transfer fetches all).  ``plan`` is static:
    repeated calls with the same plan and shapes hit the jit cache.  The
    optional ``n_valid_*`` scalars are *traced*, so bucket-padded
    requests of any natural size share one cache entry (see the module
    docstring's padding contract).""")

evaluate_layouts = jax.jit(_evaluate_layouts,
                           static_argnames=("plan", "use_kernels"))
evaluate_layouts.__doc__ = (
    """Batched evaluation: ``(B, V, 2)`` candidate layouts of one graph
    in a single natively batched dispatch (one composite-key sort per
    bucketing step, one tiered reversal sweep per orientation — see the
    module docstring). Returns an :class:`EngineResult` whose
    fields have a leading batch dimension. Plan with a batched ``pos``
    (or any representative layout) via :func:`plan_readability`.  The
    optional traced ``n_valid_vertices`` / ``n_valid_edges`` scalars
    apply to every batch member (coalesced serving requests share one
    topology, hence one natural size).""")


def replan_on_overflow(plan: ReadabilityPlan, pos, edges, result,
                       *, growth: float = 1.5) -> ReadabilityPlan:
    """Grow ``plan`` when ``result`` reports capacity overflow.

    ``result`` is anything with an ``overflow`` attribute (an
    :class:`EngineResult` or a host-side report).  Returns ``plan``
    unchanged when nothing overflowed.  Otherwise re-plans from the
    concrete offending layout (``pos``/``edges`` — pass the *natural*,
    unpadded arrays) and floors every capacity at ``growth`` x the old
    plan's, so the retry can neither overflow on the same data nor
    shrink below what previous traffic needed.

    This function grows capacities; it does NOT bound the retry loop —
    that is the caller's contract.  The serving session retries at most
    ``max_replan_retries`` times with ``growth ** attempt`` (capped at
    its ``growth_ceiling``) and then surfaces
    :class:`repro.core.validate.CapacityError` (strict validation) or a
    ``saturated``-flagged score (sanitize) rather than returning a
    silently under-counted result — see ``docs/robustness.md``."""
    ov = result.overflow
    # max() handles batched results ((B,)-shaped overflow from
    # evaluate_layouts) as well as scalars and host-side report ints
    if ov is None or int(np.max(jax.device_get(ov))) == 0:
        return plan
    fresh = plan_readability(
        pos, edges, radius=plan.radius, ideal_angle=plan.ideal,
        n_strips=plan.n_strips, orientation=plan.orientation,
        metrics=plan.metrics, cell_block=plan.cell_block,
        strip_block=plan.strip_block,
        tier_strips=any(plan.strip_tiers), precision=plan.precision)
    cell_cap = max(fresh.cell_cap,
                   gridlib._round_up(int(plan.cell_cap * growth), 8))
    # per-strip growth floors: every strip's tier capacity is floored at
    # ``growth`` x what the old plan gave it, then re-tiered — the retry
    # can neither overflow on the offending layout (fresh caps cover it)
    # nor shrink below what previous traffic needed (no replan ping-pong)
    strip_plans, strip_tiers = [], []
    for axis_i, ((f_ms, f_cap), (o_ms, o_cap)) in enumerate(
            zip(fresh.strip_plans, plan.strip_plans)):
        _, fresh_cap_s, _, _ = _tier_layout(fresh, axis_i)
        _, old_cap_s, _, _ = _tier_layout(plan, axis_i)
        floored = np.maximum(
            fresh_cap_s.astype(np.int64),
            np.array([gridlib._next_pow2(int(c * growth))
                      for c in old_cap_s], np.int64))
        tiers = gridlib.tiers_from_caps(floored)
        strip_plans.append(
            (max(f_ms, gridlib._round_up(int(o_ms * growth), 128)),
             tiers[0][0]))
        strip_tiers.append(tiers)
    # a graph-sharded plan's M_a window regrows the same way: the fresh
    # degree bound, floored at ``growth`` x the old one (the ranges are
    # re-derived from the grid at dispatch)
    graph_shard = plan.graph_shard
    if graph_shard is not None:
        graph_shard = graph_shard._replace(deg_cap=max(
            gridlib.plan_degree_cap(edges),
            gridlib._next_pow2(int(graph_shard.deg_cap * growth), floor=2)))
    return dataclasses.replace(fresh, cell_cap=cell_cap,
                               strip_plans=tuple(strip_plans),
                               strip_tiers=tuple(strip_tiers),
                               graph_shard=graph_shard)
