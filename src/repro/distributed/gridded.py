"""Distributed enhanced readability metrics (paper S3.2) via shard_map.

The enhanced algorithms are bags of independent per-strip / per-cell
subproblems — the embarrassingly-parallel regime behind the paper's Fig 4
strong scaling. Mapping:

  * the bucketing 'shuffle' (sort + scatter into dense buckets) runs once
    under pjit — GSPMD owns its collectives (the analogue of Spark's
    partitioning step);
  * the O(cap^2) per-strip pair blocks — the actual FLOP bottleneck —
    shard over every mesh axis with *zero* communication until the final
    scalar psum;
  * over-decomposition (n_strips >> n_devices) is the straggler
    mitigation: a slow device only delays its own strip quota.
"""

from __future__ import annotations

import jax
from jax import shard_map
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.engine import fused_reversal_block
from repro.core.grid import SegmentBuckets
from repro.core.validate import BackendUnavailableError, ReadabilityError


def _pad_strips(buckets: SegmentBuckets, n_dev: int):
    n_strips = buckets.yl.shape[0]
    pad = (-n_strips) % n_dev
    if pad == 0:
        return buckets, n_strips

    def padc(a, fill):
        return jnp.concatenate(
            [a, jnp.full((pad,) + a.shape[1:], fill, a.dtype)])

    return SegmentBuckets(
        yl=padc(buckets.yl, 0.0), yr=padc(buckets.yr, 0.0),
        theta=padc(buckets.theta, 0.0), v=padc(buckets.v, -1),
        u=padc(buckets.u, -2), valid=padc(buckets.valid, False),
        overflow=buckets.overflow), n_strips + pad


def sharded_reversal_stats(mesh: Mesh, buckets: SegmentBuckets, *,
                           ideal_angle=None, strip_block: int = 64):
    """Strip-sharded crossing count (+ optional angle deviation sum).

    The per-strip pair block is the engine's
    :func:`~repro.core.engine.fused_reversal_block` — the same traced
    formula as the single-device enhanced path, so the two can never
    drift."""
    axes = tuple(mesh.axis_names)
    n_dev = mesh.size
    buckets, n_strips = _pad_strips(buckets, n_dev)
    cap = buckets.yl.shape[1]
    strip_block = max(1, min(strip_block, (1 << 26) // max(cap * cap, 1)))
    want_angle = ideal_angle is not None
    ideal = jnp.asarray(ideal_angle if want_angle else 1.0, jnp.float32)
    per = n_strips // n_dev

    def shard_fn(yl, yr, th, v, u, ok):
        def block_fn(s0):
            sl = lambda a: lax.dynamic_slice_in_dim(
                a, s0, min(strip_block, per), axis=0)
            return fused_reversal_block(sl(yl), sl(yr), sl(th), sl(v),
                                        sl(u), sl(ok), ideal=ideal,
                                        with_angle=want_angle)

        starts = jnp.arange(0, per, min(strip_block, per), dtype=jnp.int32)
        counts, devs = lax.map(block_fn, starts)
        return (lax.psum(jnp.sum(counts), axes),
                lax.psum(jnp.sum(devs), axes))

    fn = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(axes), P(axes), P(axes), P(axes), P(axes), P(axes)),
        out_specs=(P(), P()), check_vma=False)
    try:
        count, dev_sum = jax.jit(fn)(buckets.yl, buckets.yr, buckets.theta,
                                     buckets.v, buckets.u, buckets.valid)
    except ReadabilityError:
        raise
    except Exception as err:
        # typed error for the degradation ladders (session / server):
        # a raw XLA runtime error from a lost mesh is not catchable by
        # design — BackendUnavailableError with the original chained is
        raise BackendUnavailableError(
            f"strip-sharded reversal dispatch over {mesh.size} devices "
            f"failed: {type(err).__name__}: {err}", request_index=0) from err
    if want_angle:
        return count, dev_sum
    return (count,)


def evaluate_sharded(mesh: Mesh, pos, edges, *, config=None, plan=None):
    """Config-driven distributed front door: one
    :class:`~repro.core.keys.EvalConfig` -> one
    :class:`~repro.core.scores.ReadabilityScores`, computed over ``mesh``.

    The same config object that drives :class:`repro.api.Evaluator` and
    the serving session selects the metric subset, radius, strips, and
    ideal angle here; ``Evaluator(EvalConfig(backend="distributed"),
    mesh=...)`` routes through this function.  Work placement:

    * ``N_c`` — the row-sharded exact pairwise sweep
      (:func:`repro.distributed.pairwise.sharded_occlusion_count`; the
      grid count equals it bit-for-bit, paper Table 3);
    * ``E_c`` / ``E_ca`` — per-orientation strip decomposition from the
      shared plan, swept by :func:`sharded_reversal_stats` (the same
      :func:`~repro.core.engine.fused_reversal_block` formula as every
      single-device path), best orientation picked like the engine;
    * ``M_a`` / ``M_l`` — O(E log E) / O(E): single-device, never worth
      a collective.

    Skipped metrics are skipped for real: a crossing-only config builds
    no cell buckets and an occlusion-only config launches no reversal
    sweep (same pruning contract as the fused engine).

    A ``(B, V, 2)`` *batch* routes to the batch-axis-sharded driver
    (:func:`repro.distributed.batched.evaluate_layouts_sharded`): the
    mesh then parallelizes over candidate layouts instead of strips —
    the right decomposition for layout-optimization populations, and
    bit-identical on integer metrics to the single-host batched engine.
    """
    from repro.core import grid as gridlib
    from repro.core import engine as _engine
    from repro.core.edge_length import edge_length_variation
    from repro.core.keys import EvalConfig
    from repro.core.min_angle import minimum_angle
    from repro.core.scores import ReadabilityScores
    from repro.distributed.pairwise import sharded_occlusion_count

    config = config or EvalConfig()
    pos = jnp.asarray(pos, jnp.float32)
    edges = jnp.asarray(edges, jnp.int32)
    if pos.ndim == 3:
        from repro.distributed.batched import evaluate_layouts_sharded
        if plan is None:
            plan = _engine.plan_readability(pos, edges,
                                            **config.plan_kwargs())
        res = jax.device_get(
            evaluate_layouts_sharded(mesh, plan, pos, edges))
        return res._replace(n_vertices=int(pos.shape[1]),
                            n_edges=int(edges.shape[0]))
    if plan is None:
        # flat strips: the sharded sweep consumes the dense flat bucket
        # layout (tiering is a single-device pair-tile optimization)
        plan = _engine.plan_readability(
            pos, edges, **config.plan_kwargs(tier_default=False))
    m = config.metrics
    out = {}
    overflow = 0

    if "node_occlusion" in m:
        out["node_occlusion"] = int(sharded_occlusion_count(
            mesh, pos, config.radius))
    if "minimum_angle" in m:
        m_a, _ = minimum_angle(pos, edges)
        out["minimum_angle"] = float(m_a)
    if "edge_length_variation" in m:
        out["edge_length_variation"] = float(edge_length_variation(pos,
                                                                   edges))

    want_ec = "edge_crossing" in m
    want_eca = "edge_crossing_angle" in m
    if want_ec or want_eca:
        stats = []
        for axis, (max_segments, cap) in zip(plan.axes, plan.strip_plans):
            segs = gridlib.build_strip_segments_batched(
                jnp.asarray(pos)[None], edges, plan.n_strips, max_segments,
                axis=axis)
            buckets = gridlib.bucketize_segments(
                jax.tree_util.tree_map(lambda a: a[0], segs), plan.n_strips,
                cap)
            res = sharded_reversal_stats(
                mesh, buckets,
                ideal_angle=plan.ideal if want_eca else None)
            cnt = int(res[0])
            dev = float(res[1]) if want_eca else 0.0
            stats.append((cnt, dev, int(buckets.overflow)))
        # best orientation = most crossings; strictly-greater keeps
        # axis 0 on ties (the engine's rule)
        best = max(range(len(stats)), key=lambda i: (stats[i][0], -i))
        ec_count = max(s[0] for s in stats)
        overflow += max(s[2] for s in stats)
        if want_ec:
            out["edge_crossing"] = ec_count
        if want_eca:
            cnt, dev, _ = stats[best]
            out["edge_crossing_angle"] = (1.0 - dev / cnt if cnt > 0
                                          else 1.0)
            out["crossing_count_for_angle"] = cnt

    return ReadabilityScores(overflow=overflow,
                             n_vertices=int(pos.shape[0]),
                             n_edges=int(edges.shape[0]), **out)


def lower_sharded_reversal(mesh: Mesh, n_strips: int, cap: int, *,
                           strip_block: int = 64, with_angle: bool = False,
                           ideal_angle=None):
    """Build + lower the strip-sharded enhanced crossing counter for
    abstract bucket inputs (dry run at full problem size).

    Shares :func:`~repro.core.engine.fused_reversal_block` with the
    executable paths (this used to hand-roll the deviation without the
    ``/ ideal`` normalization — unified so the formula cannot drift)."""
    axes = tuple(mesh.axis_names)
    n_dev = mesh.size
    n_strips_pad = -(-n_strips // n_dev) * n_dev
    per = n_strips_pad // n_dev
    ideal = jnp.asarray(1.0 if ideal_angle is None else ideal_angle,
                        jnp.float32)

    def shard_fn(yl, yr, th, v, u, ok):
        def block_fn(s0):
            sl = lambda a: lax.dynamic_slice_in_dim(
                a, s0, min(strip_block, per), axis=0)
            return fused_reversal_block(sl(yl), sl(yr), sl(th), sl(v),
                                        sl(u), sl(ok), ideal=ideal,
                                        with_angle=with_angle)

        starts = jnp.arange(0, per, min(strip_block, per), dtype=jnp.int32)
        counts, devs = lax.map(block_fn, starts)
        return (lax.psum(jnp.sum(counts), axes),
                lax.psum(jnp.sum(devs), axes))

    fn = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(axes), P(axes), P(axes), P(axes), P(axes), P(axes)),
        out_specs=(P(), P()), check_vma=False)
    f32 = lambda: jax.ShapeDtypeStruct((n_strips_pad, cap), jnp.float32)
    i32 = lambda: jax.ShapeDtypeStruct((n_strips_pad, cap), jnp.int32)
    b8 = lambda: jax.ShapeDtypeStruct((n_strips_pad, cap), jnp.bool_)
    args = (f32(), f32(), f32(), i32(), i32(), b8())
    return jax.jit(fn), args
