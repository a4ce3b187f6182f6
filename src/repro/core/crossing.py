"""Edge crossing ``E_c`` (paper S3.1.4 exact, S3.2.2 enhanced).

* ``count_crossings_exact`` — all edge pairs, CCW straddle test, blocked
  dense sweep (Pallas tile: :mod:`repro.kernels.segment_crossing`).
* ``count_crossings_enhanced`` — vertical-strip decomposition. Within a
  strip every comparable segment spans the full strip, and two segments
  cross iff their boundary-ordinate order *reverses* between the strip's
  left and right lines. The paper sweeps with a balanced BST
  (O(n log n) sequential); the TPU adaptation counts order reversals with
  a dense per-strip pair block (O(cap^2) *parallel*, MXU/VPU-regular):
  a reversal is simply ``(yl_i < yl_j) & (yr_i > yr_j)`` counted over
  ordered pairs, which tallies each unordered crossing exactly once.
  ``orientation='both'`` evaluates vertical + horizontal strips and takes
  the max (Table 4's accuracy trick).

Edge pairs sharing an endpoint are excluded (Greadability.js convention;
a shared endpoint is a touch, not a crossing).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import grid as gridlib
from repro.core.geometry import edge_endpoints, segments_cross


def _pad_to(arr, n, fill):
    pad = n - arr.shape[0]
    if pad <= 0:
        return arr
    return jnp.concatenate([arr, jnp.full((pad,) + arr.shape[1:], fill, arr.dtype)])


def count_crossings_exact(pos: jax.Array, edges: jax.Array, *,
                          block: int = 512, edge_valid=None) -> jax.Array:
    """Exact E_c: edge pairs (i < j), no shared endpoint, CCW straddle."""
    e = edges.shape[0]
    if edge_valid is None:
        edge_valid = jnp.ones(e, dtype=bool)
    x1, y1, x2, y2 = edge_endpoints(pos, edges)
    e_pad = -(-e // block) * block
    x1, y1 = _pad_to(x1, e_pad, 0.0), _pad_to(y1, e_pad, 0.0)
    x2, y2 = _pad_to(x2, e_pad, 0.0), _pad_to(y2, e_pad, 0.0)
    v = _pad_to(edges[:, 0].astype(jnp.int32), e_pad, -1)
    u = _pad_to(edges[:, 1].astype(jnp.int32), e_pad, -2)
    ok = _pad_to(edge_valid, e_pad, False)
    idx = jnp.arange(e_pad, dtype=jnp.int32)

    def row_block(i0):
        sl = lambda a: lax.dynamic_slice(a, (i0,), (block,))
        bx1, by1, bx2, by2 = sl(x1), sl(y1), sl(x2), sl(y2)
        bv, bu, bok = sl(v), sl(u), sl(ok)
        ii = i0 + jnp.arange(block, dtype=jnp.int32)
        cross = segments_cross(
            bx1[:, None], by1[:, None], bx2[:, None], by2[:, None],
            x1[None, :], y1[None, :], x2[None, :], y2[None, :])
        shared = ((bv[:, None] == v[None, :]) | (bv[:, None] == u[None, :]) |
                  (bu[:, None] == v[None, :]) | (bu[:, None] == u[None, :]))
        mask = (ii[:, None] < idx[None, :]) & bok[:, None] & ok[None, :] & ~shared
        return jnp.sum(jnp.where(mask & cross, 1, 0),
                       dtype=gridlib.count_dtype())

    starts = jnp.arange(0, e_pad, block, dtype=jnp.int32)
    return jnp.sum(lax.map(row_block, starts))


def bucket_reversal_stats(buckets: gridlib.SegmentBuckets, *,
                          strip_block: int = 256, ideal_angle=None):
    """Count order reversals (crossings) across all strip buckets.

    Returns ``(count,)`` or ``(count, deviation_sum)`` when ``ideal_angle``
    is given (the crossing-angle variant: the paper's 2-D segment tree
    collapses to a masked elementwise reduction here, see DESIGN.md S2).

    Thin shim over the engine's fused sweep
    (:func:`repro.core.engine.fused_reversal_stats`) — one formula for
    every reversal consumer.
    """
    from repro.core import engine
    want_angle = ideal_angle is not None
    count, dev_sum = engine.fused_reversal_stats(
        buckets, ideal=ideal_angle if want_angle else 1.0,
        strip_block=strip_block, with_angle=want_angle)
    if want_angle:
        return count, dev_sum
    return (count,)


def count_crossings_strips(pos, edges, n_strips: int, max_segments: int,
                           cap: int, *, axis: int = 0, edge_valid=None,
                           strip_block: int = 256):
    """Enhanced E_c for one strip orientation (jit-friendly, static sizes)."""
    segs = gridlib.build_strip_segments_batched(
        jnp.asarray(pos)[None], edges, n_strips, max_segments, axis=axis,
        edge_valid=edge_valid)
    buckets = gridlib.bucketize_segments(
        jax.tree_util.tree_map(lambda a: a[0], segs), n_strips, cap)
    (count,) = bucket_reversal_stats(buckets, strip_block=strip_block)
    return count, buckets.overflow


def count_crossings_enhanced(pos, edges, *, n_strips: int = 64,
                             orientation: str = "both", edge_valid=None,
                             strip_block: int = 256):
    """Host-facing enhanced E_c: plans capacities, runs one or both
    orientations, returns (count, overflow)."""
    pos = jnp.asarray(pos)
    edges = jnp.asarray(edges)
    results = []
    overflows = []
    axes = {"vertical": (0,), "horizontal": (1,), "both": (0, 1)}[orientation]
    for axis in axes:
        max_segments, cap = gridlib.plan_strips(pos, edges, n_strips, axis=axis)
        c, ov = count_crossings_strips(
            pos, edges, n_strips, max_segments, cap, axis=axis,
            edge_valid=edge_valid, strip_block=min(strip_block, n_strips))
        results.append(c)
        overflows.append(ov)
    return jnp.max(jnp.stack(results)), jnp.max(jnp.stack(overflows))
