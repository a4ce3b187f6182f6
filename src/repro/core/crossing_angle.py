"""Edge crossing angle ``E_ca`` (paper S3.1.5 exact, S3.2.3 enhanced).

``E_ca = 1 - mean over crossing pairs of |ideal - a_c| / ideal`` where
``a_c`` is the acute angle between the two crossing edges and ``ideal``
defaults to 70 degrees (Huang et al. 2008).

The enhanced variant shares the strip decomposition with edge crossing.
The paper's 2-D dynamic segment tree (8 angle-category algebra, Eq. 1)
exists to avoid touching every crossing pair on a sequential machine; on
TPU the per-strip dense pair block *already materializes* every candidate
pair, so the deviation reduces to one fused masked elementwise reduction
(see DESIGN.md S2). That is the closest TPU-idiomatic equivalent: same
asymptotic work per strip as the dense crossing count it rides on.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import grid as gridlib
from repro.core.crossing import _pad_to, bucket_reversal_stats
from repro.core.grid import count_dtype
from repro.core.geometry import (edge_endpoints, segment_theta,
                                 segments_cross)

DEFAULT_IDEAL = jnp.deg2rad(70.0)


def crossing_angle_exact(pos, edges, *, ideal=DEFAULT_IDEAL, block: int = 512,
                         edge_valid=None):
    """Exact E_ca plus the crossing count it is normalized by.

    Returns ``(e_ca, count, dev_sum)``; ``e_ca = 1 - dev_sum / count``
    (1.0 when there are no crossings).
    """
    e = edges.shape[0]
    if edge_valid is None:
        edge_valid = jnp.ones(e, dtype=bool)
    x1, y1, x2, y2 = edge_endpoints(pos, edges)
    theta = segment_theta(x1, y1, x2, y2)
    e_pad = -(-e // block) * block
    x1, y1 = _pad_to(x1, e_pad, 0.0), _pad_to(y1, e_pad, 0.0)
    x2, y2 = _pad_to(x2, e_pad, 0.0), _pad_to(y2, e_pad, 0.0)
    th = _pad_to(theta, e_pad, 0.0)
    v = _pad_to(edges[:, 0].astype(jnp.int32), e_pad, -1)
    u = _pad_to(edges[:, 1].astype(jnp.int32), e_pad, -2)
    ok = _pad_to(edge_valid, e_pad, False)
    idx = jnp.arange(e_pad, dtype=jnp.int32)
    ideal = jnp.asarray(ideal, pos.dtype)

    def row_block(i0):
        sl = lambda a: lax.dynamic_slice(a, (i0,), (block,))
        bx1, by1, bx2, by2 = sl(x1), sl(y1), sl(x2), sl(y2)
        bth, bv, bu, bok = sl(th), sl(v), sl(u), sl(ok)
        ii = i0 + jnp.arange(block, dtype=jnp.int32)
        cross = segments_cross(
            bx1[:, None], by1[:, None], bx2[:, None], by2[:, None],
            x1[None, :], y1[None, :], x2[None, :], y2[None, :])
        shared = ((bv[:, None] == v[None, :]) | (bv[:, None] == u[None, :]) |
                  (bu[:, None] == v[None, :]) | (bu[:, None] == u[None, :]))
        mask = (ii[:, None] < idx[None, :]) & bok[:, None] & ok[None, :] \
            & ~shared & cross
        d = jnp.abs(bth[:, None] - th[None, :])
        a_c = jnp.minimum(d, jnp.pi - d)
        dev = jnp.abs(ideal - a_c) / ideal
        return (jnp.sum(jnp.where(mask, 1, 0), dtype=count_dtype()),
                jnp.sum(jnp.where(mask, dev, 0.0)))

    starts = jnp.arange(0, e_pad, block, dtype=jnp.int32)
    counts, devs = lax.map(row_block, starts)
    count = jnp.sum(counts)
    dev_sum = jnp.sum(devs)
    e_ca = jnp.where(count > 0, 1.0 - dev_sum / jnp.maximum(count, 1), 1.0)
    return e_ca, count, dev_sum


def crossing_angle_strips(pos, edges, n_strips: int, max_segments: int,
                          cap: int, *, ideal=DEFAULT_IDEAL, axis: int = 0,
                          edge_valid=None, strip_block: int = 256):
    """Enhanced E_ca for one orientation (jit-friendly, static sizes)."""
    segs = gridlib.build_strip_segments_batched(
        jnp.asarray(pos)[None], edges, n_strips, max_segments, axis=axis,
        edge_valid=edge_valid)
    buckets = gridlib.bucketize_segments(
        jax.tree_util.tree_map(lambda a: a[0], segs), n_strips, cap)
    count, dev_sum = bucket_reversal_stats(buckets, strip_block=strip_block,
                                           ideal_angle=ideal)
    e_ca = jnp.where(count > 0, 1.0 - dev_sum / jnp.maximum(count, 1), 1.0)
    return e_ca, count, dev_sum, buckets.overflow


def crossing_angle_enhanced(pos, edges, *, n_strips: int = 64,
                            ideal=DEFAULT_IDEAL, orientation: str = "both",
                            edge_valid=None, strip_block: int = 256):
    """Host-facing enhanced E_ca; on 'both' keeps the orientation that saw
    the most crossings (the better-covered estimate, cf. Table 4).

    The orientation pick happens with ``jnp.where`` on device — no
    per-orientation blocking transfer (the old ``int(count)`` forced one
    host sync per axis)."""
    pos = jnp.asarray(pos)
    edges = jnp.asarray(edges)
    results = []
    axes = {"vertical": (0,), "horizontal": (1,), "both": (0, 1)}[orientation]
    for axis in axes:
        max_segments, cap = gridlib.plan_strips(pos, edges, n_strips, axis=axis)
        results.append(crossing_angle_strips(
            pos, edges, n_strips, max_segments, cap, ideal=ideal, axis=axis,
            edge_valid=edge_valid, strip_block=min(strip_block, n_strips)))
    best = results[0]
    for cand in results[1:]:
        # strictly-greater keeps the earlier axis on ties, matching the
        # historical host-side selection
        take = cand[1] > best[1]
        best = tuple(jnp.where(take, c, b) for c, b in zip(cand, best))
    return best
