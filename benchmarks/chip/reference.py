"""Plain reference of the five readability metrics, in float64 numpy.

Written from the metric definitions alone; it imports nothing of the
program under test and no JAX.  ``scores(pos, edges, geometry)`` returns
the metrics of one layout under the same semantics as the engine:

* ``node_occlusion`` (N_c): vertex pairs closer than ``2 * radius``;
* ``minimum_angle`` (M_a): ``1 - mean_v (phi_v - gap_v) / phi_v`` over
  vertices with an edge, ``phi_v = 2 pi / deg v`` and ``gap_v`` the
  smallest angle between circularly adjacent incident edges;
* ``edge_length_variation`` (M_l):
  ``sqrt(sum (l - mean)^2 / (E mean^2)) / sqrt(E - 1)``;
* ``edge_crossing`` (E_c): the strip estimate.  The extent of the edge
  endpoints along an axis is cut into ``n_strips`` equal strips; an edge
  belongs to a strip when it spans both of its boundary lines, and two
  edges of a strip that share no endpoint cross when their order along
  the boundary lines reverses (strictly).  Counted for vertical strips
  and for horizontal ones; E_c is the larger count;
* ``edge_crossing_angle`` (E_ca): over the crossings of the orientation
  with more of them (vertical on a tie),
  ``1 - mean |ideal - a| / ideal``, ``a`` the acute angle between the two
  edges; 1 where nothing crosses.  ``crossing_count_for_angle`` is that
  orientation's count.
"""

from __future__ import annotations

import numpy as np


def node_occlusion(pos: np.ndarray, radius: float) -> int:
    from scipy.spatial import cKDTree

    p = np.asarray(pos, np.float64)
    pairs = cKDTree(p).query_pairs(2.0 * radius, output_type="ndarray")
    if pairs.size == 0:
        return 0
    d2 = np.sum((p[pairs[:, 0]] - p[pairs[:, 1]]) ** 2, axis=1)
    return int(np.count_nonzero(d2 < (2.0 * radius) ** 2))


def minimum_angle(pos: np.ndarray, edges: np.ndarray) -> float:
    p = np.asarray(pos, np.float64)
    src = np.concatenate([edges[:, 0], edges[:, 1]]).astype(np.int64)
    dst = np.concatenate([edges[:, 1], edges[:, 0]]).astype(np.int64)
    ang = np.arctan2(p[dst, 1] - p[src, 1], p[dst, 0] - p[src, 0])
    ang = np.where(ang < 0, ang + 2 * np.pi, ang)
    order = np.lexsort((ang, src))
    s, a = src[order], ang[order]
    starts = np.flatnonzero(np.r_[True, s[1:] != s[:-1]])
    deg = np.diff(np.r_[starts, s.size])
    gaps = np.where(s[1:] == s[:-1], np.diff(a), np.inf)
    gap_min = np.minimum.reduceat(np.r_[gaps, np.inf], starts)
    ends = starts + deg - 1
    wrap = 2 * np.pi - (a[ends] - a[starts])
    phi_min = np.minimum(gap_min, wrap)
    ideal = 2 * np.pi / deg
    return float(1.0 - np.mean((ideal - phi_min) / ideal))


def edge_length_variation(pos: np.ndarray, edges: np.ndarray) -> float:
    p = np.asarray(pos, np.float64)
    lengths = np.linalg.norm(p[edges[:, 0]] - p[edges[:, 1]], axis=1)
    n_e = lengths.size
    mean = lengths.mean()
    l_a = np.sqrt(np.sum((lengths - mean) ** 2) / (n_e * mean ** 2))
    return float(l_a / np.sqrt(n_e - 1))


def strip_crossings(pos: np.ndarray, edges: np.ndarray, n_strips: int,
                    axis: int, ideal: float):
    """``(count, deviation_sum)`` of one strip orientation."""
    p = np.asarray(pos, np.float64)
    a, b = edges[:, 0].astype(np.int64), edges[:, 1].astype(np.int64)
    x1, y1 = p[a, axis], p[a, 1 - axis]
    x2, y2 = p[b, axis], p[b, 1 - axis]
    theta = np.arctan2(p[b, 1] - p[a, 1], p[b, 0] - p[a, 0])
    theta = np.mod(np.where(theta < 0, theta + np.pi, theta), np.pi)
    lo = min(x1.min(), x2.min())
    hi = max(x1.max(), x2.max())
    width = (hi - lo) / n_strips
    xa, xb = np.minimum(x1, x2), np.maximum(x1, x2)
    bounds = lo + np.arange(n_strips + 1) * width
    # strips s with bounds[s] >= xa and bounds[s + 1] <= xb
    first = np.searchsorted(bounds, xa, side="left")
    last = np.searchsorted(bounds, xb, side="right") - 2
    first = np.minimum(first, n_strips)
    last = np.minimum(last, n_strips - 1)
    n_seg = np.maximum(last - first + 1, 0)
    eid = np.repeat(np.arange(edges.shape[0]), n_seg)
    strip = first[eid] + (np.arange(eid.size)
                          - np.repeat(np.cumsum(n_seg) - n_seg, n_seg))
    slope = (y2 - y1)[eid] / (x2 - x1)[eid]
    yl = y1[eid] + (bounds[strip] - x1[eid]) * slope
    yr = y1[eid] + (bounds[strip + 1] - x1[eid]) * slope

    order = np.lexsort((yl, strip))
    strip, eid, yl, yr = strip[order], eid[order], yl[order], yr[order]
    cuts = np.flatnonzero(np.r_[True, strip[1:] != strip[:-1], True])
    count, dev_sum = 0, 0.0
    for s0, s1 in zip(cuts[:-1], cuts[1:]):
        if s1 - s0 < 2:
            continue
        r = yr[s0:s1]
        # in yl order, a pair i < j reverses when yr_i > yr_j
        i, j = np.nonzero(np.triu(r[:, None] > r[None, :], 1))
        i, j = i + s0, j + s0
        keep = yl[i] < yl[j]
        ei, ej = eid[i[keep]], eid[j[keep]]
        keep = ((a[ei] != a[ej]) & (a[ei] != b[ej]) & (b[ei] != a[ej])
                & (b[ei] != b[ej]))
        ei, ej = ei[keep], ej[keep]
        d = np.abs(theta[ei] - theta[ej])
        acute = np.minimum(d, np.pi - d)
        count += ei.size
        dev_sum += float(np.sum(np.abs(ideal - acute) / ideal))
    return count, dev_sum


def scores(pos, edges, geometry: dict) -> dict:
    """All metrics of one layout under a configuration's ``geometry``."""
    pos = np.asarray(pos, np.float64)
    edges = np.asarray(edges, np.int64)
    ideal = np.deg2rad(float(geometry["ideal_angle_deg"]))
    per_axis = [strip_crossings(pos, edges, int(geometry["n_strips"]), ax,
                                ideal) for ax in (0, 1)]
    (c0, d0), (c1, d1) = per_axis
    best_c, best_d = (c1, d1) if c1 > c0 else (c0, d0)
    return {
        "node_occlusion": node_occlusion(pos, float(geometry["radius"])),
        "minimum_angle": minimum_angle(pos, edges),
        "edge_length_variation": edge_length_variation(pos, edges),
        "edge_crossing": max(c0, c1),
        "edge_crossing_angle": (1.0 - best_d / best_c) if best_c else 1.0,
        "crossing_count_for_angle": best_c,
    }
