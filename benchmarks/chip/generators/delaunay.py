"""The ``delaunay`` generator."""

from __future__ import annotations

import numpy as np

from graphs import BOX, undirected


def build(params: dict):
    """DIMACS10 ``delaunay_n<k>``: the Delaunay triangulation of 2^k
    uniform random points in the unit square; the points are the layout
    (scaled to the box)."""
    from scipy.spatial import Delaunay

    n = 1 << int(params["log2_vertices"])
    rng = np.random.default_rng(int(params["seed"]))
    pts = rng.random((n, 2))
    tri = Delaunay(pts).simplices
    edges = np.concatenate([tri[:, [0, 1]], tri[:, [1, 2]], tri[:, [0, 2]]])
    pos = (pts * BOX).astype(np.float32)
    return pos, undirected(edges), BOX / np.sqrt(n)
