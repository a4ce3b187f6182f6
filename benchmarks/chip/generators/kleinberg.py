"""The ``kleinberg`` generator."""

from __future__ import annotations

import numpy as np

from graphs import BOX, undirected


def _ring_offsets(d: np.ndarray, t: np.ndarray):
    """The ``t``-th of the ``4 d`` lattice offsets at Manhattan distance
    ``d`` (each offset of the ring exactly once)."""
    q, k = t // d, t % d
    dx = np.select([q == 0, q == 1, q == 2], [d - k, -k, -(d - k)], k)
    dy = np.select([q == 0, q == 1, q == 2], [k, d - k, -k], -(d - k))
    return dx, dy


def build(params: dict):
    """Kleinberg's small world (Nature 406, 2000): an n x n lattice with
    edges to the four lattice neighbours, plus ``q`` long-range contacts
    per node drawn with P(v) ~ d(u, v)^-r (lattice distance).  The
    lattice coordinates, jittered, are the layout.

    Sampling: a ring distance d with P(d) ~ d^(1-r) times a uniform point
    of the ring (4 d points) proposes each offset with probability
    ~ d^-r; proposals that leave the lattice are drawn again, which
    leaves P(v) ~ d(u, v)^-r over the nodes that exist."""
    side = int(params["side"])
    q, r = int(params["contacts"]), float(params["exponent"])
    rng = np.random.default_rng(int(params["seed"]))
    n = side * side
    iy, ix = np.divmod(np.arange(n), side)
    right = np.stack([np.arange(n), np.arange(n) + 1], 1)[ix < side - 1]
    down = np.stack([np.arange(n), np.arange(n) + side], 1)[iy < side - 1]

    d_all = np.arange(1, 2 * (side - 1) + 1)
    p_d = d_all ** (1.0 - r)
    p_d = p_d / p_d.sum()
    src = np.repeat(np.arange(n), q)
    dst = np.full(src.shape, -1, np.int64)
    todo = np.arange(src.size)
    while todo.size:
        d = rng.choice(d_all, size=todo.size, p=p_d)
        t = (rng.random(todo.size) * 4 * d).astype(np.int64)
        dx, dy = _ring_offsets(d, t)
        x, y = ix[src[todo]] + dx, iy[src[todo]] + dy
        ok = (x >= 0) & (x < side) & (y >= 0) & (y < side)
        dst[todo[ok]] = y[ok] * side + x[ok]
        todo = todo[~ok]
    edges = np.concatenate([right, down, np.stack([src, dst], 1)])

    spacing = BOX / side
    pos = np.stack([ix, iy], 1) * spacing
    pos = pos + rng.normal(0.0, float(params["jitter"]) * spacing, pos.shape)
    return pos.astype(np.float32), undirected(edges), spacing
