"""Graph layouts: random placement and a JAX Fruchterman-Reingold.

The paper evaluates readability on random layouts (S4.1) and on FR
layouts (S4.2, Table 4); its conclusion highlights readability-in-the-
loop layout optimization — ``examples/layout_optimization.py`` drives
:func:`fruchterman_reingold` with the readability engine as the monitor.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def random_layout(n_vertices: int, seed: int = 0, scale: float = 100.0):
    rng = np.random.default_rng(seed)
    return rng.uniform(0.0, scale, size=(n_vertices, 2)).astype(np.float32)


def lattice_graph(n_v: int, seed: int = 0, frac_long: float = 0.02):
    """Layout-local graph: jittered lattice positions in a 100x100 box,
    lattice-neighbour edges plus a sprinkle of long-range ones.  Returns
    numpy ``(pos (V, 2) f32, edges (E, 2) i32)``, E ~ 2V.

    This is the enhanced algorithms' target regime (a mostly-readable
    layout, as produced inside an optimization loop): short edges span few
    strips, so per-strip capacities — and the O(cap^2 * strips) sweep —
    stay proportionate.  A uniformly-random edge set would make every
    edge span ~half the strips and blow the capacity up by ~100x, which
    benchmarks the degenerate worst case rather than the workload.
    """
    rng = np.random.default_rng(seed)
    side = int(np.ceil(np.sqrt(n_v)))
    iy, ix = np.divmod(np.arange(n_v), side)
    pos = np.stack([ix, iy], axis=1) * (100.0 / side)
    pos = (pos + rng.normal(0, 0.15 * 100.0 / side,
                            size=pos.shape)).astype(np.float32)
    right = np.stack([np.arange(n_v), np.arange(n_v) + 1], axis=1)
    right = right[(right[:, 1] < n_v) & (ix[: right.shape[0]] + 1 < side)]
    down = np.stack([np.arange(n_v), np.arange(n_v) + side], axis=1)
    down = down[down[:, 1] < n_v]
    edges = np.concatenate([right, down])
    n_long = int(frac_long * edges.shape[0])
    long_e = rng.integers(0, n_v, size=(2 * n_long, 2))
    long_e = long_e[long_e[:, 0] != long_e[:, 1]][:n_long]
    edges = np.concatenate([edges, long_e]).astype(np.int32)
    return pos, edges


@functools.partial(jax.jit, static_argnames=("n_iter", "block"))
def fruchterman_reingold(pos0, edges, *, n_iter: int = 100,
                         block: int = 512):
    """Force-directed layout (Fruchterman & Reingold 1991), blocked O(V^2)
    repulsion (the same tiling pattern as the exact occlusion sweep)."""
    n = pos0.shape[0]
    area = 100.0 * 100.0
    k = jnp.sqrt(area / n)
    n_pad = -(-n // block) * block
    pad = n_pad - n
    pos0 = jnp.concatenate(
        [pos0, jnp.full((pad, 2), 1e6, pos0.dtype)]) if pad else pos0
    valid = jnp.arange(n_pad) < n

    def repulsion(pos):
        def row_block(i0):
            pi = lax.dynamic_slice(pos, (i0, 0), (block, 2))
            d = pi[:, None, :] - pos[None, :, :]
            dist2 = jnp.maximum(jnp.sum(d * d, -1), 1e-4)
            f = (k * k / dist2)[:, :, None] * d / jnp.sqrt(dist2)[:, :, None]
            f = jnp.where(valid[None, :, None], f, 0.0)
            return jnp.sum(f, axis=1)
        starts = jnp.arange(0, n_pad, block)
        return lax.map(row_block, starts).reshape(n_pad, 2)

    def step(i, pos):
        t = 10.0 * (1.0 - i / n_iter) + 0.01          # cooling
        disp = repulsion(pos)
        d = pos[edges[:, 0]] - pos[edges[:, 1]]
        dist = jnp.sqrt(jnp.maximum(jnp.sum(d * d, -1), 1e-8))
        fa = (dist / k)[:, None] * d
        disp = disp.at[edges[:, 0]].add(-fa)
        disp = disp.at[edges[:, 1]].add(fa)
        norm = jnp.sqrt(jnp.maximum(jnp.sum(disp * disp, -1), 1e-8))
        lim = jnp.minimum(norm, t) / norm
        pos = pos + disp * lim[:, None]
        return jnp.where(valid[:, None], pos, 1e6)

    pos = lax.fori_loop(0, n_iter, step, pos0)
    return pos[:n]
