"""Node occlusion ``N_c`` (paper S3.1.1 exact, S3.2.1 enhanced).

Two vertices are occluded when their centre distance is below the disc
diameter ``2r``. ``N_c`` counts occluded unordered pairs.

* ``count_occlusions_exact`` — the paper's all-pairs join, as a blocked
  dense pairwise sweep (row blocks via ``lax.map``; the Pallas kernel in
  :mod:`repro.kernels.occlusion_pairs` implements the same tile on TPU).
* ``count_occlusions_enhanced`` — the paper's 2r-grid divide and conquer:
  vertices bucketed per cell, half-neighbourhood dense compares, exact
  result (Table 3 reports 0% error; our tests assert equality).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import grid as gridlib
from repro.core.geometry import pair_dist_sq


def _pad_to(arr, n, fill=0.0):
    pad = n - arr.shape[0]
    if pad <= 0:
        return arr
    return jnp.concatenate([arr, jnp.full((pad,) + arr.shape[1:], fill, arr.dtype)])


def count_occlusions_exact(pos: jax.Array, radius, *, block: int = 1024,
                           valid=None) -> jax.Array:
    """Exact N_c: all vertex pairs (i < j) with dist^2 < (2r)^2."""
    n = pos.shape[0]
    if valid is None:
        valid = jnp.ones(n, dtype=bool)
    n_pad = -(-n // block) * block
    x = _pad_to(pos[:, 0], n_pad)
    y = _pad_to(pos[:, 1], n_pad)
    ok = _pad_to(valid, n_pad, False)
    thresh = jnp.asarray((2.0 * radius) ** 2, pos.dtype)
    idx = jnp.arange(n_pad, dtype=jnp.int32)

    def row_block(i0):
        xi = lax.dynamic_slice(x, (i0,), (block,))
        yi = lax.dynamic_slice(y, (i0,), (block,))
        oi = lax.dynamic_slice(ok, (i0,), (block,))
        ii = i0 + jnp.arange(block, dtype=jnp.int32)
        d2 = pair_dist_sq(xi, yi, x, y)
        mask = (ii[:, None] < idx[None, :]) & oi[:, None] & ok[None, :]
        return jnp.sum(jnp.where(mask & (d2 < thresh), 1, 0),
                       dtype=gridlib.count_dtype())

    starts = jnp.arange(0, n_pad, block, dtype=jnp.int32)
    return jnp.sum(lax.map(row_block, starts))


def count_occlusions_gridded_batched(pos: jax.Array, radius, origin, nx: int,
                                     ny: int, cap: int, *, valid=None,
                                     cell_block: int = 512, cell_size=None):
    """Natively batched enhanced N_c: ``(B, V, 2)`` -> ``((B,), (B,))``.

    The whole batch is grouped by ONE composite-key sort and gathered
    into ``(B * n_cells, cap)`` bucket rows
    (:func:`~repro.core.grid.gather_ragged_buckets` with uniform caps; no
    scatter, no vmap — vmapped argsort/scatter over the single-layout
    counter is the exact per-call overhead that made batching slower
    than a Python loop), then swept with per-row partial sums.  A single
    layout is ``B=1``.  Exact: the cell size (>= 2r, default 2r) bounds
    the interaction radius, so every occluding pair lands in the same
    cell or in a half-neighbourhood pair.

    ``valid`` may be ``(V,)`` (one mask for every layout — the serving
    bucket-padding case) or ``(B, V)``.
    """
    import numpy as np

    B, V = pos.shape[0], pos.shape[1]
    n_cells = nx * ny
    gridlib.CALL_COUNTS["cell_builds"] += 1
    _, _, cid = gridlib.cell_indices(pos, radius, origin, nx, ny,
                                     cell_size=cell_size)  # (B, V)
    vmask = None
    if valid is not None:
        vmask = jnp.broadcast_to(jnp.asarray(valid), (B, V))
    x, y, bval, _, overflow = gridlib.gather_ragged_buckets(
        cid, n_cells, np.arange(n_cells, dtype=np.int64) * cap,
        np.full(n_cells, cap, np.int64), pos[..., 0], pos[..., 1],
        valid=vmask)
    x = x.reshape(B * n_cells, cap)
    y = y.reshape(B * n_cells, cap)
    bval = bval.reshape(B * n_cells, cap)

    # per-layout neighbour ids: the half-neighbourhood never crosses the
    # batch boundary, so flat row b*n_cells + c pairs with b*n_cells + nbr
    nbr = gridlib.neighbour_bucket_ids(nx, ny)             # (n_cells, 4)
    nbr_f = jnp.where(
        nbr[None] >= 0,
        nbr[None] + jnp.arange(B, dtype=jnp.int32)[:, None, None] * n_cells,
        -1).reshape(B * n_cells, 4)
    nbr_ok = nbr_f >= 0
    nbr_idx = jnp.maximum(nbr_f, 0)
    thresh = jnp.asarray((2.0 * radius) ** 2, pos.dtype)

    rows = B * n_cells
    cell_block = min(cell_block, rows)
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
                       axis=(1, 2), dtype=gridlib.count_dtype())
        cx = x[ni].reshape(cell_block, -1)
        cy = y[ni].reshape(cell_block, -1)
        cv = (bval[ni] & no[:, :, None]).reshape(cell_block, -1)
        c2 = ((bx[:, :, None] - cx[:, None, :]) ** 2
              + (by[:, :, None] - cy[:, None, :]) ** 2)
        cmask = bv[:, :, None] & cv[:, None, :]
        cross = jnp.sum(jnp.where(cmask & (c2 < thresh), 1, 0),
                        axis=(1, 2), dtype=gridlib.count_dtype())
        return same + cross

    starts = jnp.arange(0, pad_rows, cell_block, dtype=jnp.int32)
    per_row = lax.map(block_fn, starts).reshape(pad_rows)[:rows]
    return per_row.reshape(B, n_cells).sum(axis=1), overflow


def count_occlusions_enhanced(pos, radius, *, valid=None, cell_block: int = 512):
    """Host-facing enhanced N_c: plans the grid from the data, then runs the
    gridded counter at B=1. Returns (count, overflow)."""
    origin, nx, ny, cap, size = gridlib.plan_occlusion_grid(pos, radius)
    count, overflow = count_occlusions_gridded_batched(
        jnp.asarray(pos)[None], radius, origin, nx, ny, cap, valid=valid,
        cell_block=min(cell_block, nx * ny), cell_size=size)
    return count[0], overflow[0]
