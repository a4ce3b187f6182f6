"""Pallas TPU kernel: tiled CCW edge-crossing counting (paper S3.1.4).

One grid step = a (TILE_I x TILE_J) tile of the edge-pair matrix. The
endpoint vectors for both tiles live in VMEM as ``(1, TILE)`` rows (the
row-tile side is turned into a column there); the four CCW orientation
tiles are pure VPU broadcast arithmetic. The shared-endpoint exclusion
and the i<j ownership mask are applied before the popcount, and each
step adds its column sums into the ``(1, TILE_J)`` partial row owned by
row tile ``i`` (``j`` is the reduction axis).

VMEM per step (TI=TJ=256, f32): 14 (1, 256) endpoint rows + ~6 (TI,TJ)
temporaries ~ 1.7 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.grid import count_dtype

TILE_I = 256
TILE_J = 256


def _cross_tile(ax1, ay1, ax2, ay2, bx1, by1, bx2, by2):
    """(TI,TJ) bool: proper CCW straddle between segment tiles."""
    def ccw(px, py, qx, qy, rx, ry):
        return jnp.sign((qx - px) * (ry - py) - (qy - py) * (rx - px))

    d1 = ccw(ax1, ay1, ax2, ay2, bx1, by1)
    d2 = ccw(ax1, ay1, ax2, ay2, bx2, by2)
    d3 = ccw(bx1, by1, bx2, by2, ax1, ay1)
    d4 = ccw(bx1, by1, bx2, by2, ax2, ay2)
    return (d1 * d2 <= 0) & (d3 * d4 <= 0)


def pair_tile_mask(x1i, y1i, x2i, y2i, vi, ui, oki,
                   x1j, y1j, x2j, y2j, vj, uj, okj, *, tile_i: int,
                   tile_j: int):
    """(TI,TJ) bool for one grid step over ``(1, TILE)`` endpoint refs:
    owned (i < j), both valid, no shared endpoint, properly crossing."""
    gi = pl.program_id(0)
    gj = pl.program_id(1)
    a = lambda r: r[...].reshape(tile_i, 1)
    b = lambda r: r[...]
    cross = _cross_tile(a(x1i), a(y1i), a(x2i), a(y2i),
                        b(x1j), b(y1j), b(x2j), b(y2j))
    shared = ((a(vi) == b(vj)) | (a(vi) == b(uj)) |
              (a(ui) == b(vj)) | (a(ui) == b(uj)))
    rows = gi * tile_i + lax.broadcasted_iota(jnp.int32, (tile_i, tile_j), 0)
    cols = gj * tile_j + lax.broadcasted_iota(jnp.int32, (tile_i, tile_j), 1)
    return (rows < cols) & (a(oki) > 0) & (b(okj) > 0) & ~shared & cross


def pair_grid_call(kernel, args, out_dtypes, *, tile_i: int, tile_j: int,
                   interpret: bool):
    """Run ``kernel`` over the (n/TI, n/TJ) pair grid of the 1-D edge
    arrays ``args`` (each passed once as the row tile, once as the column
    tile).  Returns one ``(n/TI, 1, TJ)`` partial-row array per dtype in
    ``out_dtypes``."""
    n = args[0].shape[0]
    assert n % tile_i == 0 and n % tile_j == 0, (n, tile_i, tile_j)
    grid = (n // tile_i, n // tile_j)
    rows = [a.reshape(1, n) for a in args]
    row_spec = pl.BlockSpec((1, tile_i), lambda i, j: (0, i))
    col_spec = pl.BlockSpec((1, tile_j), lambda i, j: (0, j))
    out_spec = pl.BlockSpec((None, 1, tile_j), lambda i, j: (i, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[row_spec] * len(rows) + [col_spec] * len(rows),
        out_specs=tuple(out_spec for _ in out_dtypes),
        out_shape=tuple(jax.ShapeDtypeStruct((grid[0], 1, tile_j), dt)
                        for dt in out_dtypes),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*rows, *rows)


def _crossing_kernel(x1i, y1i, x2i, y2i, vi, ui, oki,
                     x1j, y1j, x2j, y2j, vj, uj, okj,
                     out_ref, *, tile_i: int, tile_j: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    mask = pair_tile_mask(x1i, y1i, x2i, y2i, vi, ui, oki,
                          x1j, y1j, x2j, y2j, vj, uj, okj,
                          tile_i=tile_i, tile_j=tile_j)
    out_ref[...] += jnp.sum(mask.astype(jnp.int32), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("tile_i", "tile_j", "interpret"))
def crossing_count(x1, y1, x2, y2, v, u, valid, *, interpret: bool,
                   tile_i: int = TILE_I, tile_j: int = TILE_J) -> jax.Array:
    """Count properly-crossing edge pairs (i < j, no shared endpoint)."""
    kernel = functools.partial(_crossing_kernel, tile_i=tile_i, tile_j=tile_j)
    (partial_counts,) = pair_grid_call(
        kernel, (x1, y1, x2, y2, v, u, valid), (jnp.int32,),
        tile_i=tile_i, tile_j=tile_j, interpret=interpret)
    return jnp.sum(partial_counts, dtype=count_dtype())
