"""Pallas TPU kernel: tiled pairwise occlusion counting (paper S3.1.1).

The Spark exact algorithm's ``join`` with a distance predicate becomes a
(TILE_I x TILE_J) sweep over the pair matrix. Each grid step loads two
coordinate tiles into VMEM, forms the squared-distance tile with VPU
broadcasts (the contraction dim is only 2, so the MXU form
|a|^2+|b|^2-2ab^T would run the systolic array at 2/128 utilisation —
the broadcast form is the right TPU mapping, see DESIGN.md S5), applies
the i<j ownership mask, and adds the tile's column sums into a
lane-dense ``(1, TILE_J)`` partial row owned by row tile ``i`` (the
``j`` grid axis is the reduction axis).

Coordinates travel as ``(1, n)`` rows, so every block's last two dims
are ``(1, TILE)`` — the full leading dim and a lane multiple, which is
what Mosaic accepts; the row-tile side is turned into a column in VMEM.

VMEM budget per step (defaults TI=TJ=512, f32):
  6x(1, 512) coordinate rows + (TI,TJ) distance tile ~ 1 MB << 16 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.grid import count_dtype

TILE_I = 512
TILE_J = 512


def _occlusion_kernel(xi_ref, yi_ref, vi_ref, xj_ref, yj_ref, vj_ref,
                      out_ref, *, thresh: float, tile_i: int, tile_j: int):
    gi = pl.program_id(0)
    gj = pl.program_id(1)

    @pl.when(gj == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    col = lambda r: r[...].reshape(tile_i, 1)
    dx = col(xi_ref) - xj_ref[...]
    dy = col(yi_ref) - yj_ref[...]
    d2 = dx * dx + dy * dy
    rows = gi * tile_i + lax.broadcasted_iota(jnp.int32, (tile_i, tile_j), 0)
    cols = gj * tile_j + lax.broadcasted_iota(jnp.int32, (tile_i, tile_j), 1)
    mask = (rows < cols) & (col(vi_ref) > 0) & (vj_ref[...] > 0)
    hit = mask & (d2 < thresh)
    out_ref[...] += jnp.sum(hit.astype(jnp.int32), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("radius", "tile_i", "tile_j",
                                             "interpret"))
def occlusion_count(x: jax.Array, y: jax.Array, valid: jax.Array, *,
                    radius: float, interpret: bool, tile_i: int = TILE_I,
                    tile_j: int = TILE_J) -> jax.Array:
    """Count vertex pairs (i < j) with centre distance < 2*radius.

    Inputs are 1-D f32 coordinate arrays plus an int32 validity mask; the
    wrapper in :mod:`repro.kernels.ops` handles padding/layout and picks
    ``interpret``.
    """
    n = x.shape[0]
    assert n % tile_i == 0 and n % tile_j == 0, (n, tile_i, tile_j)
    grid = (n // tile_i, n // tile_j)
    kernel = functools.partial(_occlusion_kernel,
                               thresh=float((2.0 * radius) ** 2),
                               tile_i=tile_i, tile_j=tile_j)
    row_spec = pl.BlockSpec((1, tile_i), lambda i, j: (0, i))
    col_spec = pl.BlockSpec((1, tile_j), lambda i, j: (0, j))
    x, y, valid = (a.reshape(1, n) for a in (x, y, valid))
    partial_counts = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[row_spec] * 3 + [col_spec] * 3,
        out_specs=pl.BlockSpec((None, 1, tile_j), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((grid[0], 1, tile_j), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, y, valid, x, y, valid)
    return jnp.sum(partial_counts, dtype=count_dtype())
