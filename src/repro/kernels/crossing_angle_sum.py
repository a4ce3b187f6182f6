"""Pallas TPU kernel: fused crossing-count + angle-deviation sum
(paper S3.1.5 / S3.2.3).

The paper's 2-D dynamic segment tree exists to avoid materializing the
crossing pairs on a sequential machine. The TPU tile *is* the
materialized pair block, so E_ca collapses to one fused masked reduction
over the same CCW tile the crossing count uses — two partial rows per
row tile: count (int32) and deviation sum (f32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.grid import count_dtype
from repro.kernels.segment_crossing import pair_grid_call, pair_tile_mask

TILE_I = 256
TILE_J = 256


def _angle_kernel(x1i, y1i, x2i, y2i, thi, vi, ui, oki,
                  x1j, y1j, x2j, y2j, thj, vj, uj, okj,
                  count_ref, dev_ref, *, ideal: float, tile_i: int,
                  tile_j: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        count_ref[...] = jnp.zeros_like(count_ref)
        dev_ref[...] = jnp.zeros_like(dev_ref)

    mask = pair_tile_mask(x1i, y1i, x2i, y2i, vi, ui, oki,
                          x1j, y1j, x2j, y2j, vj, uj, okj,
                          tile_i=tile_i, tile_j=tile_j)
    d = jnp.abs(thi[...].reshape(tile_i, 1) - thj[...])
    a_c = jnp.minimum(d, jnp.pi - d)
    dev = jnp.abs(ideal - a_c) * (1.0 / ideal)
    count_ref[...] += jnp.sum(mask.astype(jnp.int32), axis=0, keepdims=True)
    dev_ref[...] += jnp.sum(jnp.where(mask, dev, 0.0), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("ideal", "tile_i", "tile_j",
                                             "interpret"))
def crossing_angle_stats(x1, y1, x2, y2, theta, v, u, valid, *, ideal: float,
                         interpret: bool, tile_i: int = TILE_I,
                         tile_j: int = TILE_J):
    """Returns (crossing count, sum of |ideal - a_c| / ideal)."""
    kernel = functools.partial(_angle_kernel, ideal=float(ideal),
                               tile_i=tile_i, tile_j=tile_j)
    # the kernel's ref order is (x1, y1, x2, y2, theta, v, u, valid)
    counts, devs = pair_grid_call(
        kernel, (x1, y1, x2, y2, theta, v, u, valid),
        (jnp.int32, jnp.float32), tile_i=tile_i, tile_j=tile_j,
        interpret=interpret)
    return jnp.sum(counts, dtype=count_dtype()), jnp.sum(devs)
