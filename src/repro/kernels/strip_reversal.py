"""Pallas TPU kernel: per-strip order-reversal counting (paper S3.2.2/3).

The enhanced edge-crossing algorithm's inner loop. Each strip bucket is a
(cap,) vector of left/right boundary ordinates (plus edge ids and
angles). Crossings inside the strip are order reversals
``(yl_i < yl_j) & (yr_i > yr_j)`` counted over the dense (cap x cap)
pair matrix — the TPU-native replacement for the paper's balanced-BST
sweep (DESIGN.md S2). Optionally fuses the crossing-angle deviation sum.

Grid = (n_strips, cap/T, cap/T): one step is a (T x T) tile of one
strip's pair matrix, with T the largest of 512/256/128 dividing cap, so
VMEM per step stays ~4 (T,T) tiles (~4 MB at T=512) whatever cap is.
Buckets travel as ``(n_strips, 1, cap)`` so every block is a
``(1, T)`` row; each step adds its column sums into the strip's
``(1, T)`` partial row (the two tile axes are reduction axes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.grid import count_dtype

TILES = (512, 256, 128)


def _reversal_kernel(yl_i, yr_i, th_i, v_i, u_i, ok_i,
                     yl_j, yr_j, th_j, v_j, u_j, ok_j,
                     count_ref, dev_ref, *, ideal: float, with_angle: bool,
                     tile: int):
    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _init():
        count_ref[...] = jnp.zeros_like(count_ref)
        dev_ref[...] = jnp.zeros_like(dev_ref)

    a = lambda r: r[...].reshape(tile, 1)
    b = lambda r: r[...]
    rev = (a(yl_i) < b(yl_j)) & (a(yr_i) > b(yr_j))
    shared = ((a(v_i) == b(v_j)) | (a(v_i) == b(u_j)) |
              (a(u_i) == b(v_j)) | (a(u_i) == b(u_j)))
    mask = rev & ~shared & (a(ok_i) > 0) & (b(ok_j) > 0)
    count_ref[...] += jnp.sum(mask.astype(jnp.int32), axis=0, keepdims=True)
    if with_angle:
        d = jnp.abs(a(th_i) - b(th_j))
        a_c = jnp.minimum(d, jnp.pi - d)
        # same formula as repro.core.engine.fused_reversal_block: a true
        # division, not a reciprocal multiply (keeps rounding aligned with
        # the jnp reversal path)
        dev = jnp.abs(ideal - a_c) / ideal
        dev_ref[...] += jnp.sum(jnp.where(mask, dev, 0.0), axis=0,
                                keepdims=True)


@functools.partial(jax.jit, static_argnames=("ideal", "with_angle",
                                             "interpret"))
def strip_reversal_stats(yl, yr, theta, v, u, valid, *, interpret: bool,
                         ideal: float = 1.0, with_angle: bool = False):
    """Bucketed reversal stats.

    Args: (n_strips, cap) arrays — ``yl``/``yr``/``theta`` f32, ``v``/``u``
    int32 parent-edge endpoints, ``valid`` int32; ``cap`` a multiple of
    128 (:func:`repro.kernels.ops.strip_reversal_op` pads it).
    Returns (count, deviation_sum) summed over all strips.
    """
    n_strips, cap = yl.shape
    tile = next((t for t in TILES if cap % t == 0), None)
    assert tile is not None, f"cap {cap} is not a multiple of 128"
    kernel = functools.partial(_reversal_kernel, ideal=float(ideal),
                               with_angle=with_angle, tile=tile)
    rows = [a.reshape(n_strips, 1, cap) for a in (yl, yr, theta, v, u, valid)]
    row_spec = pl.BlockSpec((None, 1, tile), lambda s, i, j: (s, 0, i))
    col_spec = pl.BlockSpec((None, 1, tile), lambda s, i, j: (s, 0, j))
    out_spec = pl.BlockSpec((None, 1, tile), lambda s, i, j: (s, 0, 0))
    counts, devs = pl.pallas_call(
        kernel,
        grid=(n_strips, cap // tile, cap // tile),
        in_specs=[row_spec] * 6 + [col_spec] * 6,
        out_specs=(out_spec, out_spec),
        out_shape=(jax.ShapeDtypeStruct((n_strips, 1, tile), jnp.int32),
                   jax.ShapeDtypeStruct((n_strips, 1, tile), jnp.float32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(*rows, *rows)
    return jnp.sum(counts, dtype=count_dtype()), jnp.sum(devs)
