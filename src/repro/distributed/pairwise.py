"""Distributed exact readability metrics (paper S3.1) via shard_map.

Two strategies, mirroring how a Spark all-pairs join maps onto a TPU mesh
(DESIGN.md S2):

* ``replicated`` — pair-matrix *rows* shard across the mesh; the column
  operand (the full coordinate set, <= a few MB even at SNAP scale) is
  replicated. The Spark shuffle disappears entirely: zero per-step
  collectives until the final scalar psum.

* ``ring`` — both sides sharded; a K-step ``collective_permute`` ring
  streams column blocks around the mesh (double-buffer-friendly: XLA
  overlaps the permute of block t+1 with the compute of block t). This is
  the out-of-HBM path for layouts too large to replicate, and the
  compile-time proof that the collective schedule is sane.

Counting masks use *global* indices derived from ``lax.axis_index`` so
the i<j dedup works across shards.
"""

from __future__ import annotations

import functools

import jax
from jax import shard_map
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.geometry import (pair_dist_sq, segments_cross,
                                 segments_cross_bool)
from repro.core.validate import BackendUnavailableError, ReadabilityError


def _flat_axes(mesh: Mesh):
    return tuple(mesh.axis_names)


def _run_sharded(tag, mesh, fn, *args):
    """Execute a mesh dispatch behind the typed error taxonomy.

    A failed shard_map launch (device lost, XLA runtime error,
    incompatible mesh) used to surface as whatever raw exception the
    runtime threw — callers holding ``except ReadabilityError`` ladders
    (the session, the server) couldn't degrade on it.  One typed
    :class:`~repro.core.validate.BackendUnavailableError`, original
    chained; already-typed errors pass through untouched."""
    try:
        return fn(*args)
    except ReadabilityError:
        raise
    except Exception as err:
        raise BackendUnavailableError(
            f"{tag} dispatch over {mesh.size} devices failed: "
            f"{type(err).__name__}: {err}", request_index=0) from err


def _pad_rows(arr, n_pad, fill):
    pad = n_pad - arr.shape[0]
    if pad <= 0:
        return arr
    return jnp.concatenate([arr, jnp.full((pad,) + arr.shape[1:], fill,
                                          arr.dtype)])


def sharded_occlusion_count(mesh: Mesh, pos, radius, *, valid=None,
                            block: int = 1024):
    """Row-sharded exact N_c over every mesh axis (replicated strategy)."""
    axes = _flat_axes(mesh)
    n_dev = mesh.size
    n = pos.shape[0]
    if valid is None:
        valid = jnp.ones(n, bool)
    n_pad = -(-n // (n_dev * block)) * (n_dev * block)
    x = _pad_rows(pos[:, 0], n_pad, 0.0)
    y = _pad_rows(pos[:, 1], n_pad, 0.0)
    ok = _pad_rows(valid, n_pad, False)
    rows_per = n_pad // n_dev
    thresh = jnp.asarray((2.0 * radius) ** 2, pos.dtype)

    def shard_fn(xs, ys, oks, xg, yg, okg):
        dev = lax.axis_index(axes).astype(jnp.int32)
        row0 = dev * rows_per
        col_idx = jnp.arange(n_pad, dtype=jnp.int32)

        def row_block(i0):
            xi = lax.dynamic_slice(xs[0], (i0,), (block,))
            yi = lax.dynamic_slice(ys[0], (i0,), (block,))
            oi = lax.dynamic_slice(oks[0], (i0,), (block,))
            gi = row0 + i0 + jnp.arange(block, dtype=jnp.int32)
            d2 = pair_dist_sq(xi, yi, xg, yg)
            mask = (gi[:, None] < col_idx[None, :]) & oi[:, None] & okg[None]
            return jnp.sum(jnp.where(mask & (d2 < thresh), 1, 0))

        starts = jnp.arange(0, rows_per, block, dtype=jnp.int32)
        local = jnp.sum(lax.map(row_block, starts))
        return lax.psum(local, axes)

    fn = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(axes), P(axes), P(axes), P(), P(), P()),
        out_specs=P(), check_vma=False)
    # row shards keep a leading (1, rows_per) block inside shard_map
    return _run_sharded(
        "row-sharded occlusion", mesh, jax.jit(fn),
        x.reshape(n_dev, rows_per), y.reshape(n_dev, rows_per),
        ok.reshape(n_dev, rows_per), x, y, ok)


def ring_occlusion_count(mesh: Mesh, pos, radius, *, valid=None):
    """Ring-streamed exact N_c: both operands sharded; K permute steps."""
    axes = _flat_axes(mesh)
    n_dev = mesh.size
    n = pos.shape[0]
    if valid is None:
        valid = jnp.ones(n, bool)
    n_pad = -(-n // n_dev) * n_dev
    x = _pad_rows(pos[:, 0], n_pad, 0.0)
    y = _pad_rows(pos[:, 1], n_pad, 0.0)
    ok = _pad_rows(valid, n_pad, False)
    per = n_pad // n_dev
    thresh = jnp.asarray((2.0 * radius) ** 2, pos.dtype)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def shard_fn(xs, ys, oks):
        dev = lax.axis_index(axes).astype(jnp.int32)
        my_rows = dev * per + jnp.arange(per, dtype=jnp.int32)
        xi, yi, oi = xs[0], ys[0], oks[0]

        def step(k, carry):
            total, cx, cy, cok = carry
            # after k forward permutes, the resident block originated
            # k devices *behind* us on the ring
            src_dev = (dev - k) % n_dev
            col_idx = src_dev * per + jnp.arange(per, dtype=jnp.int32)
            d2 = pair_dist_sq(xi, yi, cx, cy)
            mask = (my_rows[:, None] < col_idx[None, :]) \
                & oi[:, None] & cok[None, :]
            total = total + jnp.sum(jnp.where(mask & (d2 < thresh), 1, 0))
            # stream the column block to the next device (overlappable)
            cx = _permute(cx, axes, perm)
            cy = _permute(cy, axes, perm)
            cok = _permute(cok, axes, perm)
            return total, cx, cy, cok

        total = jnp.zeros((), jnp.int32)
        total, *_ = lax.fori_loop(0, n_dev, step, (total, xi, yi, oi))
        return lax.psum(total, axes)

    fn = shard_map(shard_fn, mesh=mesh,
                       in_specs=(P(axes), P(axes), P(axes)), out_specs=P(), check_vma=False)
    return _run_sharded(
        "ring-streamed occlusion", mesh, jax.jit(fn),
        x.reshape(n_dev, per), y.reshape(n_dev, per),
        ok.reshape(n_dev, per))


def _permute(arr, axes, perm):
    """collective_permute along the flattened device ring."""
    if len(axes) == 1:
        return lax.ppermute(arr, axes[0], perm)
    # flatten multi-axis mesh into one logical ring via nested ppermute:
    # treat the last axis as the fast ring; a full rotation of the last
    # axis then shifts the outer axes once.
    return lax.ppermute(arr, axes, perm)


def sharded_crossing_count(mesh: Mesh, pos, edges, *, edge_valid=None,
                           block: int = 256):
    """Row-sharded exact E_c (replicated strategy)."""
    axes = _flat_axes(mesh)
    n_dev = mesh.size
    e = edges.shape[0]
    if edge_valid is None:
        edge_valid = jnp.ones(e, bool)
    p = pos[edges[:, 0]]
    q = pos[edges[:, 1]]
    x1, y1, x2, y2 = p[:, 0], p[:, 1], q[:, 0], q[:, 1]
    v = edges[:, 0].astype(jnp.int32)
    u = edges[:, 1].astype(jnp.int32)
    e_pad = -(-e // (n_dev * block)) * (n_dev * block)
    arrs = [_pad_rows(a, e_pad, f) for a, f in
            ((x1, 0.0), (y1, 0.0), (x2, 0.0), (y2, 0.0))]
    v = _pad_rows(v, e_pad, -1)
    u = _pad_rows(u, e_pad, -2)
    ok = _pad_rows(edge_valid, e_pad, False)
    per = e_pad // n_dev

    def shard_fn(sh, rep):
        dev = lax.axis_index(axes).astype(jnp.int32)
        row0 = dev * per
        gx1, gy1, gx2, gy2, gv, gu, gok = rep
        col_idx = jnp.arange(e_pad, dtype=jnp.int32)

        def row_block(i0):
            sl = lambda a: lax.dynamic_slice(a[0], (i0,), (block,))
            bx1, by1, bx2, by2, bv, bu, bok = (sl(a) for a in sh)
            gi = row0 + i0 + jnp.arange(block, dtype=jnp.int32)
            cross = segments_cross(
                bx1[:, None], by1[:, None], bx2[:, None], by2[:, None],
                gx1[None, :], gy1[None, :], gx2[None, :], gy2[None, :])
            shared = ((bv[:, None] == gv[None, :]) |
                      (bv[:, None] == gu[None, :]) |
                      (bu[:, None] == gv[None, :]) |
                      (bu[:, None] == gu[None, :]))
            mask = (gi[:, None] < col_idx[None, :]) & bok[:, None] \
                & gok[None, :] & ~shared
            return jnp.sum(jnp.where(mask & cross, 1, 0))

        starts = jnp.arange(0, per, block, dtype=jnp.int32)
        return lax.psum(jnp.sum(lax.map(row_block, starts)), axes)

    sharded = tuple(a.reshape(n_dev, per) for a in (*arrs, v, u, ok))
    rep = (*arrs, v, u, ok)
    fn = shard_map(shard_fn, mesh=mesh,
                       in_specs=(tuple(P(axes) for _ in sharded),
                                 tuple(P() for _ in rep)),
                       out_specs=P(), check_vma=False)
    return _run_sharded("row-sharded crossing", mesh, jax.jit(fn),
                        sharded, rep)


# ---------------------------------------------------------------------------
# AOT-lowerable builders (dry-run: full problem sizes, zero allocation)
# ---------------------------------------------------------------------------

def lower_sharded_occlusion(mesh: Mesh, n_vertices: int, radius: float, *,
                            block: int = 1024):
    """Build + lower the row-sharded exact N_c for abstract inputs."""
    axes = _flat_axes(mesh)
    n_dev = mesh.size
    n_pad = -(-n_vertices // (n_dev * block)) * (n_dev * block)
    rows_per = n_pad // n_dev
    thresh = jnp.asarray((2.0 * radius) ** 2, jnp.float32)

    def shard_fn(xs, ys, oks, xg, yg, okg):
        dev = lax.axis_index(axes).astype(jnp.int32)
        row0 = dev * rows_per
        col_idx = jnp.arange(n_pad, dtype=jnp.int32)

        def row_block(i0):
            xi = lax.dynamic_slice(xs[0], (i0,), (block,))
            yi = lax.dynamic_slice(ys[0], (i0,), (block,))
            oi = lax.dynamic_slice(oks[0], (i0,), (block,))
            gi = row0 + i0 + jnp.arange(block, dtype=jnp.int32)
            d2 = pair_dist_sq(xi, yi, xg, yg)
            mask = (gi[:, None] < col_idx[None, :]) & oi[:, None] & okg[None]
            return jnp.sum(jnp.where(mask & (d2 < thresh), 1, 0))

        starts = jnp.arange(0, rows_per, block, dtype=jnp.int32)
        return lax.psum(jnp.sum(lax.map(row_block, starts)), axes)

    fn = shard_map(shard_fn, mesh=mesh,
                       in_specs=(P(axes), P(axes), P(axes), P(), P(), P()),
                       out_specs=P(), check_vma=False)
    f32 = lambda s: jax.ShapeDtypeStruct(s, jnp.float32)
    b8 = lambda s: jax.ShapeDtypeStruct(s, jnp.bool_)
    args = (f32((n_dev, rows_per)), f32((n_dev, rows_per)),
            b8((n_dev, rows_per)), f32((n_pad,)), f32((n_pad,)),
            b8((n_pad,)))
    return jax.jit(fn), args


def lower_sharded_crossing(mesh: Mesh, n_edges: int, *, block: int = 256,
                           predicate: str = "sign"):
    """Build + lower the row-sharded exact E_c for abstract inputs.
    ``predicate='bool'`` uses the boolean-straddle form (SPerf cell A)."""
    cross_fn = segments_cross if predicate == "sign" else segments_cross_bool
    axes = _flat_axes(mesh)
    n_dev = mesh.size
    e_pad = -(-n_edges // (n_dev * block)) * (n_dev * block)
    per = e_pad // n_dev

    def shard_fn(sh, rep):
        dev = lax.axis_index(axes).astype(jnp.int32)
        row0 = dev * per
        gx1, gy1, gx2, gy2, gv, gu, gok = rep
        col_idx = jnp.arange(e_pad, dtype=jnp.int32)

        def row_block(i0):
            sl = lambda a: lax.dynamic_slice(a[0], (i0,), (block,))
            bx1, by1, bx2, by2, bv, bu, bok = (sl(a) for a in sh)
            gi = row0 + i0 + jnp.arange(block, dtype=jnp.int32)
            cross = cross_fn(
                bx1[:, None], by1[:, None], bx2[:, None], by2[:, None],
                gx1[None, :], gy1[None, :], gx2[None, :], gy2[None, :])
            shared = ((bv[:, None] == gv[None, :]) |
                      (bv[:, None] == gu[None, :]) |
                      (bu[:, None] == gv[None, :]) |
                      (bu[:, None] == gu[None, :]))
            mask = (gi[:, None] < col_idx[None, :]) & bok[:, None] \
                & gok[None, :] & ~shared
            return jnp.sum(jnp.where(mask & cross, 1, 0))

        starts = jnp.arange(0, per, block, dtype=jnp.int32)
        return lax.psum(jnp.sum(lax.map(row_block, starts)), axes)

    f32s = lambda: jax.ShapeDtypeStruct((n_dev, per), jnp.float32)
    i32s = lambda: jax.ShapeDtypeStruct((n_dev, per), jnp.int32)
    b8s = lambda: jax.ShapeDtypeStruct((n_dev, per), jnp.bool_)
    f32r = lambda: jax.ShapeDtypeStruct((e_pad,), jnp.float32)
    i32r = lambda: jax.ShapeDtypeStruct((e_pad,), jnp.int32)
    b8r = lambda: jax.ShapeDtypeStruct((e_pad,), jnp.bool_)
    sh = (f32s(), f32s(), f32s(), f32s(), i32s(), i32s(), b8s())
    rep = (f32r(), f32r(), f32r(), f32r(), i32r(), i32r(), b8r())
    fn = shard_map(shard_fn, mesh=mesh,
                       in_specs=(tuple(P(axes) for _ in sh),
                                 tuple(P() for _ in rep)),
                       out_specs=P(), check_vma=False)
    return jax.jit(fn), (sh, rep)
