"""Hand-written collective patterns (the optimized alternatives to
GSPMD-auto versions; compared in EXPERIMENTS.md §Perf).

* ``merge_decode_attention`` — flash-decoding softmax merge over a
  sequence-sharded KV cache: each shard computes partial (max, sum, out)
  over its KV slice; one fused psum merges them. The GSPMD baseline
  reaches the same result via separate max/sum all-reduces.

* ``sharded_embedding_lookup`` — range-partitioned embedding table
  lookup: each device resolves ids that fall in its row range and psums
  the (batch, dim) partials — O(batch x dim) traffic instead of the
  table all-gather a naive gather can degrade to.

* ``halo_exchange`` — the graph-sharded engine's ONE collective beyond
  final psums: each device receives the leading slab of its ring
  successor's arrays (boundary-cell buckets).  Bumps the
  ``halo_exchanges`` work counter in :data:`repro.core.grid.CALL_COUNTS`
  once per *trace*, which is how the tests and ``fig4_scaling --smoke``
  certify exactly one exchange per evaluation (zero for strip-only
  metric subsets, which never call this).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def merge_decode_attention(mesh: Mesh, q, k_cache, v_cache, pos, *,
                           seq_axis: str = "model"):
    """q: (B, H, dh) replicated; k/v_cache: (B, S, H, dh) sharded on S over
    ``seq_axis``. Returns (B, H, dh).

    Inside the shard: local scores -> local (m, l, o); merge:
      m* = pmax(m);  l* = psum(l e^{m-m*});  o* = psum(o l e^{m-m*}) / l*.
    """
    n_shard = mesh.shape[seq_axis]
    S = k_cache.shape[1]
    per = S // n_shard
    scale = q.shape[-1] ** -0.5

    def shard_fn(q, k, v, pos):
        idx = lax.axis_index(seq_axis)
        t = idx * per + jnp.arange(per, dtype=jnp.int32)
        s = jnp.einsum("bhd,bthd->bht", q, k).astype(jnp.float32) * scale
        s = jnp.where((t <= pos)[None, None, :], s, -1e30)
        m = jnp.max(s, axis=-1)                                   # (B, H)
        p = jnp.exp(s - m[..., None])
        l = jnp.sum(p, axis=-1)                                   # (B, H)
        o = jnp.einsum("bht,bthd->bhd", p.astype(v.dtype), v)
        m_star = lax.pmax(m, seq_axis)
        corr = jnp.exp(m - m_star)
        l_star = lax.psum(l * corr, seq_axis)
        o_star = lax.psum(o * corr[..., None].astype(o.dtype), seq_axis)
        return o_star / jnp.maximum(l_star, 1e-30)[..., None].astype(o.dtype)

    other = tuple(a for a in mesh.axis_names if a != seq_axis)
    fn = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(None, seq_axis), P(None, seq_axis), P()),
        out_specs=P(),
        check_vma=False)
    return fn(q, k_cache, v_cache, pos)


def halo_exchange(slabs, axis_name):
    """Receive each array's slab from the ring successor (``i + 1``).

    ``slabs`` is a pytree of same-leading-shape arrays — the caller's
    boundary-cell bucket rows.  Must run inside ``shard_map`` over
    ``axis_name``.  One ``ppermute`` per leaf, all the same pattern; the
    wrap-around slab (device ``n-1`` receives device 0's) is the
    caller's to mask — the graph-sharded sweep kills it with its
    global-cell-id bound.  On a 1-device mesh the permutation is the
    identity (the caller's mask makes the self-halo inert).
    """
    from repro.core import grid as gridlib
    gridlib.CALL_COUNTS["halo_exchanges"] += 1
    n = lax.psum(1, axis_name)
    perm = [((i + 1) % n, i) for i in range(n)]
    return jax.tree_util.tree_map(
        lambda a: lax.ppermute(a, axis_name, perm), slabs)


def sharded_embedding_lookup(mesh: Mesh, table, ids, *,
                             axis: str = "model"):
    """Range-partitioned lookup: table (V, d) sharded on rows over
    ``axis``; ids (...,) replicated. Returns (..., d) replicated."""
    n_shard = mesh.shape[axis]
    V = table.shape[0]
    assert V % n_shard == 0, (V, n_shard)
    per = V // n_shard

    def shard_fn(tbl, ids):
        idx = lax.axis_index(axis)
        lo = idx * per
        local = ids - lo
        in_range = (local >= 0) & (local < per)
        rows = jnp.take(tbl, jnp.clip(local, 0, per - 1), axis=0)
        rows = jnp.where(in_range[..., None], rows, 0.0)
        return lax.psum(rows, axis)

    fn = shard_map(shard_fn, mesh=mesh,
                       in_specs=(P(axis, None), P()), out_specs=P(),
                       check_vma=False)
    return fn(table, ids)
