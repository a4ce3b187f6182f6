"""Mesh-sharded *batched* evaluation: the batch axis over devices.

The paper's scaling story (17x node occlusion / 146x edge crossing on a
Spark cluster) is about one huge layout; the layout-*optimization*
workload — score B candidate layouts of one graph per search step, the
use case Kwon et al.'s ML predictor could not scale past ~600 nodes —
wants the orthogonal decomposition: shard the **batch axis** of the
natively batched engine program over a device mesh.

This composes two subsystems that were built independently:

* the native batched engine (:func:`repro.core.engine.evaluate_batched_body`):
  ONE composite-key sort per bucketing step groups a whole ``(B, M)``
  key batch (keys flattened to ``b_local * n_buckets + k`` inside the
  sort's per-row composite), and ONE occupancy-tiered reversal sweep per
  orientation covers the ``(B * n_strips_t, cap_t)`` rows through
  :func:`~repro.core.engine.fused_reversal_block`;
* the mesh drivers (:mod:`repro.distributed.gridded` /
  :mod:`repro.distributed.pairwise`): ``jax.shard_map`` over a device
  mesh.

The composition is embarrassingly parallel: every per-layout value in
the batched program is computed by per-layout-independent code (each
bucketing sort is per-row, each sweep reduction per-layout), so sharding
``(B, V, 2)`` into per-device ``(B/n_dev, V, 2)`` slices needs **zero
collectives** — each shard runs the full batched body on its local
slice, with the plan and edge topology replicated.  Integer metrics are
therefore *bit-identical* to the single-host
:func:`~repro.core.engine.evaluate_layouts` program (same decompositions,
same :func:`~repro.core.engine.fused_reversal_block` formula, same
best-orientation tie rule, order-independent integer sums), and float
metrics agree to rounding.

``Evaluator(EvalConfig(backend="distributed")).evaluate_batch`` routes
here; :class:`repro.launch.session.EvalSession` dispatches coalesced
serving batches through it when constructed with a ``mesh``.
"""

from __future__ import annotations

import jax
from jax import shard_map
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import engine
from repro.core.validate import BackendUnavailableError


def pad_batch_to_devices(batch_pos, n_dev: int):
    """Pad the batch axis up to a multiple of ``n_dev``.

    Filler rows are copies of layout 0 — real, in-extent coordinates, so
    they cannot trip capacity overflow that the natural batch would not
    (padding with zeros/PARK could overflow the occlusion grid's corner
    cell).  Returns ``(padded, natural_B)``; callers slice results back
    to ``natural_B`` rows.
    """
    B = batch_pos.shape[0]
    pad = (-B) % n_dev
    if pad == 0:
        return batch_pos, B
    filler = jnp.broadcast_to(batch_pos[:1], (pad,) + batch_pos.shape[1:])
    return jnp.concatenate([batch_pos, filler]), B


def _sharded_batched(plan, mesh, batch_pos, edges,
                     n_valid_vertices=None, n_valid_edges=None):
    """Traced body: shard_map the engine's batched program over the
    batch axis.  ``plan`` and ``mesh`` are static (jit cache keys)."""
    axes = tuple(mesh.axis_names)
    valid_args = ()
    if n_valid_vertices is not None or n_valid_edges is not None:
        # normalize to both-or-neither so the shard body has one shape;
        # a missing scalar means "everything valid" = the natural size
        nv = batch_pos.shape[1] if n_valid_vertices is None \
            else n_valid_vertices
        ne = edges.shape[0] if n_valid_edges is None else n_valid_edges
        valid_args = (jnp.asarray(nv, jnp.int32),
                      jnp.asarray(ne, jnp.int32))

    def shard_fn(pos_shard, edges_rep, *valid):
        # the ONE batched body (shared with the single-host jit) on this
        # device's (B_local, V, 2) slice — no collectives: every output
        # is per-layout, and the batch axis is the sharded axis
        return engine.evaluate_batched_body(plan, pos_shard, edges_rep,
                                            *valid)

    fn = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(axes), P()) + tuple(P() for _ in valid_args),
        out_specs=P(axes), check_vma=False)
    return fn(batch_pos, edges, *valid_args)


_jit_sharded_batched = jax.jit(_sharded_batched,
                               static_argnames=("plan", "mesh"))


def evaluate_layouts_sharded(mesh: Mesh, plan, batch_pos, edges, *,
                             n_valid_vertices=None, n_valid_edges=None):
    """Mesh-sharded :func:`~repro.core.engine.evaluate_layouts`:
    ``(B, V, 2)`` candidate layouts of one graph, batch axis sharded over
    ``mesh``, one dispatch.

    Returns the same batched :class:`~repro.core.scores.ReadabilityScores`
    device pytree as the single-host program, with integer metrics
    bit-identical to it (see the module docstring) — ``B`` need not
    divide ``mesh.size``; the batch is padded with copies of layout 0
    and results sliced back.  The optional traced ``n_valid_vertices`` /
    ``n_valid_edges`` scalars follow the engine's padding contract
    (bucket-padded serving batches share one jit entry), and the
    ``overflow`` field feeds :func:`~repro.core.engine.replan_on_overflow`
    exactly like the single-host result.

    ``plan`` is the ordinary host-side
    :class:`~repro.core.engine.ReadabilityPlan` (plan from the whole
    batch, or any representative layout); it is replicated — only
    coordinates are sharded.
    """
    batch_pos = jnp.asarray(batch_pos, plan.dtype)
    edges = jnp.asarray(edges, jnp.int32)
    if batch_pos.ndim != 3:
        raise ValueError("evaluate_layouts_sharded wants a (B, V, 2) "
                         f"batch; got shape {batch_pos.shape}")
    padded, B = pad_batch_to_devices(batch_pos, mesh.size)
    try:
        res = _jit_sharded_batched(plan, mesh, padded, edges,
                                   n_valid_vertices, n_valid_edges)
    except Exception as err:
        # a failed mesh dispatch (device lost, XLA runtime error) is an
        # infrastructure failure, not a caller bug: surface it as the
        # typed BackendUnavailableError with the original chained, so
        # the serving session's degradation ladder (and direct callers)
        # can catch ONE error class for "this backend cannot dispatch"
        raise BackendUnavailableError(
            f"sharded dispatch over {mesh.size} devices failed: "
            f"{type(err).__name__}: {err}") from err
    if padded.shape[0] != B:
        res = jax.tree_util.tree_map(lambda a: a[:B], res)
    return res
