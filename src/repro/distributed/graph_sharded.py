"""Graph-axis sharded evaluation: ONE layout spatially partitioned.

The paper's headline numbers (17x node occlusion / 146x edge crossing on
a Spark cluster, fig. 4) are about a *single graph too large for one
worker* — the orthogonal decomposition to
:mod:`repro.distributed.batched`, which shards the batch axis and needs
every layout to fit one device.  This driver partitions the
decompositions of one layout contiguously across a 1-D mesh
(:func:`repro.core.grid.plan_graph_shards`):

* **strips** (E_c / E_ca): shard ``i`` sweeps strips
  ``[i * strips_per_shard, ...)`` — embarrassingly parallel, zero
  collectives beyond the final psum of partial (count, deviation) sums;
* **occlusion cells** (N_c): contiguous flat-cell ranges with exactly
  ONE one-sided halo exchange
  (:func:`repro.distributed.collectives.halo_exchange`) for boundary
  cells; the owner-cell rule counts each cross-boundary pair once;
* **M_a**: split by vertex ownership — the topology sort is
  replicated, each device scores the vertex runs that start in its share
  of it, and one psum joins the partial sums;
* **M_l**: replicated (an O(E) pass, cheaper than any collective).

Inputs are fully replicated (coordinates are O(V) — what's sharded is
the O(pairs) sweep *work*, which is what dominates at scale); outputs
are replicated psum totals.  Integer metrics are bit-identical to the
single-host fused engine under the same flat-capacity plan and are
invariant to the shard count (1/2/4 devices) — ``tests/test_graph_sharded.py``
proves both, and the ``halo_exchanges`` counter in
:data:`repro.core.grid.CALL_COUNTS` certifies the collective budget:
one exchange per evaluation, zero for strip-only metric subsets.

``Evaluator(EvalConfig(backend="graph_sharded"))`` routes here through
:class:`repro.launch.session.EvalSession`, which adds the degradation
ladder (graph_sharded -> single-host fused on mesh loss, through the
:class:`~repro.core.validate.BackendUnavailableError` taxonomy).
"""

from __future__ import annotations

import dataclasses

import jax
from jax import shard_map
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import tracing
from repro.core import engine
from repro.core import grid as gridlib
from repro.core.validate import BackendUnavailableError


def plan_with_shard_spec(plan, n_shards: int, edges=None,
                         n_valid_edges=None):
    """``plan`` with its ``graph_shard`` spec matching ``n_shards``.

    Derives the per-device strip/cell ranges from the plan's own grid
    geometry, so a replanned (grown) plan re-derives fresh ranges — the
    spec can never go stale relative to the capacities.  The M_a degree
    bound (``GraphShardSpec.deg_cap``) is kept from the plan's spec;
    a plan without one takes it from ``edges[:n_valid_edges]`` on the
    host (:func:`~repro.core.grid.plan_degree_cap`), so planning once
    (``Evaluator.plan``, the session's plan cache) keeps that pass out
    of every call.  Returns the plan unchanged when the spec already
    matches (plan equality keeps the jit cache warm)."""
    deg_cap = plan.graph_shard.deg_cap if plan.graph_shard is not None else 0
    if deg_cap <= 0 and edges is not None:
        deg_cap = gridlib.plan_degree_cap(edges, n_valid_edges)
    spec = gridlib.plan_graph_shards(plan.n_strips, plan.grid_nx,
                                     plan.grid_ny, n_shards, deg_cap)
    if plan.graph_shard == spec:
        return plan
    return dataclasses.replace(plan, graph_shard=spec)


def _graph_sharded(plan, mesh, pos, edges, n_valid_vertices=None,
                   n_valid_edges=None):
    """Traced body: shard_map the per-shard engine body with fully
    replicated inputs.  ``plan`` and ``mesh`` are static."""
    axis = mesh.axis_names[0]
    valid_args = ()
    if n_valid_vertices is not None or n_valid_edges is not None:
        # both-or-neither, as in the batch-axis driver: a missing scalar
        # means "everything valid" = the natural size
        nv = pos.shape[0] if n_valid_vertices is None else n_valid_vertices
        ne = edges.shape[0] if n_valid_edges is None else n_valid_edges
        valid_args = (jnp.asarray(nv, jnp.int32),
                      jnp.asarray(ne, jnp.int32))

    def shard_fn(pos_rep, edges_rep, *valid):
        kw = ({"n_valid_vertices": valid[0], "n_valid_edges": valid[1]}
              if valid else {})
        return engine.evaluate_graph_shard_body(plan, pos_rep, edges_rep,
                                                axis_name=axis, **kw)

    fn = shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P()) + tuple(P() for _ in valid_args),
        out_specs=P(), check_vma=False)
    return fn(pos, edges, *valid_args)


_jit_graph_sharded = jax.jit(_graph_sharded,
                             static_argnames=("plan", "mesh"))


def evaluate_graph_sharded(mesh: Mesh, plan, pos, edges, *,
                           n_valid_vertices=None, n_valid_edges=None):
    """Evaluate ONE ``(V, 2)`` layout with its decompositions partitioned
    over ``mesh`` (1-D).

    Returns the same :class:`~repro.core.scores.ReadabilityScores`
    device-scalar pytree as
    :func:`~repro.core.engine.evaluate_planned`, with integer metrics
    bit-identical to it under the same flat-capacity plan (plan with
    ``tier_strips=False`` — per-device slot maps must be uniform, so the
    sharded sweep always runs the flat top capacity).  The optional
    traced ``n_valid_vertices`` / ``n_valid_edges`` scalars follow the
    engine's padding contract, and the ``overflow`` field feeds
    :func:`~repro.core.engine.replan_on_overflow` exactly like the
    single-host result.

    ``plan`` is the ordinary host-side plan; its ``graph_shard`` spec is
    (re)derived here from ``mesh.size``, so callers never manage it.
    Dispatch failures surface as the typed
    :class:`~repro.core.validate.BackendUnavailableError` with the
    original error chained.

    Program spans (:mod:`repro.tracing`): ``graph_sharded.inputs``
    around the conversion and placement of ``pos`` and ``edges``, and
    ``graph_sharded.launch`` around the jitted call.
    """
    host_edges = edges
    with tracing.span("graph_sharded.inputs"):
        pos = jnp.asarray(pos, plan.dtype)
        edges = jnp.asarray(edges, jnp.int32)
    if pos.ndim != 2:
        raise ValueError("evaluate_graph_sharded wants ONE (V, 2) layout "
                         f"(the graph axis is what's sharded); got shape "
                         f"{pos.shape}")
    if len(mesh.axis_names) != 1:
        raise ValueError("evaluate_graph_sharded wants a 1-D mesh; got "
                         f"axes {tuple(mesh.axis_names)}")
    plan = plan_with_shard_spec(plan, mesh.size, host_edges, n_valid_edges)
    try:
        with tracing.span("graph_sharded.launch"):
            return _jit_graph_sharded(plan, mesh, pos, edges,
                                      n_valid_vertices, n_valid_edges)
    except Exception as err:
        # a failed mesh dispatch (device lost, XLA runtime error) is an
        # infrastructure failure, not a caller bug: one typed error
        # class, original chained — the session's degradation ladder
        # catches this and falls back to the single-host fused engine
        raise BackendUnavailableError(
            f"graph-sharded dispatch over {mesh.size} devices failed: "
            f"{type(err).__name__}: {err}") from err
