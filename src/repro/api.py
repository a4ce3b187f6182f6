"""One front door: config-driven readability evaluation.

The paper's pitch is that readability evaluation should be a cheap,
composable building block inside layout-generation loops.  This module
is the single public surface for that:

>>> from repro.api import EvalConfig, Evaluator
>>> ev = Evaluator(EvalConfig(radius=0.5, n_strips=128))
>>> scores = ev.evaluate(pos, edges)            # one layout
>>> batch = ev.evaluate_batch(batch_pos, edges) # B layouts, one dispatch
>>> scores.normalized()                         # [0, 1] readability view

Everything is driven by the frozen, hashable
:class:`~repro.core.keys.EvalConfig` — the ONE source of truth threaded
through engine planning (:meth:`EvalConfig.plan_kwargs`), the serving
session's plan-cache key, the server, and the distributed drivers.  All
paths return the typed :class:`~repro.core.scores.ReadabilityScores`
pytree (batch-aware fields, ``.normalized()`` view).

**Metric subsets are real at trace level**: a config with
``metrics=("edge_crossing",)`` plans no occlusion grid and its traced
program builds zero cell buckets and runs zero vertex-key sorts; an
occlusion-only config builds zero strip decompositions and runs zero
reversal sweeps.  The work counters in :mod:`repro.core.grid` certify
this (``tests/test_api.py``), and ``BENCH_engine.json`` records the
resulting speedups — consumers that want one metric (cf. Kwon et al.'s
one-model-per-metric predictor, PAPERS.md) pay for one metric.

Backends (see :class:`~repro.core.keys.EvalConfig`): ``"fused"``
(plan-cached jitted engine — default), ``"eager"`` (plan per call, no
jit cache growth), ``"kernels"`` (Pallas TPU kernels),
``"distributed"`` (``shard_map`` drivers over a mesh: strip-sharded
singles, batch-axis-sharded batches), and ``"graph_sharded"`` (ONE
layout spatially partitioned over the mesh with a single halo exchange
— the million-vertex single-graph path, served through the session's
degradation ladder).

The old entry points (``repro.core.metrics.evaluate_layout``,
``EvalSession(**kwargs)``, ``ReadabilityServer(method=...)``) remain as
thin deprecation shims that map onto an ``EvalConfig`` and call into
this module.
"""

from __future__ import annotations

from repro import tracing
from repro.core import engine
from repro.core.engine import ALL_METRICS  # noqa: F401  (re-export)
from repro.core.keys import (EvalConfig, pow2_bucket,  # noqa: F401
                             pow2_chunks, reset_deprecation_warnings,
                             topology_hash)
from repro.core.metrics import evaluate_exact  # noqa: F401  (re-export)
from repro.core.scores import (ReadabilityScores,  # noqa: F401
                               scores_from_batch, scores_from_result)
from repro.core.validate import (BackendUnavailableError,  # noqa: F401
                                 CancelledError, CapacityError,
                                 DeadlineExceededError, InvalidInputError,
                                 OverloadedError, ReadabilityError,
                                 validate_batch, validate_request)
from repro.launch.admission import CancelToken  # noqa: F401  (re-export)
from repro.launch.session import EvalSession
from repro.search import (GradientSearch, SearchResult)  # noqa: F401

__all__ = [
    "ALL_METRICS", "BackendUnavailableError", "CancelToken",
    "CancelledError", "CapacityError", "DeadlineExceededError", "EvalConfig",
    "EvalSession", "Evaluator", "GradientSearch", "InvalidInputError",
    "OverloadedError", "ReadabilityError", "ReadabilityScores",
    "SearchResult", "evaluate_exact", "evaluator_for", "pow2_bucket",
    "pow2_chunks", "reset_deprecation_warnings", "scores_from_batch",
    "scores_from_result", "topology_hash", "validate_batch",
    "validate_request",
]


class Evaluator:
    """Config-bound readability evaluator: plan once, evaluate many.

    * :meth:`plan` — host-side :class:`~repro.core.engine.ReadabilityPlan`
      from concrete data (hold it across a hot loop).
    * :meth:`evaluate` — one layout -> host
      :class:`~repro.core.scores.ReadabilityScores`.  On the fused /
      kernels backends this is served by an internal
      :class:`~repro.launch.session.EvalSession`, so repeated calls on
      the same topology reuse the cached plan and jit entry (pow2 shape
      buckets, auto-replan on overflow).  ``backend="eager"`` plans per
      call and runs the fused program eagerly (no jit cache growth);
      ``backend="distributed"`` routes through
      :func:`repro.distributed.gridded.evaluate_sharded` over ``mesh``;
      ``backend="graph_sharded"`` is served by the session too — ONE
      layout spatially partitioned over the mesh
      (:func:`repro.distributed.graph_sharded.evaluate_graph_sharded`),
      degrading to single-host fused on mesh loss.
    * :meth:`evaluate_batch` — ``(B, V, 2)`` candidate layouts of ONE
      graph in one natively batched dispatch; returns a batched
      :class:`ReadabilityScores` (fields carry a leading ``B`` dim;
      ``.unbatch()`` splits).  Pass ``plan=`` in hot loops.  On
      ``backend="distributed"`` the batch axis shards over the mesh
      (:func:`repro.distributed.batched.evaluate_layouts_sharded`;
      ``EvalConfig.shards`` bounds the device count) with integer
      metrics bit-identical to the single-host batched program.
    * :meth:`register_layout` / :meth:`update` — dynamic layouts: score
      once, then re-score small vertex moves incrementally (session
      backends dirty only the grid cells/strips whose membership
      changed — :mod:`repro.core.incremental`; integer metrics stay
      bit-identical to a from-scratch evaluation).
    * :meth:`search` — gradient-guided layout *generation*: descend the
      differentiable relaxations (:mod:`repro.core.soft`) of this
      config's metrics with AdamW from a seed layout, B parallel
      restarts per step in one batched dispatch (batch-axis sharded on
      ``backend="distributed"``), exact integer re-scores selecting the
      winner.  Returns a :class:`~repro.search.gradient.SearchResult`.
    * :meth:`session` — a fresh :class:`EvalSession` bound to the same
      config, for request streams that want the serving policy knobs.
    """

    def __init__(self, config: EvalConfig = None, *, mesh=None,
                 cache_size: int = 128, vertex_floor: int = 128,
                 edge_floor: int = 128, max_coalesce: int = 32,
                 update_dirty_threshold: float = 0.25):
        self.config = config if config is not None else EvalConfig()
        self.mesh = mesh
        self._session = None
        self._session_knobs = dict(cache_size=cache_size,
                                   vertex_floor=vertex_floor,
                                   edge_floor=edge_floor,
                                   max_coalesce=max_coalesce,
                                   update_dirty_threshold=update_dirty_threshold)
        # dynamic layouts on the non-session backends (eager /
        # distributed): (pos, edges) per layout_id, full re-eval per
        # update — the incremental path needs the session's resident
        # state (see repro.core.incremental)
        self._layouts = {}

    def __repr__(self):
        return f"Evaluator({self.config!r})"

    # -- planning -----------------------------------------------------------

    def plan(self, pos, edges) -> engine.ReadabilityPlan:
        """Host-side plan for ``pos`` ((V, 2) or a (B, V, 2) batch):
        the plan this evaluator's backend runs.  ``graph_sharded`` plans
        flat strips (its per-device slot maps must be SPMD-uniform) and
        carry the mesh's shard spec with the M_a degree bound, so a plan
        made here and passed back in is the plan :meth:`evaluate_batch`
        would make for itself."""
        sharded = self.config.backend == "graph_sharded"
        plan = engine.plan_readability(
            pos, edges, **self.config.plan_kwargs(tier_default=not sharded))
        if sharded:
            from repro.distributed.graph_sharded import plan_with_shard_spec
            plan = plan_with_shard_spec(plan, self._mesh().size, edges)
        return plan

    # -- sessions -----------------------------------------------------------

    def session(self, **knobs) -> EvalSession:
        """A fresh serving session bound to this config.

        An :class:`Evaluator` constructed with a ``mesh`` hands it to the
        session, which then shards coalesced batches over it (serving
        scale-out; results stay bit-identical on integer metrics)."""
        return EvalSession(self.config, **{"mesh": self.mesh,
                                           **self._session_knobs, **knobs})

    def _bound_session(self) -> EvalSession:
        if self._session is None:
            self._session = self.session()
        return self._session

    def _mesh(self):
        if self.mesh is None:
            # one bring-up policy for every serving-side mesh (shared
            # with EvalSession's graph_sharded default): visible devices,
            # capped by config.shards, pow2-trimmed
            from repro.launch.elastic import serving_mesh
            self.mesh = serving_mesh("eval", shards=self.config.shards)
        return self.mesh

    # -- evaluation ---------------------------------------------------------

    def evaluate(self, pos, edges) -> ReadabilityScores:
        """Score one layout; returns host scores (one transfer).

        Requests are checked per ``EvalConfig.validation`` on every
        backend: the fused/kernels paths validate inside the serving
        session; the eager and distributed paths run
        :func:`~repro.core.validate.validate_request` here (strict mode
        raises the typed :class:`InvalidInputError`; sanitize mode
        repairs and records the repair in ``scores.flags``)."""
        backend = self.config.backend
        if backend in ("fused", "kernels", "graph_sharded"):
            # graph_sharded rides the session too: it owns the mesh
            # bring-up, validation/quarantine, and the degradation
            # ladder down to single-host fused on mesh loss
            return self._bound_session().evaluate(pos, edges)
        import numpy as np
        pos, edges, flags = validate_request(
            pos, edges, mode=self.config.validation)
        pos = np.asarray(pos, np.float32)
        edges = np.asarray(edges, np.int32)
        n_v, n_e = pos.shape[0], edges.shape[0]
        degenerate = n_v == 0 or n_e == 0
        if backend == "distributed" and not degenerate:
            from repro.distributed.gridded import evaluate_sharded
            scores = evaluate_sharded(self._mesh(), pos, edges,
                                      config=self.config)
            return scores if flags is None else scores._replace(flags=flags)
        # eager (and the degenerate distributed case, where a mesh buys
        # nothing): plan from the concrete layout (flat strips — per-call
        # tier shapes would churn the eager sub-op compile caches) and
        # run the fused program without a jit cache entry.  Degenerate
        # requests (V=0 / E=0) pad to the engine's one-row minimum and
        # mask the padding via the n_valid scalars, so the traced body
        # never sees a zero-size array.
        plan = engine.plan_readability(
            pos, edges, **self.config.plan_kwargs(tier_default=False))
        valid = {}
        if degenerate:
            pos_p = np.zeros((max(n_v, 1), 2), np.float32)
            pos_p[:n_v] = pos
            edges_p = np.zeros((max(n_e, 1), 2), np.int32)
            edges_p[:n_e] = edges
            pos, edges = pos_p, edges_p
            valid = dict(n_valid_vertices=np.int32(n_v),
                         n_valid_edges=np.int32(n_e))
        res = engine.evaluate_once(plan, pos, edges,
                                   use_kernels=self.config.use_kernels,
                                   **valid)
        scores = scores_from_result(res, n_v, n_e)
        return scores if flags is None else scores._replace(flags=flags)

    # -- dynamic layouts (incremental re-evaluation) ------------------------

    def register_layout(self, layout_id, pos, edges) -> ReadabilityScores:
        """Register a dynamic layout for :meth:`update` streams.

        Validates and fully evaluates ``pos`` once, returning its
        scores.  On the session backends (``"fused"``, ``"kernels"``,
        ``"graph_sharded"``) the bound :class:`EvalSession` also primes
        device-resident per-cell/per-strip partials
        (:mod:`repro.core.incremental`) so subsequent updates re-touch
        only dirty grid cells and strips; on ``"eager"`` /
        ``"distributed"`` the layout is tracked host-side and every
        update is a documented full re-evaluation."""
        backend = self.config.backend
        if backend in ("fused", "kernels", "graph_sharded"):
            return self._bound_session().register_layout(layout_id, pos, edges)
        import numpy as np
        scores = self.evaluate(pos, edges)
        self._layouts[layout_id] = (np.array(pos, np.float32, copy=True),
                                    np.array(edges, np.int32, copy=True))
        return scores

    def update(self, layout_id, moved_idx, new_pos) -> ReadabilityScores:
        """Move ``moved_idx`` of a registered layout to ``new_pos`` and
        re-score.

        Session backends route through
        :meth:`repro.launch.session.EvalSession.update` — incremental
        when the dirty set is small (integer metrics bit-identical to a
        from-scratch evaluation; ``scores.flags["incremental"]``
        certifies the path taken), full re-eval otherwise.  The eager
        and distributed backends always re-evaluate in full."""
        backend = self.config.backend
        if backend in ("fused", "kernels", "graph_sharded"):
            return self._bound_session().update(layout_id, moved_idx, new_pos)
        import numpy as np
        if layout_id not in self._layouts:
            raise KeyError(f"unknown layout_id {layout_id!r}; "
                           "register_layout() first")
        pos, edges = self._layouts[layout_id]
        moved = np.asarray(moved_idx, np.int64).reshape(-1)
        new_xy = np.asarray(new_pos, np.float32).reshape(-1, 2)
        if moved.size == 0 or moved.size != new_xy.shape[0]:
            raise InvalidInputError(
                "update wants matching non-empty moved_idx / new_pos; "
                f"got {moved.size} indices, {new_xy.shape[0]} positions")
        if self.config.validation != "off":
            if moved.min(initial=0) < 0 or \
                    moved.max(initial=-1) >= pos.shape[0]:
                raise InvalidInputError(
                    f"moved_idx out of range for {pos.shape[0]} vertices")
            if not np.isfinite(new_xy).all():
                raise InvalidInputError("non-finite new_pos in update")
        pos[moved] = new_xy
        return self.evaluate(pos, edges)

    def evaluate_batch(self, batch_pos, edges, *,
                       plan: engine.ReadabilityPlan = None
                       ) -> ReadabilityScores:
        """Score ``(B, V, 2)`` candidate layouts of one graph in one
        natively batched dispatch; returns a batched host
        :class:`ReadabilityScores` (``.unbatch()`` for per-layout
        scores).  Plans from the whole batch when ``plan`` is omitted —
        hot loops should plan once and pass it in.

        The shared edge list is checked per ``EvalConfig.validation``
        (:func:`~repro.core.validate.validate_batch`): strict raises the
        typed :class:`InvalidInputError` on out-of-range edges or a
        non-finite member layout; sanitize repairs the topology once for
        the whole batch and records it in ``scores.flags``."""
        with tracing.span("evaluator.evaluate_batch"):
            return self._evaluate_batch(batch_pos, edges, plan)

    def _evaluate_batch(self, batch_pos, edges, plan):
        import jax
        import numpy as np
        with tracing.span("evaluator.validate"):
            batch_pos = np.asarray(batch_pos, np.float32)
            edges = np.asarray(edges, np.int32)
            if batch_pos.ndim != 3:
                raise ValueError("evaluate_batch wants a (B, V, 2) batch; "
                                 f"got shape {batch_pos.shape}")
            batch_pos, edges, flags = validate_batch(
                batch_pos, edges, mode=self.config.validation)
        n_v, n_e = batch_pos.shape[1], edges.shape[0]
        backend = self.config.backend
        if n_v == 0 or n_e == 0:
            # degenerate batch: pad to the engine's one-row minimum,
            # mask via the n_valid scalars, and serve single-host (a
            # mesh buys nothing at this size) — well-defined scores
            # instead of the old zero-size planning crash
            B = batch_pos.shape[0]
            pos_p = np.zeros((B, max(n_v, 1), 2), np.float32)
            pos_p[:, :n_v] = batch_pos
            edges_p = np.zeros((max(n_e, 1), 2), np.int32)
            edges_p[:n_e] = edges
            if plan is None:
                plan = self.plan(batch_pos, edges)
            with tracing.span("engine.dispatch"):
                if backend == "eager":
                    res = engine.evaluate_batched_body(
                        plan, pos_p, edges_p, np.int32(n_v), np.int32(n_e))
                else:
                    res = engine.evaluate_layouts(
                        plan, pos_p, edges_p, np.int32(n_v), np.int32(n_e),
                        use_kernels=self.config.use_kernels)
            with tracing.span("scores.fetch"):
                res = jax.device_get(res)
            return res._replace(n_vertices=n_v, n_edges=n_e, flags=flags)
        if backend == "distributed":
            # mesh-sharded native batching: the batch axis shards over
            # the device mesh, each shard running the engine's batched
            # body — integer metrics bit-identical to the single-host
            # evaluate_layouts program (see repro.distributed.batched)
            from repro.distributed.batched import evaluate_layouts_sharded
            mesh = self._mesh()
            if plan is None:
                plan = self.plan(batch_pos, edges)
            with tracing.span("engine.dispatch"):
                res = evaluate_layouts_sharded(mesh, plan, batch_pos, edges)
            with tracing.span("scores.fetch"):
                res = jax.device_get(res)
            return res._replace(n_vertices=n_v, n_edges=n_e, flags=flags)
        if backend == "graph_sharded":
            # spatial partitioning is per-layout: each member IS the
            # sharded unit, so the batch axis is a host-side loop of
            # graph-sharded dispatches (one jit entry — the plan and
            # mesh are static and shared).  Flat strips (see plan()).
            from repro.distributed.graph_sharded import evaluate_graph_sharded
            mesh = self._mesh()
            if plan is None:
                plan = self.plan(batch_pos, edges)
            results = []
            for i in range(batch_pos.shape[0]):
                with tracing.span("engine.dispatch"):
                    res = evaluate_graph_sharded(mesh, plan, batch_pos[i],
                                                 edges)
                with tracing.span("scores.fetch"):
                    results.append(jax.device_get(res))
            res = jax.tree_util.tree_map(
                lambda *xs: np.stack(xs), *results)
            return res._replace(n_vertices=n_v, n_edges=n_e, flags=flags)
        if plan is None:
            plan = self.plan(batch_pos, edges)
        with tracing.span("engine.dispatch"):
            if backend == "eager":
                res = engine.evaluate_batched_body(plan, batch_pos, edges)
            else:
                res = engine.evaluate_layouts(
                    plan, batch_pos, edges,
                    use_kernels=self.config.use_kernels)
        with tracing.span("scores.fetch"):
            res = jax.device_get(res)
        return res._replace(n_vertices=n_v, n_edges=n_e, flags=flags)

    # -- search -------------------------------------------------------------

    def search(self, pos0, edges, **knobs):
        """Gradient-guided layout search from ``pos0`` under this
        config's metric subset and geometry.

        ``pos0`` is a ``(V, 2)`` seed layout (jittered into ``restarts``
        parallel starts) or an explicit ``(B, V, 2)`` restart batch;
        ``knobs`` are :class:`~repro.search.gradient.GradientSearch`
        keywords (``steps``, ``restarts``, ``rescore_every``, ``opt``,
        ``weights``, ``temperature``, ...).  The soft loss anneals from
        ``EvalConfig.temperature``; inputs route through the same
        validation taxonomy as :meth:`evaluate_batch`.  Returns a
        :class:`~repro.search.gradient.SearchResult` — exact integer
        scores only, ``result.best_positions`` is the winning layout."""
        from repro.search import GradientSearch
        knobs.setdefault("mesh", self.mesh)
        return GradientSearch(self.config, **knobs).run(pos0, edges)


# ---------------------------------------------------------------------------
# the shared evaluator cache (what the deprecated kwarg mirrors map onto)
# ---------------------------------------------------------------------------

from collections import OrderedDict as _OrderedDict

_EVALUATORS: "_OrderedDict[EvalConfig, Evaluator]" = _OrderedDict()
_EVALUATOR_CACHE_SIZE = 64


def evaluator_for(config: EvalConfig) -> Evaluator:
    """The process-wide :class:`Evaluator` for ``config``.

    Keyed by the (frozen, canonicalized) config itself, so every old
    call site that spells the same configuration — whatever kwarg order
    or legacy entry point it used — shares one evaluator, one plan
    cache, and one set of jit entries.  This is what stops repeated
    ``evaluate_layout`` calls from re-planning and re-tracing per call.

    The cache is a small LRU (configs are few; plans inside each
    evaluator's session have their own LRU).  Note the jit trade the
    caching implies: every distinct *plan* holds a compiled executable
    in jax's jit cache, which jax never evicts — a long-lived process
    streaming unbounded distinct topologies or data-derived configs
    should use ``EvalConfig(backend="eager")`` (plan per call, no jit
    entries), which is the old wrapper's behavior.
    """
    ev = _EVALUATORS.get(config)
    if ev is None:
        ev = _EVALUATORS[config] = Evaluator(config)
    _EVALUATORS.move_to_end(config)
    while len(_EVALUATORS) > _EVALUATOR_CACHE_SIZE:
        _EVALUATORS.popitem(last=False)
    return ev
