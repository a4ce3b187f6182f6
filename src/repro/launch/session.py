"""Serving session: plan cache, padded shape buckets, auto-replan,
cross-request batching, and the fault-tolerance layer — config-driven.

This is the steady-state fast path the paper's use case implies (score
layout streams fast enough to sit inside generation loops).  A request is
``(pos, edges)``; the session turns a stream of them into a small number
of fused engine dispatches:

  request --> validate (:func:`repro.core.validate.validate_request`,
              mode = ``EvalConfig.validation``; a malformed request is
              QUARANTINED to its own slot here, before it can touch a
              coalesced batch)
          --> admission control (:func:`repro.launch.admission.admit`,
              the bounded queue: past ``max_queue`` / ``max_queue_cost``
              the excess is SHED — oldest-deadline-first — with
              :class:`~repro.core.validate.OverloadedError` in its own
              slot, before any padding or planning is spent on it)
          --> pow2 shape buckets (V, E rounded up; one bucket function —
              :func:`repro.core.keys.pow2_bucket` — shared by the
              plan-cache key and the padding)
          --> :class:`PlanCache` LRU  [(topology, buckets,
              :class:`~repro.core.keys.EvalConfig`)
              -> :class:`~repro.core.engine.ReadabilityPlan`]
          --> coalesce same-key requests into ``(B, V_pad, 2)`` batches
              --> ONE :func:`~repro.core.engine.evaluate_layouts` dispatch
              (natively batched: one composite-key sort per bucketing
              step and one occupancy-tiered sweep per orientation serve
              the whole coalesced batch)
          --> :class:`~repro.core.scores.ReadabilityScores` per request
              (one device->host transfer per dispatch)

The evaluation semantics come from ONE object: the frozen
:class:`~repro.core.keys.EvalConfig`, which is itself the tail of the
plan-cache key (no hand-assembled metric/kwarg tuples — a config change
is a key change, period).  Metric subsets are first-class: a
crossing-only config plans no occlusion grid and its traced program
builds no cell buckets (see the counters in :mod:`repro.core.grid`).

**The fault contract** (see ``docs/robustness.md`` for the full
taxonomy):

* *Poison quarantine* — validation runs per request BEFORE coalescing,
  so a NaN/Inf layout or an out-of-range edge list fails only its own
  slot: :meth:`EvalSession.evaluate_batch` returns an error-carrying
  :class:`~repro.core.scores.ReadabilityScores` (``.ok`` False,
  ``.error`` the typed :class:`~repro.core.validate.InvalidInputError`)
  in that slot and clean scores everywhere else — bit-identical on
  integer metrics to a run that never saw the poison.  The
  ``quarantined`` counter certifies it.  :meth:`EvalSession.evaluate`
  (single request) raises instead.
* *Admission control* — ``max_queue`` / ``max_queue_cost`` bound the
  work a burst may enqueue; the excess is shed deterministically
  (oldest-deadline-first, ties latest-arrival-first — see
  :func:`repro.launch.admission.admit`) with
  :class:`~repro.core.validate.OverloadedError` in the shed slots only.
  ``shed`` / ``queue_high_watermark`` certify it.  Unset bounds (the
  default) keep the pre-admission behavior bit-for-bit.
* *Deadlines* — per-request budgets (``default_deadline`` knob or the
  ``deadline=`` argument).  Queued requests whose deadline passes are
  reaped before their dispatch starts
  (:class:`~repro.core.validate.DeadlineExceededError` in their own
  slot, ``expired`` counter); cancelled
  :class:`~repro.launch.admission.CancelToken`\\ s likewise
  (``CancelledError``, ``cancelled`` counter).  No deadline (the
  default) means no clock reads on the hot path.
* *Hung-dispatch watchdog* — with a deadline or ``dispatch_timeout``
  in force, every engine dispatch runs under a wall-clock guard on a
  worker thread; a dispatch that exceeds its budget is ABANDONED
  (``watchdog_abandoned`` counter) into the split-and-retry path, so a
  wedged device call fails only its own chunk's slots with
  ``DeadlineExceededError`` while the rest of the queue keeps
  draining.  With neither in force, dispatch is direct (zero threads,
  zero overhead) — the steady-state fast path is untouched.
* *Dispatch splitting* — an exception out of a coalesced dispatch
  (injected or real) splits the chunk and retries members individually,
  so one bad interaction cannot fail B-1 innocent requests
  (``dispatch_failures`` / ``chunk_splits`` counters); a single request
  that still fails gets the error quarantined to its slot.
* *Bounded replan backoff* — capacity overflow replans with
  multiplicative capacity growth (``replan_growth ** attempt``, capped
  at ``growth_ceiling``) at most ``max_replan_retries`` times.  A
  result that STILL overflows surfaces
  :class:`~repro.core.validate.CapacityError` (strict) or a
  ``saturated``-flagged score (sanitize) instead of silently
  under-counting (the pre-fault-layer behavior, kept under
  ``validation="off"``).
* *Self-healing degradation ladder* — a mesh-sharded dispatch failure
  (mesh lost, shard_map error) falls back distributed -> fused
  single-host in the same dispatch (results stay bit-identical on
  integer metrics) and OPENS the session's
  :class:`~repro.launch.admission.CircuitBreaker`; traffic serves
  single-host while the breaker counts fused successes, goes
  half-open after ``probe_interval`` of them, and the next
  mesh-eligible dispatch is a CANARY PROBE — on success the circuit
  closes and sharded serving auto-restores (``probes`` /
  ``auto_restores`` counters), on failure it re-opens and the cycle
  repeats.  The same ladder serves ``backend="graph_sharded"`` (one
  layout spatially partitioned over the mesh,
  ``graph_sharded_dispatches`` counter).  :meth:`EvalSession.health`
  is the operational snapshot (``breaker_state`` included);
  :meth:`EvalSession.restore_mesh` stays as the manual override.

Padded tail vertices/edges are masked out on device via the engine's
``n_valid_vertices`` / ``n_valid_edges`` traced scalars, so every natural
size inside a bucket shares one jit cache entry (integer metrics are
bit-identical to natural-size evaluation; see the engine docstring).
After warmup, steady-state traffic is zero-replan and zero-retrace — the
``stats`` counters prove it.

Sessions plan FLAT strips (``tier_strips`` default ``False`` here, via
``EvalConfig.plan_kwargs(tier_default=False)``): a cached plan serves a
*stream* of same-topology layouts whose occupancy drifts between strips,
and the flat cap's uniform headroom absorbs that drift where tight
per-strip tiers would trip overflow -> replan -> retrace mid-steady-state.
An explicit ``EvalConfig(tier_strips=True)`` overrides.

The old ``EvalSession(radius=..., n_strips=..., ...)`` kwarg mirror is a
deprecation shim mapping onto :class:`~repro.core.keys.EvalConfig`.
"""

from __future__ import annotations

import dataclasses
import logging
import threading
from collections import Counter, OrderedDict

import numpy as np

from repro import tracing
from repro.core import engine
from repro.core import incremental
from repro.core.keys import (EvalConfig, pow2_bucket, pow2_chunks,
                             topology_hash, warn_once)
from repro.core.scores import (error_scores, scores_from_batch,
                               scores_from_result)
from repro.core.validate import (BackendUnavailableError, CancelledError,
                                 CapacityError, DeadlineExceededError,
                                 InvalidInputError, OverloadedError,
                                 ReadabilityError, validate_request)
from repro.launch import admission, faults
from repro.launch.admission import CircuitBreaker

_log = logging.getLogger(__name__)

# Park coordinate for padded tail vertices: far outside any real layout
# extent.  Correctness rests on the n_valid masks, not on this value —
# the park just keeps padded rows visibly inert in dumps/plots.
PARK = -1.0e6

# legacy alias (callers imported the chunker from here before keys.py)
_pow2_chunks = pow2_chunks

# EvalSession kwargs that are serving *policy*, not evaluation semantics
# (they do not belong in EvalConfig and are not deprecated)
_SESSION_KNOBS = ("cache_size", "vertex_floor", "edge_floor", "max_coalesce",
                  "max_replan_retries", "replan_growth", "growth_ceiling",
                  "max_queue", "max_queue_cost", "default_deadline",
                  "dispatch_timeout", "probe_interval",
                  "update_dirty_threshold")


class PlanCache:
    """LRU cache of ReadabilityPlans.

    Keys are ``(topology hash, vertex bucket, edge bucket, EvalConfig)``
    tuples — the config rides along whole (it is frozen and hashable),
    so *every* evaluation knob is part of the key by construction;
    values are hashable frozen plans, which the jitted evaluators take
    as static arguments — a cache hit therefore implies a jit cache hit
    for any request shape already traced.

    Thread-safe: every access (lookup, LRU reorder, counter bump,
    eviction) happens under one lock — watchdog worker threads and a UI
    thread driving ``session.update`` hit the cache concurrently, and an
    unsynchronized ``move_to_end`` mid-``popitem`` corrupts the
    ``OrderedDict``'s internal links.  Single-threaded behavior is
    unchanged.
    """

    def __init__(self, capacity: int = 128):
        self.capacity = int(capacity)
        self._entries: OrderedDict = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def get(self, key):
        with self._lock:
            plan = self._entries.get(key)
            if plan is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            self.hits += 1
            return plan

    def put(self, key, plan) -> None:
        with self._lock:
            self._entries[key] = plan
            self._entries.move_to_end(key)
            while len(self._entries) > self.capacity:
                self._entries.popitem(last=False)
                self.evictions += 1


class _BreakerBuffer:
    """Write-buffering view of the session's breaker for watchdog
    workers.

    Reads (:meth:`allow` / :attr:`probing`) delegate to the live breaker
    — the worker must see the real circuit state to pick a dispatch rung
    — but the outcome records are buffered as replayable events so the
    session can discard them wholesale when the watchdog abandons the
    dispatch: a worker the session has already given up on must not
    open, close, or half-open the circuit when it eventually finishes.
    """

    def __init__(self, breaker):
        self._breaker = breaker
        self.events = []

    def allow(self):
        return self._breaker.allow()

    @property
    def probing(self):
        return self._breaker.probing

    def record_success(self):
        self.events.append("record_success")

    def record_failure(self):
        self.events.append("record_failure")

    def record_fallback_success(self):
        self.events.append("record_fallback_success")


class EvalSession:
    """Plan-caching, shape-bucketing, request-coalescing evaluator with
    the fault-tolerance layer (quarantine, admission control, deadlines,
    the hung-dispatch watchdog, dispatch splitting, bounded replan
    backoff, self-healing backend degradation — see the module
    docstring).

    ``EvalSession(config)`` is the canonical constructor; the keyword
    knobs are serving policy (cache sizing, padding floors, coalescing
    width, replan bounds, overload bounds).  The old per-knob evaluation
    kwargs (``radius=``, ``n_strips=``, ...) are accepted as a
    deprecation shim and mapped onto an
    :class:`~repro.core.keys.EvalConfig`.

    Overload knobs (all default-off — unset, the session behaves
    bit-for-bit like the unbounded one):

    * ``max_queue`` — max requests admitted per ``evaluate_batch`` call;
    * ``max_queue_cost`` — max summed padded work units (vertex bucket +
      edge bucket) admitted at once;
    * ``default_deadline`` — seconds-from-arrival budget applied to
      every request that does not carry its own;
    * ``dispatch_timeout`` — wall-clock guard on each engine dispatch
      even when requests carry no deadline;
    * ``probe_interval`` — fused successes the breaker counts while
      open before re-probing the mesh (see
      :class:`~repro.launch.admission.CircuitBreaker`).
    """

    def __init__(self, config: EvalConfig = None, *, cache_size: int = 128,
                 vertex_floor: int = 128, edge_floor: int = 128,
                 max_coalesce: int = 32, max_replan_retries: int = 2,
                 replan_growth: float = 1.5, growth_ceiling: float = 4.0,
                 max_queue: int = None, max_queue_cost: int = None,
                 default_deadline: float = None,
                 dispatch_timeout: float = None, probe_interval: int = 8,
                 update_dirty_threshold: float = 0.25,
                 mesh=None, **legacy_kwargs):
        if legacy_kwargs:
            if config is not None:
                raise TypeError("pass either an EvalConfig or legacy "
                                f"kwargs, not both: {sorted(legacy_kwargs)}")
            warn_once(
                "EvalSession kwargs",
                "EvalSession(radius=..., n_strips=..., ...) is deprecated: "
                "pass EvalSession(EvalConfig(...)) — the config is the one "
                "source of truth shared with the engine and the plan cache")
            config = EvalConfig.from_legacy(**legacy_kwargs)
        self.config = config if config is not None else EvalConfig()
        if self.config.backend not in ("fused", "kernels", "graph_sharded"):
            raise ValueError(
                "EvalSession serves the jitted engine; backend must be "
                "'fused', 'kernels' or 'graph_sharded', got "
                f"{self.config.backend!r} "
                "(use repro.api.Evaluator for the other backends)")
        if self.config.backend == "graph_sharded" and mesh is None:
            # graph_sharded NEEDS a mesh (it is what the backend means);
            # the elastic policy picks the shape from visible devices,
            # capped by config.shards
            from repro.launch.elastic import serving_mesh
            mesh = serving_mesh("graph", shards=self.config.shards)
        self.vertex_floor = int(vertex_floor)
        self.edge_floor = int(edge_floor)
        self.max_coalesce = int(max_coalesce)
        self.max_replan_retries = int(max_replan_retries)
        self.replan_growth = float(replan_growth)
        self.growth_ceiling = float(growth_ceiling)
        self.max_queue = None if max_queue is None else int(max_queue)
        self.max_queue_cost = (None if max_queue_cost is None
                               else int(max_queue_cost))
        self.default_deadline = (None if default_deadline is None
                                 else float(default_deadline))
        self.dispatch_timeout = (None if dispatch_timeout is None
                                 else float(dispatch_timeout))
        # incremental updates: fall back to a full re-evaluation when a
        # move dirties more than this fraction of the vertices, the grid
        # cells, or either orientation's strips (past that point the
        # delta program's dirty-row rebuild stops being cheaper than the
        # full fused program)
        self.update_dirty_threshold = float(update_dirty_threshold)
        # registered dynamic layouts (session.update targets): host-side
        # records + device-resident partials, guarded per layout
        self._layouts = {}
        self._layouts_lock = threading.Lock()
        # mesh is serving policy, not evaluation semantics: when set (and
        # multi-device), coalesced batches dispatch through the
        # batch-axis-sharded driver — results stay bit-identical on
        # integer metrics, so routing is transparent to callers.  A mesh
        # dispatch failure opens the breaker: the degradation ladder then
        # serves single-host until a canary probe (or restore_mesh())
        # closes it again.
        self.mesh = mesh
        self.breaker = CircuitBreaker(probe_interval)
        self.plans = PlanCache(cache_size)
        # serializes watchdog abandonment against worker publication:
        # a dispatch the watchdog gave up on must never merge its stats
        # or breaker events into shared session state
        self._publish_lock = threading.Lock()
        self._last_abandoned_worker = None
        # the first mesh-rung failure is logged with its chained cause
        # (later ones only count): a mesh that never works is visible
        # without flooding the log
        self._degrade_logged = False
        self._degrade_log_lock = threading.Lock()
        # traces counts engine traces triggered by this session (warmup
        # compiles land here; a steady-state delta of zero is the
        # "no retrace" certificate the serve benchmark asserts on)
        self._stats = {
            "requests": 0, "dispatches": 0, "coalesced": 0,
            "replans": 0, "traces": 0, "sharded_dispatches": 0,
            "graph_sharded_dispatches": 0,
            "quarantined": 0, "sanitized": 0, "dispatch_failures": 0,
            "chunk_splits": 0, "degraded_dispatches": 0, "saturated": 0,
            "shed": 0, "expired": 0, "cancelled": 0,
            "queue_high_watermark": 0, "watchdog_abandoned": 0,
            "updates": 0, "delta_hits": 0, "delta_fallbacks": 0,
        }

    @property
    def stats(self):
        """Counter snapshot; plan_hits/plan_misses come straight from the
        :class:`PlanCache` and the breaker counters from the
        :class:`~repro.launch.admission.CircuitBreaker` (single sources
        of truth)."""
        s = dict(self._stats)
        s["plan_hits"] = self.plans.hits
        s["plan_misses"] = self.plans.misses
        s.update(self.breaker.counters)
        return s

    def health(self) -> dict:
        """Operational snapshot: which rung of the degradation ladder
        the session is serving from, the breaker state, and the counters
        that certify each fault-tolerance guarantee (see
        ``docs/robustness.md``)."""
        state = self.breaker.state
        mesh_live = self.mesh is not None and state != admission.OPEN
        degraded = self.mesh is not None and state != admission.CLOSED
        return {
            "status": "degraded" if degraded else "ok",
            "backend": self.config.backend,
            "validation": self.config.validation,
            "breaker_state": state,
            "dispatch_mode": ("graph_sharded"
                              if self.config.backend == "graph_sharded"
                              and mesh_live
                              else "sharded" if self.mesh is not None
                              and self.mesh.size > 1 and mesh_live
                              else "single-host"),
            "mesh": (None if self.mesh is None else
                     {"devices": int(self.mesh.size),
                      "active": state == admission.CLOSED}),
            "plans_cached": len(self.plans),
            "counters": self.stats,
        }

    def restore_mesh(self) -> None:
        """Manual override: force the breaker closed after operator
        repair — the next coalesced dispatch climbs straight back up the
        ladder to sharded serving (no canary, no ``auto_restores``
        credit)."""
        self.breaker.force_close()

    # -- request preparation ------------------------------------------------

    def _prepare(self, index, pos, edges):
        """Validate, pad, and key one request.

        Raises :class:`InvalidInputError` (strict mode / uninterpretable
        input) — the caller quarantines it to this request's slot."""
        with tracing.span("session.prepare"):
            with tracing.span("session.validate"):
                pos, edges, flags = validate_request(
                    pos, edges, mode=self.config.validation, index=index)
            if flags:
                self._stats["sanitized"] += 1
            with tracing.span("session.pad"):
                pos = np.asarray(pos, np.float32)
                edges = np.asarray(edges, np.int32)
                n_v, n_e = pos.shape[0], edges.shape[0]
                vb = pow2_bucket(n_v, self.vertex_floor)
                eb = pow2_bucket(n_e, self.edge_floor)
                pos_p = np.full((vb, 2), PARK, np.float32)
                pos_p[:n_v] = pos
                edges_p = np.zeros((eb, 2), np.int32)
                edges_p[:n_e] = edges
            with tracing.span("session.topology_hash"):
                key = (topology_hash(edges, n_v), vb, eb, self.config)
        return key, dict(index=index, pos=pos, edges=edges, pos_p=pos_p,
                         edges_p=edges_p, n_v=n_v, n_e=n_e, flags=flags,
                         cost=vb + eb, deadline=None, cancel=None,
                         arrival=None)

    def _plan_for(self, key, member):
        with tracing.span("session.plan_lookup"):
            plan = self.plans.get(key)
            if plan is not None:
                return plan
            # tier_default=False: serving plans use the flat strip
            # capacity unless the config says otherwise (see the module
            # docstring)
            plan = engine.plan_readability(
                member["pos"], member["edges"],
                **self.config.plan_kwargs(tier_default=False))
            if (self.config.backend == "graph_sharded"
                    and self.mesh is not None):
                # the M_a degree bound from the natural edges, once per
                # topology rather than once per dispatch
                from repro.distributed.graph_sharded import \
                    plan_with_shard_spec
                plan = plan_with_shard_spec(plan, self.mesh.size,
                                            member["edges"])
            self.plans.put(key, plan)
            return plan

    # -- dispatch -----------------------------------------------------------

    def _dispatch(self, plan, chunk, stats=None, breaker=None):
        """One engine dispatch for a same-key chunk -> list of scores.

        A sharded dispatch that fails (mesh lost / shard_map error —
        injected or real) degrades to the fused single-host program
        *within this dispatch* and opens the breaker; integer metrics
        are bit-identical between the two rungs, so callers never see
        the difference except in the ``degraded_dispatches`` counter.
        While the breaker is open, each fused success feeds its
        half-open countdown; a half-open breaker makes the next
        mesh-eligible dispatch the canary probe.

        ``stats``/``breaker`` default to the session's own; the watchdog
        passes buffering stand-ins so an abandoned dispatch's writes can
        be dropped instead of skewing shared state
        (see :meth:`_guarded_dispatch`)."""
        if stats is None:
            stats = self._stats
        if breaker is None:
            breaker = self.breaker
        faults.check_dispatch()
        t0 = engine.trace_count()
        stats["dispatches"] += 1
        n_v = np.int32(chunk[0]["n_v"])
        n_e = np.int32(chunk[0]["n_e"])
        use_kernels = self.config.use_kernels
        if (self.config.backend == "graph_sharded" and self.mesh is not None
                and breaker.allow()):
            # top rung: each layout spatially partitioned over the mesh
            # (a chunk dispatches one driver call per member — the graph
            # axis, not the batch axis, is what's sharded here).  Any
            # failure drops to the fused single-host rungs below, which
            # are bit-identical on integer metrics.
            from repro.distributed.graph_sharded import \
                evaluate_graph_sharded
            try:
                if breaker.probing:
                    faults.check_probe()
                faults.check_sharded()
                with tracing.span("engine.dispatch"):
                    results = [evaluate_graph_sharded(
                        self.mesh, plan, c["pos_p"], c["edges_p"],
                        n_valid_vertices=n_v, n_valid_edges=n_e)
                        for c in chunk]
                breaker.record_success()
                stats["graph_sharded_dispatches"] += len(chunk)
                if len(chunk) > 1:
                    stats["coalesced"] += len(chunk)
                reports = [scores_from_result(r, int(n_v), int(n_e))
                           for r in results]
                stats["traces"] += engine.trace_count() - t0
                return faults.storm_overflow(reports)
            except Exception as err:
                breaker.record_failure()
                stats["degraded_dispatches"] += 1
                self._log_degraded("graph_sharded", err)
        if len(chunk) == 1:
            with tracing.span("engine.dispatch"):
                res = engine.evaluate_planned(
                    plan, chunk[0]["pos_p"], chunk[0]["edges_p"], n_v, n_e,
                    use_kernels=use_kernels)
            reports = [scores_from_result(res, int(n_v), int(n_e))]
        else:
            stats["coalesced"] += len(chunk)
            batch = np.stack([c["pos_p"] for c in chunk])
            res = None
            if (self.mesh is not None and self.mesh.size > 1
                    and not use_kernels and breaker.allow()):
                # scale-out path: shard the coalesced batch axis over the
                # mesh (the Pallas-kernel route stays single-device —
                # its vmapped tiles are not shard_map-composed)
                from repro.distributed.batched import \
                    evaluate_layouts_sharded
                try:
                    if breaker.probing:
                        faults.check_probe()
                    faults.check_sharded()
                    with tracing.span("engine.dispatch"):
                        res = evaluate_layouts_sharded(
                            self.mesh, plan, batch, chunk[0]["edges_p"],
                            n_valid_vertices=n_v, n_valid_edges=n_e)
                    breaker.record_success()
                    stats["sharded_dispatches"] += 1
                except Exception as err:
                    # one rung down the ladder: fused single-host (same
                    # batched body, bit-identical integer metrics); the
                    # breaker opens and re-probes on its own schedule
                    breaker.record_failure()
                    stats["degraded_dispatches"] += 1
                    self._log_degraded("sharded", err)
                    res = None
            if res is None:
                with tracing.span("engine.dispatch"):
                    res = engine.evaluate_layouts(
                        plan, batch, chunk[0]["edges_p"], n_v, n_e,
                        use_kernels=use_kernels)
        if len(chunk) > 1:
            reports = scores_from_batch(res, int(n_v), int(n_e))
        if self.mesh is not None:
            # the fused rung served while a mesh exists: feed the
            # breaker's half-open countdown (no-op unless it is open)
            breaker.record_fallback_success()
        stats["traces"] += engine.trace_count() - t0
        return faults.storm_overflow(reports)

    def _log_degraded(self, rung: str, err: BaseException) -> None:
        with self._degrade_log_lock:
            if self._degrade_logged:
                return
            self._degrade_logged = True
        _log.warning("%s dispatch over %d devices failed; serving from the "
                     "fused single-host rung (logged once per session)",
                     rung, self.mesh.size, exc_info=err)

    # -- the hung-dispatch watchdog ------------------------------------------

    def _chunk_timeout(self, chunk):
        """Wall-clock budget for one dispatch of ``chunk``: the tighter
        of ``dispatch_timeout`` and the earliest member deadline's
        remaining time; ``None`` (no guard) when neither is in force."""
        limit = self.dispatch_timeout
        now = None
        for m in chunk:
            d = m["deadline"]
            if d is not None:
                if now is None:
                    now = admission.clock()
                remaining = d - now
                limit = remaining if limit is None else min(limit, remaining)
        return limit

    def _guarded_dispatch(self, plan, chunk):
        """Dispatch under the watchdog.  With no budget in force this is
        a direct call (zero threads, zero clock reads — the steady-state
        fast path).  With one, the dispatch runs on a daemon worker and
        a dispatch that outlives its budget is ABANDONED: the worker is
        discarded (any injected hang is released so it exits instead of
        computing into the void) and :class:`DeadlineExceededError`
        raises into the normal split-and-retry path, so only this
        chunk's slots pay while the queue keeps draining.

        An abandoned *real* dispatch may still complete on its worker
        thread later — the session has by then failed the chunk's slots
        and moved on, so the late completion must be a no-op on shared
        state.  The worker therefore writes into a private stats buffer
        and a :class:`_BreakerBuffer` and PUBLISHES them only if the
        watchdog has not abandoned it (checked under ``_publish_lock``,
        which the watchdog holds while marking the abandonment): a late
        result can no longer skew ``stats()``/``health()``, flip the
        breaker, or double-resolve slots.
        """
        timeout = self._chunk_timeout(chunk)
        if timeout is None:
            with tracing.span("session.dispatch"):
                return self._dispatch(plan, chunk)
        start = admission.clock()
        if timeout <= 0:
            raise DeadlineExceededError(
                "dispatch budget already exhausted before launch",
                elapsed=0.0)
        box = {}
        done = threading.Event()
        abandoned = threading.Event()
        # the worker's spans belong to the caller's call
        caller = tracing.current()

        def work():
            stats = Counter()
            breaker = _BreakerBuffer(self.breaker)
            try:
                with tracing.carry(caller), \
                        tracing.span("session.dispatch"):
                    box["reports"] = self._dispatch(
                        plan, chunk, stats=stats, breaker=breaker)
            except BaseException as err:
                box["err"] = err
            finally:
                # publish-or-drop: the abandonment check and the merge
                # are atomic wrt the watchdog's abandonment mark
                with self._publish_lock:
                    if not abandoned.is_set():
                        for k, v in stats.items():
                            self._stats[k] += v
                        for event in breaker.events:
                            getattr(self.breaker, event)()
                done.set()

        worker = threading.Thread(target=work, daemon=True,
                                  name="eval-session-dispatch")
        worker.start()
        if not done.wait(timeout):
            with self._publish_lock:
                abandoned.set()
            self._stats["watchdog_abandoned"] += 1
            # test hook: the regression tests join the abandoned worker
            # to prove its late completion publishes nothing
            self._last_abandoned_worker = worker
            faults.release_hangs()
            raise DeadlineExceededError(
                f"dispatch exceeded its {timeout:.3f}s wall-clock budget "
                "and was abandoned by the watchdog",
                elapsed=admission.clock() - start)
        if "err" in box:
            raise box["err"]
        return box["reports"]

    # -- queue reaping (deadlines + cancellation) ----------------------------

    def _reap(self, members, out):
        """Drop queued members whose deadline passed or whose cancel
        token fired — each fails ONLY its own slot (``expired`` /
        ``cancelled`` counters) — and return the still-live rest.
        Deadline-free members cost no clock read."""
        live = []
        now = None
        for m in members:
            tok = m["cancel"]
            if tok is not None and tok.cancelled:
                self._stats["cancelled"] += 1
                out[m["index"]] = error_scores(
                    CancelledError("request cancelled before dispatch",
                                   request_index=m["index"]),
                    m["n_v"], m["n_e"])
                continue
            d = m["deadline"]
            if d is not None:
                if now is None:
                    now = admission.clock()
                if now >= d:
                    self._stats["expired"] += 1
                    elapsed = (None if m["arrival"] is None
                               else now - m["arrival"])
                    out[m["index"]] = error_scores(
                        DeadlineExceededError(
                            "deadline passed while queued (before "
                            "dispatch)", request_index=m["index"],
                            elapsed=elapsed),
                        m["n_v"], m["n_e"])
                    continue
            live.append(m)
        return live

    def _settle(self, member, report):
        """Attach the member's sanitization flags to its report."""
        if member["flags"]:
            merged = dict(report.flags or {})
            merged.update(member["flags"])
            report = report._replace(flags=merged)
        return report

    def _run_chunk(self, key, plan, chunk, out):
        """Dispatch one chunk with the full fault story: the watchdog
        guard, split-and-retry on dispatch exceptions, bounded replan
        backoff on overflow, and per-slot error results instead of
        batch-wide failure."""
        try:
            reports = self._guarded_dispatch(plan, chunk)
            attempt = 0
            worst = max(range(len(reports)),
                        key=lambda i: reports[i].overflow)
            while (reports[worst].overflow > 0
                   and attempt < self.max_replan_retries):
                # the layout outgrew the cached plan's capacities: grow
                # the plan from the worst offender's concrete data with
                # multiplicative backoff (growth ** attempt, capped), and
                # keep the bigger plan for future traffic
                attempt += 1
                self._stats["replans"] += 1
                growth = min(self.replan_growth ** attempt,
                             self.growth_ceiling)
                plan = engine.replan_on_overflow(
                    plan, chunk[worst]["pos"], chunk[worst]["edges"],
                    reports[worst], growth=growth)
                self.plans.put(key, plan)
                reports = self._guarded_dispatch(plan, chunk)
                worst = max(range(len(reports)),
                            key=lambda i: reports[i].overflow)
        except Exception as err:  # infrastructure failure (XLA, OOM, an
            # injected fault, a watchdog abandonment, ...) — mesh loss
            # never lands here: the ladder in _dispatch already degraded
            # it to single-host
            return self._fail_chunk(key, plan, chunk, out, err)

        mode = self.config.validation
        for member, report in zip(chunk, reports):
            if report.overflow > 0 and mode != "off":
                # the bounded retries could not cover this layout: never
                # return silently under-counted metrics
                self._stats["saturated"] += 1
                if mode == "strict":
                    report = error_scores(
                        CapacityError(
                            "plan capacities still overflowed after "
                            f"{self.max_replan_retries} replan retries "
                            f"({int(report.overflow)} dropped items)",
                            request_index=member["index"],
                            overflow=int(report.overflow)),
                        member["n_v"], member["n_e"])
                else:  # sanitize: flag, don't hide
                    merged = dict(report.flags or {})
                    merged["saturated"] = True
                    report = report._replace(flags=merged)
            out[member["index"]] = self._settle(member, report)
        return plan

    def _fail_chunk(self, key, plan, chunk, out, err):
        """A dispatch raised: split the chunk and retry members
        individually (one poisoned interaction must not take down B-1
        innocent requests); a single member that still fails has the
        error quarantined to its own slot.  An abandoned (hung) chunk
        lands here too — its members are reaped first, so the ones whose
        deadline the hang burned fail with ``DeadlineExceededError``
        rather than being pointlessly re-dispatched."""
        self._stats["dispatch_failures"] += 1
        if len(chunk) > 1:
            self._stats["chunk_splits"] += 1
            for member in self._reap(chunk, out):
                plan = self._run_chunk(key, plan, [member], out)
            return plan
        member = chunk[0]
        if isinstance(err, DeadlineExceededError):
            # the watchdog abandoned this member's dispatch (or its
            # budget was gone before launch): its own slot expires —
            # that is a deadline outcome, not a quarantine
            err.request_index = member["index"]
            self._stats["expired"] += 1
            out[member["index"]] = error_scores(err, member["n_v"],
                                                member["n_e"])
            return plan
        if not isinstance(err, ReadabilityError):
            wrapped = BackendUnavailableError(
                f"dispatch failed: {type(err).__name__}: {err}",
                request_index=member["index"])
            wrapped.__cause__ = err
            err = wrapped
        else:
            err.request_index = member["index"]
        self._stats["quarantined"] += 1
        out[member["index"]] = error_scores(err, member["n_v"],
                                            member["n_e"])
        return plan

    # -- public API ---------------------------------------------------------

    def evaluate(self, pos, edges, *, deadline=None, cancel=None):
        """One request -> one :class:`ReadabilityScores`.

        Single-request callers want exceptions, not error slots: a
        quarantined/shed/expired result re-raises its typed error here.
        ``deadline`` is a seconds-from-now budget; ``cancel`` a
        :class:`~repro.launch.admission.CancelToken`."""
        return self.evaluate_batch(
            [(pos, edges)], deadline=deadline,
            cancel=None if cancel is None else [cancel],
        )[0].raise_for_error()

    def evaluate_batch(self, requests, *, deadline=None, cancel=None):
        """Evaluate ``[(pos, edges), ...]``; same-topology same-bucket
        requests coalesce into single batched dispatches.  Returns scores
        in request order.

        ``deadline`` — seconds-from-arrival budget: a scalar (applies to
        every request) or a per-request sequence (``None`` entries mean
        no deadline); defaults to the session's ``default_deadline``
        knob.  ``cancel`` — a per-request sequence of
        :class:`~repro.launch.admission.CancelToken` (or ``None``
        entries).

        Malformed requests (under ``validation="strict"``/
        ``"sanitize"``) are QUARANTINED: their slot carries the typed
        error (``scores.ok`` is False) while every other slot evaluates
        normally.  Under ``validation="off"`` validation errors cannot
        arise, and any crash a malformed request causes propagates (the
        pre-fault-layer behavior).  Overload shedding, deadline expiry,
        and cancellation likewise fail ONLY their own slots —
        ``OverloadedError`` / ``DeadlineExceededError`` /
        ``CancelledError``, all in every validation mode (they are
        serving-policy outcomes, not input judgments)."""
        with tracing.span("session.evaluate_batch"):
            return self._evaluate_batch(requests, deadline, cancel)

    def _evaluate_batch(self, requests, deadline, cancel):
        n = len(requests)
        now = (admission.clock()
               if deadline is not None or self.default_deadline is not None
               else None)
        deadlines = admission.resolve_deadlines(
            n, deadline, self.default_deadline, 0.0 if now is None else now)
        if cancel is None:
            tokens = None
        else:
            tokens = list(cancel)
            if len(tokens) != n:
                raise ValueError(f"got {len(tokens)} cancel tokens for "
                                 f"{n} requests")
        out = [None] * n
        prepared = []
        quarantine_modes = ("strict", "sanitize")
        for i, (pos, edges) in enumerate(requests):
            pos = faults.corrupt_request(pos)
            try:
                key, member = self._prepare(i, pos, edges)
            except InvalidInputError as err:
                if self.config.validation not in quarantine_modes:
                    raise
                self._stats["quarantined"] += 1
                out[i] = error_scores(err)
                continue
            member["key"] = key
            member["deadline"] = deadlines[i]
            member["cancel"] = None if tokens is None else tokens[i]
            member["arrival"] = now
            prepared.append(member)
        self._stats["requests"] += n

        # the bounded queue: shed the overload BEFORE planning/dispatch
        # spends anything on it (deterministic: oldest-deadline-first,
        # ties latest-arrival-first)
        admitted, shed = admission.admit(
            prepared, max_queue=self.max_queue, max_cost=self.max_queue_cost)
        for m in shed:
            self._stats["shed"] += 1
            out[m["index"]] = error_scores(
                OverloadedError(
                    f"request shed by admission control ({len(prepared)} "
                    f"pending > queue bound)", request_index=m["index"],
                    queue_depth=len(prepared), bound=self.max_queue),
                m["n_v"], m["n_e"])
        if len(admitted) > self._stats["queue_high_watermark"]:
            self._stats["queue_high_watermark"] = len(admitted)

        groups: OrderedDict = OrderedDict()
        for member in admitted:
            groups.setdefault(member["key"], []).append(member)
        for key, members in groups.items():
            try:
                plan = self._plan_for(key, members[0])
            except InvalidInputError:
                raise
            except Exception as err:
                # host-side planning choked on request data that passed
                # (or skipped) validation — fail the group's slots, not
                # the whole call
                if self.config.validation not in quarantine_modes:
                    raise
                for member in members:
                    self._stats["quarantined"] += 1
                    out[member["index"]] = error_scores(
                        InvalidInputError(
                            f"planning failed: {type(err).__name__}: {err}",
                            request_index=member["index"],
                            reason="planning_failed"),
                        member["n_v"], member["n_e"])
                continue
            # chunk the live queue in descending-pow2 widths (same batch
            # dims as pow2_chunks, so steady state stays zero-retrace),
            # reaping expired/cancelled members between dispatches — a
            # slow neighbour must not drag a whole group past its
            # deadline unreported
            remaining = self._reap(members, out)
            while remaining:
                width = min(len(remaining), self.max_coalesce)
                width = 1 << (width.bit_length() - 1)
                chunk, remaining = remaining[:width], remaining[width:]
                plan = self._run_chunk(key, plan, chunk, out)
                if remaining:
                    remaining = self._reap(remaining, out)
        return out

    # -- dynamic layouts (incremental re-evaluation) --------------------------

    def register_layout(self, layout_id, pos, edges):
        """Register a dynamic layout for :meth:`update` and return its
        full from-scratch scores.

        The layout is evaluated through the normal serving path (plan
        cache, validation, counters), then — on the ``"fused"`` backend
        with a flat (untiered) plan — a device-resident partial state is
        primed so subsequent small moves take the incremental path (see
        :mod:`repro.core.incremental`).  Other backends register fine
        but serve every update as a full re-evaluation."""
        pos_v, edges_v, _ = validate_request(
            pos, edges, mode=self.config.validation, index=0)
        scores = self.evaluate(pos_v, edges_v)
        pos_v = np.asarray(pos_v, np.float32)
        edges_v = np.asarray(edges_v, np.int32)
        n_v, n_e = pos_v.shape[0], edges_v.shape[0]
        vb = pow2_bucket(n_v, self.vertex_floor)
        eb = pow2_bucket(n_e, self.edge_floor)
        pos_p = np.full((vb, 2), PARK, np.float32)
        pos_p[:n_v] = pos_v
        edges_p = np.zeros((eb, 2), np.int32)
        edges_p[:n_e] = edges_v
        lay = dict(key=(topology_hash(edges_v, n_v), vb, eb, self.config),
                   pos=pos_v.copy(), edges=edges_v, pos_p=pos_p,
                   edges_p=edges_p, n_v=n_v, n_e=n_e, vb=vb, eb=eb,
                   lock=threading.Lock(), plan_r=None, state=None,
                   vert_cell=None, strips=None)
        self._prime_layout(lay)
        with self._layouts_lock:
            self._layouts[layout_id] = lay
        return scores

    def _prime_layout(self, lay) -> None:
        """Build (or rebuild) the layout's device-resident partials.
        Leaves ``state=None`` — meaning updates fall back to full
        re-evaluation — when the backend is not the plain fused engine,
        the plan is tiered, or the prime itself overflowed."""
        lay["state"] = None
        if self.config.backend != "fused":
            return
        plan = self._plan_for(lay["key"], lay)
        if any(plan.strip_tiers):
            # tiered strip layouts permute bucket offsets per occupancy;
            # the resident tables assume the flat layout (sessions plan
            # flat by default — this guards an explicit override)
            return
        inc_nbr, inc_deg, deg_cap = incremental.incidence_table(
            lay["edges"], lay["n_v"], lay["vb"])
        plan_r = dataclasses.replace(plan, resident=("delta", deg_cap))
        state, aux = incremental.prime_state(
            plan_r, lay["pos_p"], lay["edges_p"], lay["n_v"], lay["n_e"],
            inc_nbr, inc_deg)
        if aux["overflow"] > 0:
            return
        lay["plan_r"] = plan_r
        lay["state"] = state
        # host mirrors the delta planner reads/writes (device_get output
        # can be read-only; the mirrors are mutated on commit)
        lay["vert_cell"] = np.array(aux["vert_cell"])
        lay["strips"] = [[np.array(s[0]), np.array(s[1]), s[2], s[3], s[4]]
                         for s in aux["strips"]]

    def update(self, layout_id, moved_idx, new_pos):
        """Move a few vertices of a registered layout and re-score it.

        Takes the incremental path when the resident state is live and
        the move stays small (dirty fractions under
        ``update_dirty_threshold``, strip domain unchanged, no bucket
        overflow) — integer metrics are bit-identical to a from-scratch
        evaluation either way, and incremental results carry
        ``flags={"incremental": True}``.  Every other case counts a
        ``delta_fallbacks`` and re-evaluates in full through the normal
        serving path (then re-primes).  Raises ``KeyError`` for an
        unknown ``layout_id`` and
        :class:`~repro.core.validate.InvalidInputError` for bad indices
        or non-finite coordinates (unless ``validation="off"``)."""
        with tracing.span("session.update"):
            return self._update(layout_id, moved_idx, new_pos)

    def _update(self, layout_id, moved_idx, new_pos):
        with self._layouts_lock:
            lay = self._layouts.get(layout_id)
        if lay is None:
            raise KeyError(f"unknown layout_id {layout_id!r}; "
                           "register_layout() it first")
        moved = np.asarray(moved_idx, np.int64).ravel()
        new = np.asarray(new_pos, np.float32).reshape(-1, 2)
        if self.config.validation != "off":
            if len(moved) == 0 or len(moved) != len(new):
                raise InvalidInputError(
                    f"moved_idx ({len(moved)}) and new_pos ({len(new)}) "
                    "must be equal-length and non-empty",
                    reason="bad_update")
            if (moved < 0).any() or (moved >= lay["n_v"]).any():
                raise InvalidInputError(
                    "moved_idx out of range for a layout with "
                    f"{lay['n_v']} vertices", reason="bad_update")
            if not np.isfinite(new).all():
                raise InvalidInputError(
                    "new_pos contains non-finite coordinates",
                    reason="bad_update")
        with lay["lock"]:
            self._stats["updates"] += 1
            # duplicate indices: last write wins, like a sequential drag
            uniq, ridx = np.unique(moved[::-1], return_index=True)
            new_u = new[len(moved) - 1 - ridx]
            scores = self._try_delta(lay, uniq, new_u)
            if scores is not None:
                self._stats["delta_hits"] += 1
                flags = dict(scores.flags or {})
                flags["incremental"] = True
                return scores._replace(flags=flags)
            # fallback: full re-evaluation through the serving path,
            # then re-prime the resident state from the new positions
            self._stats["delta_fallbacks"] += 1
            with tracing.span("session.update_fallback"):
                lay["pos"][uniq] = new_u
                lay["pos_p"][uniq] = new_u
                scores = self.evaluate(lay["pos"], lay["edges"])
                self._prime_layout(lay)
            return scores

    def _try_delta(self, lay, moved, new_xy):
        """Attempt the incremental path; return host scores, or None to
        fall back.  ``moved`` is sorted-unique with ``new_xy`` aligned."""
        state, plan_r = lay["state"], lay["plan_r"]
        if state is None:
            return None
        thr = self.update_dirty_threshold
        n_v, n_e = lay["n_v"], lay["n_e"]
        vb, eb = lay["vb"], lay["eb"]
        if len(moved) > thr * n_v:
            return None
        with tracing.span("incremental.affected_edges"):
            moved_p = incremental.pad_ids(moved, vb)
            new_xy_p = np.zeros((len(moved_p), 2), np.float32)
            new_xy_p[:len(moved)] = new_xy
            aff = incremental.affected_edges(lay["edges"], moved, n_v)
            aff_p = incremental.pad_ids(aff, eb, floor=16)
        with tracing.span("incremental.probe"):
            probe = incremental.delta_probe(
                plan_r, state, lay["edges_p"], n_e, moved_p, new_xy_p,
                aff_p)

        dirty_strips, k = [], len(moved)
        with tracing.span("incremental.plan_strips"):
            for axis_i, (lo2, hi2, sfn, sln, nsn) in enumerate(
                    probe["axes"]):
                sfo, slo, total, lo, hi = lay["strips"][axis_i]
                if lo2 != lo or hi2 != hi:
                    # an extremal vertex moved: every strip boundary
                    # shifts
                    return None
                ds, old_segs, new_segs = [], 0, 0
                for j, e in enumerate(aff_p):
                    if e >= eb:
                        continue
                    if slo[e] >= sfo[e]:
                        ds.extend(range(int(sfo[e]), int(slo[e]) + 1))
                        old_segs += int(slo[e]) - int(sfo[e]) + 1
                    if sln[j] >= sfn[j]:
                        ds.extend(range(int(sfn[j]), int(sln[j]) + 1))
                        new_segs += int(sln[j]) - int(sfn[j]) + 1
                max_segments = plan_r.strip_plans[axis_i][0]
                if total - old_segs + new_segs > max_segments:
                    return None          # the delta would outgrow the plan
                ds = np.unique(np.asarray(ds, np.int64))
                if len(ds) > thr * plan_r.n_strips:
                    return None
                dirty_strips.append(
                    incremental.pad_ids(ds if len(ds) else [plan_r.n_strips],
                                        plan_r.n_strips))

        with tracing.span("incremental.plan_cells"):
            dc_p = own_p = np.zeros(0, np.int32)
            if lay["vert_cell"] is not None and \
                    "node_occlusion" in plan_r.metrics:
                n_cells = plan_r.grid_nx * plan_r.grid_ny
                dirty = np.unique(np.concatenate(
                    [lay["vert_cell"][moved], probe["new_cid"][:k]]))
                if len(dirty) > thr * n_cells:
                    return None
                dc_p = incremental.pad_ids(dirty, n_cells)
                own_p = incremental.pad_ids(
                    incremental.owner_cells(dirty, plan_r.grid_nx,
                                            plan_r.grid_ny),
                    n_cells, floor=16)
            # the M_a rows to re-derive: movers and their neighbours
            dirty_ma = np.unique(np.concatenate(
                [moved, lay["edges"][aff].reshape(-1).astype(np.int64)]))
            dv_p = incremental.pad_ids(dirty_ma, vb, floor=16)

        with tracing.span("incremental.delta"):
            res, new_state = incremental.evaluate_delta(
                plan_r, state, lay["edges_p"], n_e, moved_p, new_xy_p,
                aff_p, dc_p, own_p, tuple(dirty_strips), dv_p)
            scores = scores_from_result(res, n_v, n_e)
        if scores.overflow > 0:
            # bucket overflow or a dirty-set miss during the rebuild:
            # membership equality is not guaranteed, so never commit
            return None
        # commit: device state + the host mirrors the next probe reads
        with tracing.span("incremental.commit"):
            lay["state"] = new_state
            lay["pos"][moved] = new_xy
            lay["pos_p"][moved] = new_xy
            if lay["vert_cell"] is not None and \
                    "node_occlusion" in plan_r.metrics:
                lay["vert_cell"][moved] = probe["new_cid"][:k]
            for axis_i, (lo2, hi2, sfn, sln, nsn) in enumerate(
                    probe["axes"]):
                rec = lay["strips"][axis_i]
                sfo, slo, total = rec[0], rec[1], rec[2]
                live = aff_p < eb
                old = np.where(slo[aff_p[live]] >= sfo[aff_p[live]],
                               slo[aff_p[live]] - sfo[aff_p[live]] + 1, 0)
                newn = np.where(sln[live] >= sfn[live],
                                sln[live] - sfn[live] + 1, 0)
                sfo[aff_p[live]] = sfn[live]
                slo[aff_p[live]] = sln[live]
                rec[2] = total - int(old.sum()) + int(newn.sum())
        return scores
