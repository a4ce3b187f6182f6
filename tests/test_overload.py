"""Overload-safe serving: admission control, deadlines, cancellation,
the hung-dispatch watchdog, and the self-healing breaker.

The contract under test (``docs/robustness.md``, "Overload & deadlines"
/ "Breaker"):

* the bounded queue never admits more than ``max_queue`` requests (or
  ``max_queue_cost`` padded work units); the excess fails ONLY its own
  slots with the typed ``OverloadedError`` — deterministically (the
  same arrival sequence sheds the same request set, proven by property);
* a request whose deadline passes while queued (or whose dispatch the
  watchdog abandons) fails its own slot with ``DeadlineExceededError``
  while every neighbour keeps draining;
* a cancelled ``CancelToken`` fails its slot with ``CancelledError``
  before any engine work;
* the ``CircuitBreaker`` walks closed -> open -> half_open -> closed
  with ``probes`` / ``auto_restores`` counter certificates (the
  end-to-end mesh cycle lives in ``tests/test_faults.py``);
* with no overload knob set, behavior is bit-identical to the
  pre-overload session (the steady-state fast path is untouched).
"""

import time

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.keys import EvalConfig
from repro.core.validate import (CancelledError, DeadlineExceededError,
                                 OverloadedError)
from repro.launch import admission
from repro.launch.admission import (CLOSED, HALF_OPEN, OPEN, CancelToken,
                                    CircuitBreaker, admit,
                                    resolve_deadlines, shed_order)
from repro.launch.faults import FaultPlan
from repro.launch.session import EvalSession

RADIUS = 2.0
N_STRIPS = 48


def graph(n_v=60, n_e=120, seed=0):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0, 60, (n_v, 2)).astype(np.float32)
    n_e = min(n_e, n_v * (n_v - 1) // 2)
    edges = set()
    while len(edges) < n_e:
        v, u = rng.integers(0, n_v, 2)
        if v != u:
            edges.add((min(v, u), max(v, u)))
    return pos, np.array(sorted(edges), np.int32)


def requests(B=4, seed=0):
    """B same-topology layouts (same V/E buckets -> they coalesce)."""
    pos, edges = graph(seed=seed)
    rng = np.random.default_rng(seed + 100)
    return [(pos + rng.normal(0, 1.5, pos.shape).astype(np.float32), edges)
            for _ in range(B)]


def session(**kw):
    kw.setdefault("vertex_floor", 64)
    kw.setdefault("edge_floor", 64)
    return EvalSession(EvalConfig(radius=RADIUS, n_strips=N_STRIPS), **kw)


INT_FIELDS = ("node_occlusion", "edge_crossing", "crossing_count_for_angle")


def assert_same_scores(a, b):
    for f in INT_FIELDS:
        assert getattr(a, f) == getattr(b, f), f


# ---------------------------------------------------------------------------
# the pure admission policy (no engine)
# ---------------------------------------------------------------------------

def _members(deadlines, costs=None):
    return [dict(index=i, deadline=d,
                 cost=1 if costs is None else costs[i])
            for i, d in enumerate(deadlines)]


def test_admit_unbounded_is_identity():
    members = _members([None, 5.0, 1.0])
    admitted, shed = admit(members)
    assert admitted is members or admitted == members
    assert shed == []


def test_shed_order_is_oldest_deadline_first_then_drop_tail():
    # earliest deadlines shed first; deadline-free sheds last; within a
    # tie the latest arrival goes first (FIFO drop-tail)
    members = _members([5.0, None, 1.0, 5.0, 2.0])
    order = shed_order(members)
    assert order == [2, 4, 3, 0, 1]


def test_admit_count_bound_sheds_earliest_deadlines():
    members = _members([5.0, None, 1.0, 5.0, 2.0])
    admitted, shed = admit(members, max_queue=3)
    assert [m["index"] for m in shed] == [2, 4]           # arrival order
    assert [m["index"] for m in admitted] == [0, 1, 3]
    assert len(admitted) == 3


def test_admit_cost_bound_and_never_sheds_last():
    members = _members([1.0, 2.0, 3.0], costs=[10, 10, 10])
    admitted, shed = admit(members, max_cost=15)
    # sheds earliest-deadline members until <= budget, keeps the rest
    assert [m["index"] for m in shed] == [0, 1]
    assert [m["index"] for m in admitted] == [2]
    # one over-budget member is still admitted alone (backpressure, not
    # a per-request size limit)
    admitted, shed = admit(_members([None], costs=[99]), max_cost=10)
    assert len(admitted) == 1 and shed == []


def test_resolve_deadlines_forms():
    assert resolve_deadlines(3, None, None, 100.0) == [None] * 3
    assert resolve_deadlines(2, None, 5.0, 100.0) == [105.0, 105.0]
    assert resolve_deadlines(2, 1.0, 5.0, 100.0) == [101.0, 101.0]
    assert resolve_deadlines(3, [1.0, None, 2.0], 5.0, 100.0) == \
        [101.0, None, 102.0]
    with pytest.raises(ValueError):
        resolve_deadlines(2, [1.0], None, 0.0)


@settings(max_examples=200, deadline=None)
@given(st.lists(st.one_of(st.none(),
                          st.floats(min_value=0.0, max_value=100.0,
                                    allow_nan=False)),
                max_size=40),
       st.integers(min_value=1, max_value=12),
       st.one_of(st.none(), st.integers(min_value=1, max_value=200)))
def test_property_queue_bound_and_deterministic_shedding(deadlines,
                                                         max_queue,
                                                         max_cost):
    """The queue never exceeds its bound, nothing is lost or duplicated,
    and replaying the same arrival sequence sheds the identical set."""
    costs = [(i * 7) % 13 + 1 for i in range(len(deadlines))]
    members = _members(deadlines, costs)
    admitted, shed = admit(members, max_queue=max_queue, max_cost=max_cost)
    assert len(admitted) <= max_queue
    if max_cost is not None and len(admitted) > 1:
        assert sum(m["cost"] for m in admitted) <= max_cost
    # partition: every member lands in exactly one side, order preserved
    assert sorted(m["index"] for m in admitted + shed) == \
        list(range(len(members)))
    assert [m["index"] for m in admitted] == \
        sorted(m["index"] for m in admitted)
    # determinism: the same arrivals shed the same set
    again_admitted, again_shed = admit(_members(deadlines, costs),
                                       max_queue=max_queue,
                                       max_cost=max_cost)
    assert [m["index"] for m in again_shed] == [m["index"] for m in shed]


def test_admit_twice_same_shed_set_seeded():
    """Deterministic twin of the property (runs without hypothesis)."""
    rng = np.random.default_rng(42)
    for _ in range(50):
        n = int(rng.integers(0, 30))
        deadlines = [None if rng.random() < 0.3 else float(rng.uniform(0, 9))
                     for _ in range(n)]
        costs = [int(rng.integers(1, 20)) for _ in range(n)]
        mq = int(rng.integers(1, 10))
        mc = None if rng.random() < 0.5 else int(rng.integers(5, 100))
        a1, s1 = admit(_members(deadlines, costs), max_queue=mq, max_cost=mc)
        a2, s2 = admit(_members(deadlines, costs), max_queue=mq, max_cost=mc)
        assert [m["index"] for m in s1] == [m["index"] for m in s2]
        assert len(a1) <= mq


# ---------------------------------------------------------------------------
# admission wired into the session
# ---------------------------------------------------------------------------

def test_overload_sheds_excess_only():
    reqs = requests(B=8)
    clean = session().evaluate_batch(reqs)

    sess = session(max_queue=5)
    out = sess.evaluate_batch(reqs)
    shed = [i for i, r in enumerate(out) if r.shed]
    assert len(shed) == 3
    for i in shed:
        err = out[i].error
        assert isinstance(err, OverloadedError)
        assert err.request_index == i
        assert err.queue_depth == 8 and err.bound == 5
    # admitted slots are bit-identical to the uncontended run
    for i, r in enumerate(out):
        if not r.shed:
            assert_same_scores(r, clean[i])
    assert sess.stats["shed"] == 3
    assert sess.stats["queue_high_watermark"] == 5
    # deadline-free burst -> FIFO drop-tail: the last arrivals shed
    assert shed == [5, 6, 7]


def test_overload_sheds_oldest_deadline_first():
    reqs = requests(B=4)
    sess = session(max_queue=2)
    out = sess.evaluate_batch(reqs, deadline=[60.0, 1.0, 60.0, 2.0])
    assert [r.shed for r in out] == [False, True, False, True]
    assert all(r.ok for i, r in enumerate(out) if i in (0, 2))


def test_cost_budget_backpressure():
    reqs = requests(B=6)
    # each request pads to the 64/128 buckets -> cost 64 + 128 = 192
    sess = session(max_queue_cost=192 * 2)
    out = sess.evaluate_batch(reqs)
    assert sum(r.shed for r in out) == 4
    assert sess.stats["shed"] == 4


def test_unbounded_session_is_bit_identical_to_baseline():
    reqs = requests(B=6)
    base = session().evaluate_batch(reqs)
    sess = session()       # no overload knobs: the pre-overload session
    out = sess.evaluate_batch(reqs)
    for a, b in zip(out, base):
        assert_same_scores(a, b)
    s = sess.stats
    assert s["shed"] == 0 and s["expired"] == 0 and s["cancelled"] == 0
    assert s["watchdog_abandoned"] == 0


# ---------------------------------------------------------------------------
# deadlines + cancellation
# ---------------------------------------------------------------------------

def test_zero_deadline_expires_without_dispatching():
    reqs = requests()
    sess = session()
    d0 = sess.stats["dispatches"]
    out = sess.evaluate_batch(reqs, deadline=0.0)
    assert all(r.expired for r in out)
    for i, r in enumerate(out):
        assert isinstance(r.error, DeadlineExceededError)
        assert r.error.request_index == i
    assert sess.stats["dispatches"] == d0      # no engine work burned
    assert sess.stats["expired"] == len(reqs)
    # the session serves normally afterwards
    assert all(r.ok for r in sess.evaluate_batch(reqs))


def test_generous_deadline_full_parity_and_steady_state():
    reqs = requests()
    clean = session().evaluate_batch(reqs)
    sess = session(default_deadline=300.0)
    out = sess.evaluate_batch(reqs)
    for a, b in zip(out, clean):
        assert a.ok
        assert_same_scores(a, b)
    # the guard ran (deadline in force) but abandoned nothing, and the
    # steady state stays zero-replan/zero-retrace under it
    t0 = sess.stats["traces"]
    out2 = sess.evaluate_batch(reqs)
    assert all(r.ok for r in out2)
    assert sess.stats["traces"] == t0
    assert sess.stats["replans"] == 0
    assert sess.stats["watchdog_abandoned"] == 0


def test_cancel_token_fails_only_its_slot():
    reqs = requests()
    clean = session().evaluate_batch(reqs)
    sess = session()
    toks = [CancelToken() for _ in reqs]
    toks[1].cancel()
    out = sess.evaluate_batch(reqs, cancel=toks)
    assert out[1].cancelled
    assert isinstance(out[1].error, CancelledError)
    assert out[1].error.request_index == 1
    for i in (0, 2, 3):
        assert_same_scores(out[i], clean[i])
    assert sess.stats["cancelled"] == 1
    with pytest.raises(ValueError):
        sess.evaluate_batch(reqs, cancel=toks[:2])


def test_slow_dispatch_expires_queued_neighbours():
    """An injected straggler burns the queue's clock: members of LATER
    chunks whose deadline passes while it runs are reaped with
    ``DeadlineExceededError`` instead of being dispatched late."""
    reqs = requests(B=4)
    sess = session(max_coalesce=2)
    sess.evaluate_batch(reqs)                        # warm: plans + traces
    with FaultPlan(slow_dispatches=0, slow_seconds=0.3) as fp:
        out = sess.evaluate_batch(reqs,
                                  deadline=[30.0, 30.0, 0.05, 0.05])
    assert fp.injected["slow_dispatches"] == 1
    assert out[0].ok and out[1].ok
    assert out[2].expired and out[3].expired
    assert sess.stats["expired"] == 2
    assert sess.stats["quarantined"] == 0


# ---------------------------------------------------------------------------
# the hung-dispatch watchdog
# ---------------------------------------------------------------------------

def test_hung_dispatch_fails_only_its_chunk_and_queue_drains():
    reqs = requests(B=4)
    sess = session(max_coalesce=2)
    clean = session(max_coalesce=2).evaluate_batch(reqs)
    sess.evaluate_batch(reqs)                        # warm
    t0 = time.monotonic()
    with FaultPlan(hang_dispatches=0) as fp:
        out = sess.evaluate_batch(reqs, deadline=[0.5, 0.5, 30.0, 30.0])
    elapsed = time.monotonic() - t0
    assert fp.injected["hang_dispatches"] == 1
    # the hung chunk's members expired; nobody was quarantined
    assert out[0].expired and out[1].expired
    assert isinstance(out[0].error, DeadlineExceededError)
    # the rest of the queue drained normally, bit-identical
    assert out[2].ok and out[3].ok
    assert_same_scores(out[2], clean[2])
    assert_same_scores(out[3], clean[3])
    s = sess.stats
    assert s["watchdog_abandoned"] == 1
    assert s["expired"] == 2
    assert s["quarantined"] == 0
    # the watchdog cut the hang at the ~0.5s budget, not the 20s bound
    assert elapsed < 5.0
    # and the session serves normally afterwards
    assert all(r.ok for r in sess.evaluate_batch(reqs))


def test_dispatch_timeout_guards_without_deadlines():
    """``dispatch_timeout`` arms the watchdog even for deadline-free
    requests: the hung dispatch is abandoned and its slot expires."""
    pos, edges = graph()
    session().evaluate(pos, edges)     # compile outside the guard
    sess = session(dispatch_timeout=0.4)
    sess.evaluate(pos, edges)                        # warm (jit cache hit)
    with FaultPlan(hang_dispatches=0) as fp:
        out = sess.evaluate_batch([(pos, edges)])
    assert fp.injected["hang_dispatches"] == 1
    assert out[0].expired
    assert sess.stats["watchdog_abandoned"] >= 1


# ---------------------------------------------------------------------------
# the breaker state machine (unit; the mesh cycle is in test_faults.py)
# ---------------------------------------------------------------------------

def test_breaker_cycle_closed_open_half_open_closed():
    b = CircuitBreaker(probe_interval=3)
    assert b.state == CLOSED
    assert b.allow() and not b.probing

    b.record_failure()
    assert b.state == OPEN and b.opens == 1
    assert not b.allow()                 # open: the mesh rung is skipped

    for i in range(3):
        assert b.state == OPEN, i
        b.record_fallback_success()
    assert b.state == HALF_OPEN

    assert b.allow() and b.probing       # the canary
    assert b.probes == 1
    b.record_success()
    assert b.state == CLOSED
    assert b.auto_restores == 1
    assert not b.probing


def test_breaker_probe_failure_reopens_and_recounts():
    b = CircuitBreaker(probe_interval=2)
    b.record_failure()
    b.record_fallback_success()
    b.record_fallback_success()
    assert b.state == HALF_OPEN
    assert b.allow() and b.probing
    b.record_failure()                   # canary failed
    assert b.state == OPEN and b.opens == 2
    # the countdown restarts from zero
    b.record_fallback_success()
    assert b.state == OPEN
    b.record_fallback_success()
    assert b.state == HALF_OPEN
    assert b.allow()
    b.record_success()
    assert b.state == CLOSED and b.auto_restores == 1 and b.probes == 2


def test_breaker_force_close_is_manual_override():
    b = CircuitBreaker(probe_interval=8)
    b.record_failure()
    b.force_close()
    assert b.state == CLOSED
    assert b.auto_restores == 0          # no credit for the operator
    assert b.counters == {"breaker_opens": 1, "probes": 0,
                          "auto_restores": 0}


def test_session_exposes_breaker_state():
    sess = session()
    h = sess.health()
    assert h["breaker_state"] == "closed"
    assert "breaker_opens" in h["counters"]
    assert h["counters"]["probes"] == 0
    assert h["counters"]["auto_restores"] == 0
    sess.restore_mesh()                  # manual override is idempotent
    assert sess.health()["breaker_state"] == "closed"


# ---------------------------------------------------------------------------
# elastic mesh bring-up policy (the serving-side default)
# ---------------------------------------------------------------------------

def test_choose_mesh_shape_one_axis_is_pow2():
    from repro.launch.elastic import choose_mesh_shape
    assert choose_mesh_shape(1, axes=1) == (1,)
    assert choose_mesh_shape(4, axes=1) == (4,)
    assert choose_mesh_shape(6, axes=1) == (4,)
    assert choose_mesh_shape(7, axes=1) == (4,)
    assert choose_mesh_shape(8, axes=1) == (8,)
    with pytest.raises(ValueError):
        choose_mesh_shape(4, axes=3)


def test_serving_mesh_caps_and_names():
    import jax
    from repro.launch.elastic import serving_mesh
    mesh = serving_mesh("graph", shards=1)
    assert mesh.axis_names == ("graph",)
    assert mesh.size == 1
    mesh = serving_mesh()
    assert mesh.axis_names == ("eval",)
    assert mesh.size <= len(jax.devices())
    assert mesh.size & (mesh.size - 1) == 0     # power of two


def test_evaluator_mesh_uses_serving_policy():
    from repro.api import Evaluator
    ev = Evaluator(EvalConfig(backend="distributed", shards=1))
    mesh = ev._mesh()
    assert mesh.axis_names == ("eval",) and mesh.size == 1
