"""Chaos suite: deterministic fault injection against the serving
session's fault-tolerance layer.

Every guarantee in ``docs/robustness.md`` gets a test that *forces* the
fault (via :mod:`repro.launch.faults`) and asserts both the outcome and
the counter that certifies it:

* a NaN-poisoned request in a coalesced batch fails ONLY its own slot,
  and the innocent members' integer metrics are bit-identical to a run
  that never saw the poison;
* a failed dispatch splits the chunk and retries members individually;
* an overflow storm stops at ``max_replan_retries`` and surfaces
  ``CapacityError`` (strict) / a ``saturated`` flag (sanitize) instead
  of silently under-counting;
* simulated mesh loss degrades distributed -> fused single-host with
  correct scores (subprocess with 4 forced host devices, same pattern
  as ``test_sharded_batched.py``);
* the breaker self-heals: after ``probe_interval`` fused successes the
  session re-probes the mesh with a canary dispatch and auto-restores
  sharded serving (closed -> open -> half_open -> closed, certified by
  ``probes`` / ``auto_restores``); a rejected probe re-opens it;
* ``FaultPlan``'s ordinal bookkeeping is thread-safe (the watchdog
  dispatches on worker threads).

Overload/deadline/watchdog coverage lives in ``tests/test_overload.py``.
"""

import json
import os
import subprocess
import sys
import threading
import time
import warnings

import numpy as np
import pytest

from repro.core.keys import (EvalConfig, reset_deprecation_warnings,
                             warn_once)
from repro.core.validate import (BackendUnavailableError, CapacityError,
                                 DeadlineExceededError, InvalidInputError)
from repro.launch import faults
from repro.launch.faults import FaultInjected, FaultPlan
from repro.launch.session import EvalSession, PlanCache

RADIUS = 2.0
N_STRIPS = 48


def graph(n_v=60, n_e=120, seed=0):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0, 60, (n_v, 2)).astype(np.float32)
    n_e = min(n_e, n_v * (n_v - 1) // 2)   # sampling must terminate
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


def session(validation="strict", **kw):
    return EvalSession(EvalConfig(radius=RADIUS, n_strips=N_STRIPS,
                                  validation=validation), **kw)


INT_FIELDS = ("node_occlusion", "edge_crossing", "crossing_count_for_angle")
FLOAT_FIELDS = ("minimum_angle", "edge_length_variation",
                "edge_crossing_angle")


def assert_same_scores(a, b):
    for f in INT_FIELDS:
        assert getattr(a, f) == getattr(b, f), f
    for f in FLOAT_FIELDS:
        np.testing.assert_allclose(getattr(a, f), getattr(b, f),
                                   rtol=1e-6, err_msg=f)


# ---------------------------------------------------------------------------
# the harness itself
# ---------------------------------------------------------------------------

def test_fault_plan_bookkeeping():
    assert faults.active() is None
    with FaultPlan(nan_requests=0) as fp:
        assert faults.active() is fp
        with pytest.raises(RuntimeError):
            with FaultPlan():
                pass  # pragma: no cover
    assert faults.active() is None
    # hooks are no-ops when nothing is armed
    pos = np.ones((3, 2), np.float32)
    assert faults.corrupt_request(pos) is pos
    faults.check_dispatch()
    faults.check_sharded()
    faults.check_probe()
    faults.release_hangs()
    assert faults.storm_overflow(["x"]) == ["x"]


def test_fault_plan_ordinals_are_thread_safe():
    """Concurrent hooks must assign unique ordinals: N threads x K
    check_dispatch calls hit exactly the selected fail ordinals, no
    double-counts, no gaps (the watchdog runs dispatches on worker
    threads, so this is load-bearing, not theoretical)."""
    n_threads, per_thread = 8, 50
    total = n_threads * per_thread
    fail_at = set(range(0, total, 7))
    failures = []
    with FaultPlan(fail_dispatches=fail_at) as fp:
        start = threading.Barrier(n_threads)

        def worker():
            start.wait()
            for _ in range(per_thread):
                try:
                    faults.check_dispatch()
                except FaultInjected:
                    failures.append(1)

        threads = [threading.Thread(target=worker)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert fp._seen["dispatches"] == total
    assert fp.injected["fail_dispatches"] == len(fail_at)
    assert len(failures) == len(fail_at)


def test_warn_once_is_thread_safe():
    """N threads racing ``warn_once`` on the same keys issue exactly one
    warning per key: the check-and-add is atomic under the module lock
    (watchdog worker threads reach the shims too, and an unlocked
    membership test lets two threads both pass it and warn twice)."""
    reset_deprecation_warnings()
    n_threads, per_thread = 8, 25
    keys = [f"race-key-{i}" for i in range(4)]
    start = threading.Barrier(n_threads)

    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")     # dedup must come from warn_once

        def worker():
            start.wait()
            for _ in range(per_thread):
                for k in keys:
                    warn_once(k, f"deprecated: {k}")

        threads = [threading.Thread(target=worker)
                   for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

    assert len(rec) == len(keys)
    assert sorted(str(w.message) for w in rec) == \
        sorted(f"deprecated: {k}" for k in keys)
    # the reset hook re-arms every key (also under the lock)
    reset_deprecation_warnings()
    with warnings.catch_warnings(record=True) as rec2:
        warnings.simplefilter("always")
        warn_once(keys[0], "again")
    assert len(rec2) == 1
    reset_deprecation_warnings()


def test_plan_cache_is_thread_safe():
    # single-threaded contract first: miss/hit/LRU-evict accounting is
    # unchanged by the locking
    cache = PlanCache(capacity=2)
    assert cache.get("a") is None and cache.misses == 1
    cache.put("a", "plan_a")
    cache.put("b", "plan_b")
    assert cache.get("a") == "plan_a" and cache.hits == 1
    cache.put("c", "plan_c")            # "b" is LRU now -> evicted
    assert cache.get("b") is None
    assert cache.evictions == 1
    assert len(cache) == 2

    # concurrent get/put storm over a deliberately overflowing key space:
    # an unsynchronized move_to_end racing popitem corrupts the
    # OrderedDict's links (raises KeyError/RuntimeError from inside it)
    cache = PlanCache(capacity=8)
    n_threads, per_thread, key_space = 8, 200, 16
    start = threading.Barrier(n_threads)
    errors = []

    def worker(tid):
        rng = np.random.default_rng(tid)
        start.wait()
        try:
            for _ in range(per_thread):
                key = int(rng.integers(0, key_space))
                if cache.get(key) is None:
                    cache.put(key, key * 10)
        except Exception as err:        # pragma: no cover - failure path
            errors.append(err)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    assert errors == []
    assert cache.hits + cache.misses == n_threads * per_thread
    assert len(cache) <= 8
    # surviving entries are intact key->value pairs, never torn
    for k in range(key_space):
        v = cache.get(k)
        assert v is None or v == k * 10


# ---------------------------------------------------------------------------
# poison quarantine
# ---------------------------------------------------------------------------

def test_nan_poison_fails_only_its_own_slot():
    reqs = requests()
    clean = session().evaluate_batch(reqs)
    assert all(s.ok for s in clean)

    sess = session()
    with FaultPlan(nan_requests=2) as fp:
        scores = sess.evaluate_batch(reqs)
    assert fp.injected["nan_requests"] == 1

    # the poisoned slot carries the typed error, located
    assert not scores[2].ok
    assert isinstance(scores[2].error, InvalidInputError)
    assert scores[2].error.reason == "non_finite_positions"
    assert scores[2].error.request_index == 2
    with pytest.raises(InvalidInputError):
        scores[2].raise_for_error()

    # every innocent member is bit-identical to the never-poisoned run,
    # even though the poisoned request arrived into the same coalescing
    # window (validation runs BEFORE coalescing)
    for i in (0, 1, 3):
        assert_same_scores(scores[i], clean[i])
    assert sess.stats["quarantined"] == 1
    assert sess.stats["requests"] == 4


def test_single_request_evaluate_raises_instead():
    pos, edges = graph()
    sess = session()
    with FaultPlan(nan_requests=0):
        with pytest.raises(InvalidInputError):
            sess.evaluate(pos, edges)
    # the session survives: the next request is served normally
    assert sess.evaluate(pos, edges).ok


def test_validation_off_is_garbage_in_garbage_out():
    reqs = requests()
    sess = session(validation="off")
    # poison the request host planning will use as the group
    # representative: pre-fault-layer behavior is a crash that takes the
    # whole call down (nothing is quarantined)
    with FaultPlan(nan_requests=0):
        with pytest.raises(Exception):
            sess.evaluate_batch(reqs)
    assert sess.stats["quarantined"] == 0
    # poison a NON-representative member and the cached plan serves the
    # batch anyway: the engine silently emits garbage (NaN floats) for
    # that slot — exactly the behavior the validation layer exists to
    # replace
    sess2 = session(validation="off")
    sess2.evaluate_batch(reqs)          # warm the plan cache cleanly
    with FaultPlan(nan_requests=1):
        scores = sess2.evaluate_batch(reqs)
    assert all(s.ok for s in scores)    # no typed errors: nobody noticed
    assert sess2.stats["quarantined"] == 0


# ---------------------------------------------------------------------------
# dispatch splitting
# ---------------------------------------------------------------------------

def test_failed_dispatch_splits_chunk_and_retries_members():
    reqs = requests()
    clean = session().evaluate_batch(reqs)

    sess = session()
    with FaultPlan(fail_dispatches=0) as fp:
        scores = sess.evaluate_batch(reqs)
    assert fp.injected["fail_dispatches"] == 1
    # one coalesced dispatch failed; every member was retried alone and
    # came back correct — no request was lost to a neighbour's fault
    for got, want in zip(scores, clean):
        assert got.ok
        assert_same_scores(got, want)
    s = sess.stats
    assert s["dispatch_failures"] == 1
    assert s["chunk_splits"] == 1
    assert s["quarantined"] == 0


def test_persistent_dispatch_failure_quarantines_each_slot():
    reqs = requests()
    sess = session()
    with FaultPlan(fail_dispatches=True) as fp:
        scores = sess.evaluate_batch(reqs)
    assert fp.injected["fail_dispatches"] >= len(reqs)
    for i, s in enumerate(scores):
        assert not s.ok
        assert isinstance(s.error, BackendUnavailableError)
        assert s.error.request_index == i
        assert isinstance(s.error.__cause__, FaultInjected)
    assert sess.stats["quarantined"] == len(reqs)
    # and the session recovers the moment the fault clears
    healthy = sess.evaluate_batch(reqs)
    assert all(s.ok for s in healthy)


# ---------------------------------------------------------------------------
# bounded replan backoff
# ---------------------------------------------------------------------------

def test_overflow_storm_strict_surfaces_capacity_error():
    pos, edges = graph()
    sess = session(max_replan_retries=2)
    with FaultPlan(overflow_storms=True) as fp:
        scores = sess.evaluate_batch([(pos, edges)])
    # initial dispatch + exactly max_replan_retries replans, then stop
    assert sess.stats["replans"] == 2
    assert fp.injected["overflow_storms"] == 3
    assert sess.stats["saturated"] == 1
    err = scores[0].error
    assert isinstance(err, CapacityError)
    assert err.overflow >= 1
    assert err.request_index == 0
    # storm over: the session serves clean again (no sticky poison)
    assert sess.evaluate(pos, edges).ok


def test_overflow_storm_sanitize_flags_saturation():
    pos, edges = graph()
    sess = session(validation="sanitize", max_replan_retries=1)
    with FaultPlan(overflow_storms=True):
        scores = sess.evaluate_batch([(pos, edges)])
    s = scores[0]
    # sanitize never hides: the score is returned but marked
    assert s.ok
    assert s.saturated
    assert s.flags["saturated"] is True
    assert sess.stats["replans"] == 1
    assert sess.stats["saturated"] == 1


def test_replan_growth_is_bounded():
    sess = session(max_replan_retries=3, replan_growth=2.0,
                   growth_ceiling=3.0)
    assert min(sess.replan_growth ** 3, sess.growth_ceiling) == 3.0
    # a real (non-storm) overflow still converges within the bound:
    # starve the strip capacity via a tiny n_strips plan on a dense
    # graph, then watch one replan fix it for the rest of the stream
    pos, edges = graph(n_v=120, n_e=360, seed=5)
    r = sess.evaluate(pos, edges)
    assert r.ok and r.overflow == 0


# ---------------------------------------------------------------------------
# health snapshot
# ---------------------------------------------------------------------------

def test_health_snapshot_single_host():
    sess = session()
    h = sess.health()
    assert h["status"] == "ok"
    assert h["dispatch_mode"] == "single-host"
    assert h["mesh"] is None
    assert h["validation"] == "strict"
    pos, edges = graph()
    sess.evaluate(pos, edges)
    h = sess.health()
    assert h["counters"]["requests"] == 1
    assert h["plans_cached"] == 1


# ---------------------------------------------------------------------------
# degenerate graphs end-to-end (the old planning crashes)
# ---------------------------------------------------------------------------

def test_degenerate_graphs_end_to_end():
    sess = session()
    pos, _ = graph(n_v=8, n_e=10)
    e0 = np.zeros((0, 2), np.int32)
    cases = {
        "no_edges": (pos, e0),
        "one_vertex": (pos[:1], e0),
        "empty": (np.zeros((0, 2), np.float32), e0),
        "all_duplicate": (np.zeros((8, 2), np.float32),
                          np.array([[0, 1], [2, 3], [4, 5]], np.int32)),
    }
    for name, (p, e) in cases.items():
        s = sess.evaluate(p, e)
        assert s.ok, name
        assert s.edge_crossing == 0, name
        assert np.isfinite(s.edge_length_variation), name
        n = s.normalized()          # zero pair budgets must not divide by 0
        for f in ("node_occlusion", "edge_crossing", "minimum_angle",
                  "edge_length_variation", "edge_crossing_angle"):
            v = getattr(n, f)
            assert v is not None and 0.0 <= v <= 1.0, (name, f)
    # all-duplicate positions: every edge has length 0, so the variation
    # is exactly 0 (this used to be NaN via a float32 underflow)
    assert sess.evaluate(*cases["all_duplicate"]).edge_length_variation == 0.0


# ---------------------------------------------------------------------------
# degradation ladder: simulated mesh loss (forced 4-device subprocess)
# ---------------------------------------------------------------------------

MESH_SCRIPT = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import numpy as np
from jax.sharding import AxisType

from repro.core.keys import EvalConfig
from repro.launch.faults import FaultPlan
from repro.launch.session import EvalSession

assert len(jax.devices()) == 4

rng = np.random.default_rng(7)
pos = rng.uniform(0, 60, (60, 2)).astype(np.float32)
edges = set()
while len(edges) < 120:
    v, u = rng.integers(0, 60, 2)
    if v != u:
        edges.add((min(v, u), max(v, u)))
edges = np.array(sorted(edges), np.int32)
reqs = [(pos + rng.normal(0, 1.5, pos.shape).astype(np.float32), edges)
        for _ in range(4)]

config = EvalConfig(radius=2.0, n_strips=48)
mesh = jax.make_mesh((4,), ("eval",), axis_types=(AxisType.Auto,))

# ground truth: a single-host session (no mesh at all)
truth = EvalSession(config).evaluate_batch(reqs)

sess = EvalSession(config, mesh=mesh)
with FaultPlan(mesh_loss_dispatches=0) as fp:
    degraded = sess.evaluate_batch(reqs)
health_after_loss = sess.health()

# the mesh stays off for subsequent traffic until restored
sess.evaluate_batch(reqs)
sharded_while_down = sess.stats["sharded_dispatches"]
sess.restore_mesh()
restored = sess.evaluate_batch(reqs)
health_restored = sess.health()

out = {
    "injected": fp.injected["mesh_loss_dispatches"],
    "degraded_dispatches": sess.stats["degraded_dispatches"],
    "quarantined": sess.stats["quarantined"],
    "sharded_while_down": sharded_while_down,
    "sharded_after_restore": sess.stats["sharded_dispatches"],
    "health_after_loss": {
        "status": health_after_loss["status"],
        "dispatch_mode": health_after_loss["dispatch_mode"],
        "mesh_active": health_after_loss["mesh"]["active"],
    },
    "health_restored": {
        "status": health_restored["status"],
        "dispatch_mode": health_restored["dispatch_mode"],
    },
    "same_as_truth": [
        [s.edge_crossing, s.node_occlusion] == [t.edge_crossing,
                                                t.node_occlusion]
        and s.ok and t.ok
        for s, t in zip(degraded, truth)],
    "restored_same": [
        [s.edge_crossing, s.node_occlusion] == [t.edge_crossing,
                                                t.node_occlusion]
        for s, t in zip(restored, truth)],
}
print("RESULT " + json.dumps(out))
"""


def test_mesh_loss_degrades_to_single_host():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    result = subprocess.run([sys.executable, "-c", MESH_SCRIPT],
                            env=env, capture_output=True, text=True,
                            timeout=900)
    assert result.returncode == 0, result.stdout + "\n" + result.stderr
    line = [l for l in result.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])

    assert out["injected"] == 1
    assert out["degraded_dispatches"] == 1
    assert out["quarantined"] == 0
    # the lost mesh never served, and stays off until restore_mesh()
    assert out["sharded_while_down"] == 0
    assert out["health_after_loss"] == {"status": "degraded",
                                        "dispatch_mode": "single-host",
                                        "mesh_active": False}
    # degraded results are still correct (bit-identical integers)
    assert all(out["same_as_truth"])
    # after restore the ladder climbs back up to sharded serving
    assert out["health_restored"] == {"status": "ok",
                                      "dispatch_mode": "sharded"}
    assert out["sharded_after_restore"] >= 1
    assert all(out["restored_same"])


# ---------------------------------------------------------------------------
# self-healing breaker: probe/auto-restore cycle (forced 4-device subprocess)
# ---------------------------------------------------------------------------

BREAKER_SCRIPT = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import numpy as np
from jax.sharding import AxisType

from repro.core.keys import EvalConfig
from repro.launch.faults import FaultPlan
from repro.launch.session import EvalSession

assert len(jax.devices()) == 4

rng = np.random.default_rng(7)
pos = rng.uniform(0, 60, (60, 2)).astype(np.float32)
edges = set()
while len(edges) < 120:
    v, u = rng.integers(0, 60, 2)
    if v != u:
        edges.add((min(v, u), max(v, u)))
edges = np.array(sorted(edges), np.int32)
reqs = [(pos + rng.normal(0, 1.5, pos.shape).astype(np.float32), edges)
        for _ in range(4)]

config = EvalConfig(radius=2.0, n_strips=48)
mesh = jax.make_mesh((4,), ("eval",), axis_types=(AxisType.Auto,))

def same(batch, truth):
    return [[s.edge_crossing, s.node_occlusion] ==
            [t.edge_crossing, t.node_occlusion] and s.ok and t.ok
            for s, t in zip(batch, truth)]

truth = EvalSession(config).evaluate_batch(reqs)

# ---- leg 1: closed -> open -> half_open -> closed (auto-restore) ----
sess = EvalSession(config, mesh=mesh, probe_interval=2)
states = [sess.health()["breaker_state"]]
with FaultPlan(mesh_loss_dispatches=0) as fp:
    r1 = sess.evaluate_batch(reqs)        # mesh loss -> open, fused serves
states.append(sess.health()["breaker_state"])
r2 = sess.evaluate_batch(reqs)            # fused success #2 -> half_open
states.append(sess.health()["breaker_state"])
r3 = sess.evaluate_batch(reqs)            # canary probe -> closed
states.append(sess.health()["breaker_state"])
health = sess.health()
s = sess.stats

# ---- leg 2: the canary is rejected -> re-open -> heal on the next ----
sess2 = EvalSession(config, mesh=mesh, probe_interval=1)
with FaultPlan(mesh_loss_dispatches=0):
    sess2.evaluate_batch(reqs)            # open; fused success -> half_open
with FaultPlan(reject_probes=0) as fpr:
    r_rej = sess2.evaluate_batch(reqs)    # canary REJECTED -> open again
reopened = sess2.health()["breaker_state"]
r_heal = sess2.evaluate_batch(reqs)       # next canary passes -> closed
s2 = sess2.stats

out = {
    "states": states,
    "injected": fp.injected["mesh_loss_dispatches"],
    "probes": s["probes"],
    "auto_restores": s["auto_restores"],
    "breaker_opens": s["breaker_opens"],
    "degraded_dispatches": s["degraded_dispatches"],
    "quarantined": s["quarantined"],
    "sharded_dispatches": s["sharded_dispatches"],
    "health": {"status": health["status"],
               "dispatch_mode": health["dispatch_mode"],
               "mesh_active": health["mesh"]["active"]},
    "same1": same(r1, truth), "same2": same(r2, truth),
    "same3": same(r3, truth),
    "probe_rejected": fpr.injected["reject_probes"],
    "reopened": reopened,
    "leg2": {"probes": s2["probes"], "auto_restores": s2["auto_restores"],
             "breaker_opens": s2["breaker_opens"],
             "degraded_dispatches": s2["degraded_dispatches"],
             "quarantined": s2["quarantined"],
             "state": sess2.health()["breaker_state"]},
    "same_rej": same(r_rej, truth), "same_heal": same(r_heal, truth),
}
print("RESULT " + json.dumps(out))
"""


def test_breaker_self_heals_and_survives_rejected_probe():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    result = subprocess.run([sys.executable, "-c", BREAKER_SCRIPT],
                            env=env, capture_output=True, text=True,
                            timeout=900)
    assert result.returncode == 0, result.stdout + "\n" + result.stderr
    line = [l for l in result.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])

    # the full cycle, observed from health() after each batch
    assert out["states"] == ["closed", "open", "half_open", "closed"]
    assert out["injected"] == 1
    assert out["probes"] == 1
    assert out["auto_restores"] == 1
    assert out["breaker_opens"] == 1
    assert out["degraded_dispatches"] == 1
    assert out["quarantined"] == 0
    # only the canary's dispatch reached the mesh
    assert out["sharded_dispatches"] == 1
    assert out["health"] == {"status": "ok", "dispatch_mode": "sharded",
                             "mesh_active": True}
    # every batch — degraded, fallback, and restored — is bit-identical
    # to the single-host truth
    assert all(out["same1"]) and all(out["same2"]) and all(out["same3"])

    # leg 2: a rejected canary re-opens the circuit, traffic still
    # serves correctly, and the NEXT probe heals it
    assert out["probe_rejected"] == 1
    assert out["reopened"] == "half_open"      # interval=1 re-arms at once
    assert out["leg2"]["probes"] == 2
    assert out["leg2"]["auto_restores"] == 1
    assert out["leg2"]["breaker_opens"] == 2
    assert out["leg2"]["degraded_dispatches"] == 2
    assert out["leg2"]["quarantined"] == 0
    assert out["leg2"]["state"] == "closed"
    assert all(out["same_rej"]) and all(out["same_heal"])


# ---------------------------------------------------------------------------
# abandoned-dispatch late completions are no-ops on shared state
# ---------------------------------------------------------------------------

def test_abandoned_dispatch_late_completion_publishes_nothing():
    """An injected straggler outlives the watchdog budget, gets
    abandoned, then COMPLETES the real dispatch on its discarded worker
    thread — and that late completion must not skew a single shared
    counter or breaker event (the publish-or-drop race this certifies
    used to double-count ``dispatches``/``traces``)."""
    pos, edges = graph()
    session().evaluate(pos, edges)      # compile outside the guard
    sess = session(dispatch_timeout=0.3)
    sess.evaluate(pos, edges)                        # warm (jit cache hit)
    with FaultPlan(slow_dispatches=0, slow_seconds=1.0) as fp:
        out = sess.evaluate_batch([(pos, edges)])
    assert fp.injected["slow_dispatches"] == 1
    assert out[0].expired
    assert isinstance(out[0].error, DeadlineExceededError)
    assert sess.stats["watchdog_abandoned"] == 1

    snapshot = sess.stats
    worker = sess._last_abandoned_worker
    assert worker is not None
    worker.join(timeout=30.0)           # let the real dispatch finish late
    assert not worker.is_alive()
    # the late completion published nothing: counters and breaker state
    # are bit-identical to the snapshot taken at abandonment
    assert sess.stats == snapshot
    # and the session still serves normally
    assert sess.evaluate(pos, edges).ok


def test_abandoned_hang_releases_late_and_stays_clean():
    """The watchdog releases an injected hang at abandonment; the
    discarded worker's FaultInjected must die with the worker — it never
    reaches the split-and-retry path or the failure counters."""
    pos, edges = graph()
    session().evaluate(pos, edges)      # compile outside the guard
    sess = session(dispatch_timeout=0.4)
    sess.evaluate(pos, edges)
    t0 = time.monotonic()
    with FaultPlan(hang_dispatches=0) as fp:
        out = sess.evaluate_batch([(pos, edges)])
        assert fp.injected["hang_dispatches"] == 1
        assert out[0].expired
        worker = sess._last_abandoned_worker
        assert worker is not None
        snapshot = sess.stats
        worker.join(timeout=10.0)       # release_hangs() already fired:
        assert not worker.is_alive()    # the worker exits promptly...
    assert time.monotonic() - t0 < 10.0  # ...not after the 20s hang bound
    s = sess.stats
    # the main thread's abandonment bookkeeping is all there is: one
    # dispatch failure (the abandonment itself), one expired slot — the
    # discarded worker's FaultInjected added nothing on top of it
    assert s == snapshot
    assert s["watchdog_abandoned"] == 1
    assert s["dispatch_failures"] == 1
    assert s["expired"] == 1
    assert s["quarantined"] == 0
    assert sess.evaluate(pos, edges).ok
