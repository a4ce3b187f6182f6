"""The span recorder (``repro.tracing``) and the engine's named scopes.

The recorder is on exactly while a JAX profiler session collects.  These
tests record one call of each instrumented entry point under
``jax.profiler.trace`` and check the buffer against the profiler's own
trace: same names, same durations, one constant between the clocks.
"""

import gc
import glob
from collections import defaultdict

import jax
import numpy as np
import pytest

from repro import tracing
from repro.api import EvalConfig, Evaluator
from repro.core import engine
from repro.graphs.layouts import lattice_graph
from repro.launch.session import EvalSession

CONFIG = EvalConfig(radius=2.0, n_strips=32)
SIDE = 16
MOVED = [SIDE * (SIDE // 2) + SIDE // 2]      # an interior vertex

# root -> {span: its parent}, for one call of each entry point
CALLS = {
    "session.update": {
        "incremental.affected_edges": "session.update",
        "incremental.probe": "session.update",
        "incremental.probe_fetch": "incremental.probe",
        "incremental.plan_strips": "session.update",
        "incremental.plan_cells": "session.update",
        "incremental.delta": "session.update",
        "scores.fetch": "incremental.delta",
        "incremental.commit": "session.update",
    },
    "evaluator.evaluate_batch": {
        "evaluator.validate": "evaluator.evaluate_batch",
        "engine.dispatch": "evaluator.evaluate_batch",
        "scores.fetch": "evaluator.evaluate_batch",
    },
    "session.evaluate_batch": {
        "session.prepare": "session.evaluate_batch",
        "session.validate": "session.prepare",
        "session.pad": "session.prepare",
        "session.topology_hash": "session.prepare",
        "session.plan_lookup": "session.evaluate_batch",
        "session.dispatch": "session.evaluate_batch",
        "engine.dispatch": "session.dispatch",
        "scores.fetch": "session.dispatch",
    },
}

ENGINE_SCOPES = ("occlusion", "min_angle", "edge_length",
                 "strips.build/axis0", "strips.build/axis1",
                 "strips.sweep/axis0/tier0", "strips.sweep/axis1/tier0",
                 "crossing.select")


@pytest.fixture(scope="module")
def graph():
    return lattice_graph(SIDE * SIDE, seed=3)


@pytest.fixture(scope="module")
def calls(graph):
    """Warmed objects and one closure per entry point."""
    pos, edges = graph
    session = EvalSession(CONFIG, update_dirty_threshold=1.0)
    session.register_layout("a", pos, edges)
    ev = Evaluator(CONFIG)
    batch = np.stack([pos, pos + np.float32(0.05)])
    plan = ev.plan(batch, edges)
    step = [0]

    def update():
        step[0] += 1
        new = pos[MOVED] + np.float32(0.2 * (step[0] % 2))
        out = session.update("a", MOVED, new)
        assert out.flags["incremental"]

    def select():
        ev.evaluate_batch(batch, edges, plan=plan)

    def serve():
        session.evaluate_batch([(pos, edges), (batch[1], edges)])

    fns = {"session.update": update, "evaluator.evaluate_batch": select,
           "session.evaluate_batch": serve}
    for fn in fns.values():
        fn()
        fn()
    return fns


@pytest.fixture(scope="module")
def recording(calls, tmp_path_factory):
    """``(snapshot, xplane path)`` of one recorded call of each."""
    tmp = tmp_path_factory.mktemp("trace")
    tracing.reset()
    with jax.profiler.trace(str(tmp)):
        for fn in calls.values():
            fn()
    snap = tracing.snapshot()
    tracing.reset()
    (path,) = glob.glob(str(tmp / "**" / "*.xplane.pb"), recursive=True)
    return snap, path


def test_nothing_is_recorded_without_a_profiler(calls):
    tracing.reset()
    for fn in calls.values():
        fn()
    assert tracing.snapshot() == {"spans": [], "dropped": 0}
    assert tracing.span("a") is tracing.span("b", x=1)
    assert tracing.current() is None


@pytest.mark.parametrize("root", sorted(CALLS))
def test_a_call_records_its_spans_under_one_call_id(recording, root):
    snap, _ = recording
    assert snap["dropped"] == 0
    (top,) = [s for s in snap["spans"] if s["name"] == root]
    assert top["parent"] is None and top["call_id"] == top["id"]
    mine = [s for s in snap["spans"] if s["call_id"] == top["id"]]
    by_id = {s["id"]: s for s in mine}
    names = {s["name"] for s in mine} - {"python.gc"}
    assert names == {root} | set(CALLS[root])
    for s in mine:
        if s["name"] in CALLS[root]:
            assert by_id[s["parent"]]["name"] == CALLS[root][s["name"]]
            up = by_id[s["parent"]]
            assert up["start_ns"] <= s["start_ns"] <= s["end_ns"] \
                <= up["end_ns"]


def test_a_watchdog_dispatch_keeps_its_callers_call_id(graph, tmp_path):
    pos, edges = graph
    # a dispatch timeout puts every dispatch on the watchdog's worker
    guarded = EvalSession(CONFIG, dispatch_timeout=600.0)
    guarded.evaluate_batch([(pos, edges)])
    tracing.reset()
    with jax.profiler.trace(str(tmp_path)):
        guarded.evaluate_batch([(pos, edges)])
    spans = tracing.snapshot()["spans"]
    tracing.reset()
    (root,) = [s for s in spans if s["name"] == "session.evaluate_batch"]
    (disp,) = [s for s in spans if s["name"] == "session.dispatch"]
    assert disp["parent"] == root["id"]
    inner = [s for s in spans if s["parent"] == disp["id"]]
    assert {s["name"] for s in inner} == {"engine.dispatch", "scores.fetch"}
    assert all(s["call_id"] == root["id"] for s in inner + [disp])


def test_every_span_is_in_the_trace_on_one_clock(recording):
    from jax.profiler import ProfileData

    snap, path = recording
    ours = defaultdict(list)
    for s in snap["spans"]:
        if s["name"] != "jax.compile":
            ours[s["name"]].append(s)
    theirs = defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in ours:
                        theirs[ev.name].append(ev)
    pairs = []
    for name, spans in ours.items():
        spans.sort(key=lambda s: s["start_ns"])
        evs = sorted(theirs[name], key=lambda ev: ev.start_ns)
        assert len(evs) == len(spans), name
        pairs += [(name, ev.start_ns - s["start_ns"],
                   ev.start_ns + ev.duration_ns - s["end_ns"])
                  for s, ev in zip(spans, evs)]
    assert len(pairs) >= sum(len(v) + 1 for v in CALLS.values())
    mid = float(np.median([start for _, start, _ in pairs]))
    for name, start, end in pairs:
        # the duration, and the start against every other span's
        assert abs(end - start) < 50e3, (name, start - mid, end - mid)
        assert abs(start - mid) < 50e3, (name, start - mid, end - mid)


def test_a_new_shape_records_one_trace_and_a_cached_one_none(tmp_path):
    f = jax.jit(lambda x: jax.lax.mul(x, x))
    f(np.ones(3, np.float32))

    def traces():
        return [s for s in tracing.snapshot()["spans"]
                if s["name"] == "jax.compile"
                and s["attrs"]["event"] == tracing.COMPILE_EVENTS[0]]

    tracing.reset()
    with jax.profiler.trace(str(tmp_path)):
        f(np.ones(3, np.float32))
        cached = traces()
        f(np.ones(5, np.float32))
        fresh = traces()
    tracing.reset()
    assert cached == []
    assert len(fresh) == 1
    assert fresh[0]["end_ns"] >= fresh[0]["start_ns"]


def test_a_full_buffer_counts_what_it_drops(monkeypatch, tmp_path):
    monkeypatch.setattr(tracing, "CAPACITY", 2)
    tracing.reset()
    gc.disable()            # a collection would take a slot of its own
    try:
        with jax.profiler.trace(str(tmp_path)):
            for name in ("a", "b", "c", "d"):
                with tracing.span(name, n=1):
                    pass
    finally:
        gc.enable()
    snap = tracing.snapshot()
    assert [s["name"] for s in snap["spans"]] == ["a", "b"]
    assert snap["spans"][0]["attrs"] == {"n": 1}
    assert snap["dropped"] == 2
    tracing.reset()
    assert tracing.snapshot() == {"spans": [], "dropped": 0}


def test_the_batched_program_names_every_engine_scope(graph):
    pos, edges = graph
    batch = np.stack([pos, pos + np.float32(0.05)])
    plan = engine.plan_readability(batch, edges, radius=2.0, n_strips=32)
    text = engine.evaluate_layouts.lower(plan, batch, edges).as_text(
        debug_info=True)
    missing = [s for s in ENGINE_SCOPES if f"/{s}/" not in text]
    assert missing == []


def test_a_collection_is_recorded_as_a_span(tmp_path):
    tracing.reset()
    with jax.profiler.trace(str(tmp_path)):
        with tracing.span("outer") as outer:
            gc.collect()
    spans = tracing.snapshot()["spans"]
    tracing.reset()
    (col,) = [s for s in spans if s["name"] == "python.gc"
              and s["attrs"]["generation"] == 2]
    assert col["parent"] == outer.id and col["call_id"] == outer.id
    assert col["end_ns"] >= col["start_ns"]


def test_threads_record_every_span_under_their_own_roots(tmp_path):
    import sys
    import threading

    n_threads, n_spans = 16, 200
    tracing.reset()

    def work():
        for _ in range(n_spans):
            with tracing.span("outer"):
                with tracing.span("inner"):
                    pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with jax.profiler.trace(str(tmp_path)):
            threads = [threading.Thread(target=work)
                       for _ in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    spans = [s for s in tracing.snapshot()["spans"]
             if s["name"] in ("outer", "inner")]
    tracing.reset()
    assert len(spans) == 2 * n_threads * n_spans
    assert len({s["id"] for s in spans}) == len(spans)
    by_id = {s["id"]: s for s in spans}
    for s in spans:
        if s["name"] == "inner":
            up = by_id[s["parent"]]
            assert up["name"] == "outer" and s["call_id"] == up["id"]
        else:
            assert s["parent"] is None and s["call_id"] == s["id"]
