"""Alignment of the program's spans to the trace's clock, on spans built
by hand."""

import sys

import _load  # noqa: F401  (puts the harness and src on sys.path)
import program_spans

OFF = 12.5          # trace clock minus the buffer's, seconds
NS = 1_000_000_000


def prog(i, name, s, e, parent=None, call=None, **attrs):
    """A buffer record at buffer-clock seconds ``s`` .. ``e``."""
    return {"id": i, "name": name, "start_ns": int(s * NS),
            "end_ns": int(e * NS), "parent": parent,
            "call_id": i if call is None else call, "attrs": attrs}


def frames(starts, first_id=1):
    """One ``session.update`` root per start (10 ms, buffer clock) with a
    4 ms ``incremental.probe`` child, and the harness span around each
    (trace clock, 0.2 ms wider on each side)."""
    spans, bench = [], []
    for k, t in enumerate(starts):
        i = first_id + 2 * k
        spans.append(prog(i + 1, "incremental.probe", t + 0.001, t + 0.005,
                          parent=i, call=i))
        spans.append(prog(i, "session.update", t, t + 0.010))
        bench.append(("bench.update", t + OFF - 0.0002, t + OFF + 0.0102))
    return spans, bench


def test_a_known_offset_is_recovered():
    spans, bench = frames([100.0, 100.02, 100.04])
    bench.append(("bench.window", 100.0 + OFF - 0.001, 100.06 + OFF))
    out = program_spans.align(spans, bench, "bench.update", "session.update")
    assert len(out) == 6
    for sp, want in zip(out, spans):
        assert abs(sp["start"] - (want["start_ns"] / NS + OFF)) < 1e-6
    roots = [sp for sp in out if sp["name"] == "session.update"]
    assert all(abs(sp["self"] - 0.006) < 1e-6 for sp in roots)
    probes = [sp for sp in out if sp["name"] == "incremental.probe"]
    assert all(abs(sp["self"] - 0.004) < 1e-6 for sp in probes)


def test_an_earlier_run_is_left_out_of_the_window():
    early, _ = frames([50.0, 50.02], first_id=1)
    late, bench = frames([100.0, 100.02], first_id=11)
    gc_early = prog(21, "python.gc", 50.03, 50.031)
    gc_late = prog(22, "python.gc", 100.015, 100.016)
    bench.append(("bench.window", 100.0 + OFF - 0.001, 100.04 + OFF))
    out = program_spans.align(early + [gc_early] + late + [gc_late], bench,
                              "bench.update", "session.update")
    assert sorted(sp["id"] for sp in out) == [11, 12, 13, 14, 22]


def test_an_empty_bracket_gives_none():
    spans, bench = frames([100.0, 100.02])
    # the second root outlasts the harness span that should enclose it
    spans[3]["end_ns"] += int(0.001 * NS)
    bench.append(("bench.window", 100.0 + OFF - 0.001, 100.04 + OFF))
    assert program_spans.align(spans, bench, "bench.update",
                               "session.update") is None


def test_too_few_roots_give_none():
    spans, bench = frames([100.0, 100.02])
    bench.append(("bench.window", 100.0 + OFF - 0.001, 100.04 + OFF))
    assert program_spans.align(spans[:2], bench, "bench.update",
                               "session.update") is None


def test_a_buffer_that_dropped_spans_gives_none(monkeypatch):
    from repro import tracing

    spans, bench = frames([100.0])
    bench.append(("bench.window", 100.0 + OFF - 0.001, 100.02 + OFF))
    rec = {"driver": "drag", "trace": {"spans": bench, "ops": []}}
    monkeypatch.setattr(tracing, "snapshot",
                        lambda: {"spans": spans, "dropped": 0})
    assert len(program_spans.window(rec, "drag")) == 2
    monkeypatch.setattr(tracing, "snapshot",
                        lambda: {"spans": spans, "dropped": 1})
    assert program_spans.buffer() is None
    assert program_spans.window(rec, "drag") is None


def test_a_program_without_the_recorder_gives_none(monkeypatch):
    import repro

    monkeypatch.delattr(repro, "tracing", raising=False)
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    assert program_spans.buffer() is None
    rec = {"driver": "select", "trace": {"spans": [], "ops": []}}
    assert program_spans.window(rec, "select") is None
