"""The trace reduction and the window arithmetic, on numbers built by
hand."""

import math

import pytest

import _load  # noqa: F401  (puts the harness on the path)
import find
import trace_reduce as tr
from window import due_latencies, percentile, whole_call_rate

OPS = [("sort", 0.0, 1.0), ("gather", 0.5, 2.0), ("sort", 3.0, 4.0),
       ("sweep", 6.0, 9.0)]
SPANS = [("bench.window", 0.0, 10.0), ("bench.evaluate_batch", 0.0, 4.5),
         ("bench.evaluate_batch", 5.0, 9.5)]


def test_union_merges_overlaps_and_keeps_disjoint_intervals():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (4, 5)]) == [(0, 2), (3, 5)]
    assert tr.union([]) == []


def test_busy_and_idle_over_a_window():
    assert tr.busy_seconds(OPS, 0.0, 10.0) == pytest.approx(6.0)
    assert tr.idle_gaps(OPS, 0.0, 10.0) == [(2.0, 3.0), (4.0, 6.0),
                                            (9.0, 10.0)]
    # the window cuts intervals that reach past it
    assert tr.busy_seconds(OPS, 0.5, 7.0) == pytest.approx(1.5 + 1 + 1)


def test_idle_gaps_are_attributed_to_the_innermost_open_span():
    by = tr.idle_by_span(OPS, SPANS, 0.0, 10.0)
    # 2-3 inside the first call, 4-4.5 inside it, 4.5-5 between calls,
    # 5-6 inside the second call, 9-9.5 inside it, 9.5-10 after it
    assert by["bench.evaluate_batch"] == pytest.approx(1.0 + 0.5 + 1.0 + 0.5)
    assert by["bench.window"] == pytest.approx(0.5 + 0.5)
    assert sum(by.values()) == pytest.approx(4.0)
    assert tr.idle_within(OPS, SPANS, "bench.evaluate_batch", 0.0,
                          10.0) == pytest.approx(3.0)
    gaps = tr.longest_gaps(OPS, SPANS, 0.0, 10.0, n=2)
    assert gaps[0] == ["bench.evaluate_batch", pytest.approx(2.0)]
    assert gaps[1][1] == pytest.approx(1.0)


def test_top_ops_sum_durations_by_name():
    top = tr.top_ops(OPS, 0.0, 10.0)
    assert top[0] == ["sweep", 3.0]
    assert top[1] == ["sort", 2.0]


def test_gap_labels_follow_the_same_rule_as_idle_by_span():
    # two spans that start together, the inner one listed first: both
    # readings give the gap to the one that ends first
    spans = [("bench.update", 0.0, 4.0), ("bench.window", 0.0, 10.0)]
    ops = [("op", 0.0, 1.0), ("op", 3.0, 10.0)]
    assert tr.longest_gaps(ops, spans, 0.0, 10.0) == [
        ["bench.update", pytest.approx(2.0)]]
    assert tr.idle_by_span(ops, spans, 0.0, 10.0) == {
        "bench.update": pytest.approx(2.0)}
    # a gap outside every span
    assert tr.longest_gaps([("op", 0.0, 1.0)], [], 0.0, 3.0) == [
        ["outside_spans", pytest.approx(2.0)]]


def test_summary_and_trace_readers_divide_per_layout_and_per_frame():
    summary = tr.summarize({"/device:TPU:0": OPS}, SPANS, 0.0, 10.0)
    assert summary["busy_s"] == pytest.approx(6.0)
    assert summary["window_s"] == pytest.approx(10.0)
    rec = {"driver": "select", "layouts": 48, "trace": summary}
    assert find.module("metrics", "device_ms_per_layout.select").read(rec) == \
        pytest.approx(6.0e3 / 48)
    assert find.module("metrics", "device_idle_share.select").read(rec) == \
        pytest.approx(40.0)
    # the drag reader: idle time inside bench.update spans, per frame
    spans = [("bench.window", 0.0, 10.0), ("bench.update", 1.0, 3.5),
             ("bench.update", 5.0, 7.0)]
    drag = {"driver": "drag", "frames": 2, "delta_hits": 1,
            "trace": tr.summarize({"/device:TPU:0": OPS}, spans, 0.0, 10.0)}
    assert find.module("metrics", "host_ms_per_frame.drag").read(drag) == \
        pytest.approx(1e3 * (1.0 + 1.0) / 2)
    assert find.module("metrics", "delta_hit_share.drag").read(drag) == 50.0
    # a reader with nothing to read returns nothing
    assert find.module("metrics", "device_idle_share.serve").read(rec) is None
    assert find.module("metrics", "frame_p95_ms").read(rec) is None


def test_busy_time_is_averaged_over_devices():
    s = tr.summarize({"/device:TPU:0": OPS, "/device:TPU:1": OPS[:1]},
                     SPANS, 0.0, 10.0)
    assert s["busy_s"] == pytest.approx((6.0 + 1.0) / 2)
    with pytest.raises(ValueError):
        tr.summarize({}, SPANS, 0.0, 10.0)


def test_whole_call_rate_counts_the_last_call_whole():
    calls = [(0.0, 4.0), (4.0, 8.5), (8.5, 12.0)]
    # a 10 s window: the third call ends after it and is counted whole
    assert whole_call_rate(calls, 16) == pytest.approx(48 / 12.0)
    assert whole_call_rate([], 16) is None


def test_latency_is_taken_from_the_due_time_and_failures_are_missing():
    lat = due_latencies([0.0, 0.1, 0.2], [0.05, 0.3, None])
    assert lat[:2] == [pytest.approx(0.05), pytest.approx(0.2)]
    assert math.isinf(lat[2])
    assert percentile(list(range(1, 101)), 95) == 95
    assert percentile([1.0, 2.0, math.inf], 95) == math.inf
    assert percentile([], 95) is None
    rec = {"latency_ms": [float(x) for x in range(1, 21)]}
    assert find.module("metrics", "request_p95_ms").read(rec) == 19.0


def test_peaks_are_keyed_by_device_kind_and_unknown_kinds_fail():
    import peaks

    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flop_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
