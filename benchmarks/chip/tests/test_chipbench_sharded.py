"""The graph-sharded cell ``delaunay_n20.sharded`` end to end at its
rehearsal size on the CPU, its generator's size, and its metric readers
on records built by hand."""

import json
import math

import numpy as np
import pytest

import _load
import find

CELL = "delaunay_n20.sharded"
NEW_METRICS = ("device_idle_share.sharded", "device_ms_per_layout.sharded",
               "collective_ms_per_layout.sharded",
               "front_host_ms_per_call.sharded",
               "shard_inputs_ms_per_layout.sharded")


def result(capsys, *argv):
    rc = _load.harness().main(["--workload", CELL, "--rehearsal", *argv])
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    return json.loads(out.out.strip().splitlines()[-1])


def test_the_cell_is_on_four_chips_on_the_graph_sharded_backend():
    run = _load.harness()
    spec = run.load_spec()
    cell, conf = run.find_cell(spec, CELL)
    cfg = run.load_config(conf)
    assert cell["chips"] == 4 and cell["traffic"] == "select_k1"
    assert cfg["eval"]["backend"] == "graph_sharded"
    assert cfg["eval"]["shards"] == cell["chips"]
    assert run.load_traffic(cell["traffic"])["batch"] == 1
    names = {m["name"] for m in run.metrics_for(spec["per_layer"], CELL)}
    assert names == set(NEW_METRICS)


def test_a_traced_run_reads_every_new_metric(capsys):
    res = result(capsys, "--seed", str(2**31 + 17), "--seconds", "1",
                 "--trace", "1")
    assert res["correct"] is True, res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == set(NEW_METRICS)
    assert all(math.isfinite(m["value"]) for m in res["metrics"].values())


def test_an_untraced_run_reads_the_end_to_end_metrics(capsys):
    res = result(capsys, "--seed", "20", "--seconds", "1", "--trace", "0")
    assert res["correct"] is True, res["check"]
    assert set(res["metrics"]) == {"layouts_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())


def test_the_bfloat16_control_is_not_correct(capsys):
    res = result(capsys, "--seed", "424242", "--seconds", "1", "--trace",
                 "0", "--control")
    assert res["correct"] is False
    assert [k for k, v in res["check"].items() if v["value"] > v["limit"]]


def test_the_rehearsal_graph_is_a_full_triangulation():
    from scipy.spatial import ConvexHull

    import graphs

    run = _load.harness()
    _, conf = run.find_cell(run.load_spec(), CELL)
    cfg = run.load_config(conf, rehearsal=True)
    pos, edges, spacing = graphs.build(cfg["graph"])
    n = 1 << cfg["graph"]["log2_vertices"]
    assert pos.shape == (n, 2) and spacing == pytest.approx(100 / n ** 0.5)
    # a triangulation of n points, h of them on the hull, has 3n - 3 - h
    # edges
    hull = len(ConvexHull(pos.astype(np.float64)).vertices)
    assert edges.shape == (3 * n - 3 - hull, 2)


WINDOW = [("bench.window", 1.0, 3.0)]


def test_collective_time_sums_the_first_chips_collectives_in_the_window():
    reader = find.module("metrics", "collective_ms_per_layout.sharded")
    ops = [("all-reduce.3", 0.5, 1.5), ("collective-permute-start.1", 2.0,
                                        2.25), ("all-gather", 2.5, 2.75),
           ("fusion.2", 1.0, 3.0), ("all-reduce-done", 2.9, 3.4)]
    rec = {"driver": "select", "layouts": 5,
           "trace": {"ops": ops, "spans": WINDOW}}
    assert reader.read(rec) == pytest.approx(1e3 * (0.5 + 0.25 + 0.25
                                                    + 0.1) / 5)
    assert reader.read({"driver": "select", "layouts": 5}) is None


def test_the_span_readers_read_nothing_where_the_program_has_no_span(
        monkeypatch):
    import program_spans

    spans = [{"name": "evaluator.evaluate_batch", "start": 1.0, "end": 1.5,
              "id": 1, "parent": None},
             {"name": "scores.fetch", "start": 1.3, "end": 1.5, "id": 2,
              "parent": 1}]
    monkeypatch.setattr(program_spans, "window", lambda rec, drv: spans)
    inputs = find.module("metrics", "shard_inputs_ms_per_layout.sharded")
    front = find.module("metrics", "front_host_ms_per_call.sharded")
    assert inputs.read({}) is None
    assert front.read({}) == pytest.approx(300.0)
    spans += [{"name": "graph_sharded.inputs", "start": 1.0 + t,
               "end": 1.002 + t, "id": 3 + i, "parent": 1}
              for i, t in enumerate((0.0, 0.1))]
    assert inputs.read({}) == pytest.approx(2.0)
