"""Generators: graph sizes, determinism under the seeds, and the
data-driven lookup of configurations, traffic mixes and metrics."""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

import _load
import find

delaunay = find.module("generators", "delaunay")
kleinberg = find.module("generators", "kleinberg")


def test_delaunay_n16_has_the_dimacs_size():
    pos, edges, spacing = delaunay.build({"log2_vertices": 16, "seed": 16})
    assert pos.shape == (65536, 2) and pos.dtype == np.float32
    # DIMACS10 delaunay_n16 has 196,575 edges; a triangulation of 2^16
    # uniform points has 3V - 3 - (hull size)
    assert 196_000 < edges.shape[0] < 197_000
    assert pos.min() >= 0.0 and pos.max() <= 100.0
    assert spacing == pytest.approx(100.0 / 256)


def test_kleinberg_s128_has_the_lattice_and_one_contact_per_node():
    params = {"side": 128, "contacts": 1, "exponent": 2.0, "jitter": 0.15,
              "seed": 128}
    pos, edges, spacing = kleinberg.build(params)
    assert pos.shape == (16384, 2)
    lattice = 2 * 128 * 127
    assert lattice + 12_000 < edges.shape[0] <= lattice + 16384
    assert np.all(edges[:, 0] < edges[:, 1])           # undirected, once
    assert len(np.unique(edges, axis=0)) == edges.shape[0]
    # long-range lengths are close to log-uniform: about as many contacts
    # reach 2-8 spacings as 8-32
    d = np.abs(np.divmod(edges[:, 0], 128)[1] - np.divmod(edges[:, 1], 128)[1]) \
        + np.abs(edges[:, 0] // 128 - edges[:, 1] // 128)
    a, b = np.sum((d >= 2) & (d < 8)), np.sum((d >= 8) & (d < 32))
    assert 0.7 < a / b < 1.5
    again = kleinberg.build(params)
    assert np.array_equal(again[1], edges) and np.array_equal(again[0], pos)


def test_ring_offsets_cover_each_ring_once():
    for d in (1, 2, 5):
        dx, dy = kleinberg._ring_offsets(np.full(4 * d, d), np.arange(4 * d))
        pts = set(zip(dx.tolist(), dy.tolist()))
        assert len(pts) == 4 * d
        assert all(abs(x) + abs(y) == d for x, y in pts)


def _ctx(seed, traffic):
    from repro.api import EvalConfig

    pos, edges, spacing = delaunay.build({"log2_vertices": 8, "seed": 1})
    return SimpleNamespace(pos=pos, edges=edges, spacing=spacing,
                           traffic=traffic, seed=seed, rate=None,
                           eval_config=EvalConfig(n_strips=16))


def test_select_and_drag_traffic_repeat_under_a_seed():
    run = _load.harness()
    select = find.module("drivers", "select").Driver
    t = run.load_traffic("select_k16", rehearsal=True)
    a, b, c = (select(_ctx(s, t)) for s in (2**31 + 5, 2**31 + 5, 9))
    assert all(np.array_equal(x, y) for x, y in zip(a.pool, b.pool))
    assert not np.array_equal(a.pool[0], c.pool[0])
    # the plan comes from the traffic's fixed seed, not from --seed
    assert a.plan == c.plan

    t = run.load_traffic("drag", rehearsal=True)
    drag = find.module("drivers", "drag").Driver
    d1, d2 = drag(_ctx(77, t)), drag(_ctx(77, t))
    assert len(d1.frames) == len(d2.frames) == 3 * 2 * 3
    for (s1, p1), (s2, p2) in zip(d1.frames, d2.frames):
        assert np.array_equal(s1, s2) and np.array_equal(p1, p2)
    # every gesture ends where it began
    assert np.array_equal(d1.positions_at(len(d1.frames) - 1), d1.pos)


def test_serve_arrivals_have_a_fixed_count_and_repeat_under_a_seed():
    run = _load.harness()
    serve = find.module("drivers", "serve").Driver
    t = run.load_traffic("serve_poisson_13", rehearsal=True)
    recs = [serve(_ctx(s, t)).window(1.0) for s in (5, 5)]
    assert recs[0]["requests"] == recs[1]["requests"] == round(t["rate_per_s"])
    assert recs[0]["failed"] == 0


def test_new_config_mix_and_metric_files_are_found_by_name(tmp_path):
    run = _load.harness()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "traffic" / "select_k64.json").write_text(json.dumps(
        {"driver": "select", "batch": 64, "rehearsal": {"batch": 2}}))
    (tmp_path / "metrics" / "calls_per_s.select.py").write_text(
        "def read(rec):\n    return rec['calls'] / rec['elapsed_s']\n")
    (tmp_path / "cfg.json").write_text(json.dumps(
        {"graph": {"generator": "delaunay", "log2_vertices": 12},
         "rehearsal": {"graph": {"log2_vertices": 6}}}))
    assert run.load_traffic("select_k64", here=str(tmp_path))["batch"] == 64
    assert run.load_traffic("select_k64", rehearsal=True,
                            here=str(tmp_path))["batch"] == 2
    reader = find.module("metrics", "calls_per_s.select", here=str(tmp_path))
    assert reader.read({"calls": 10, "elapsed_s": 4.0}) == 2.5
    cfg = run.load_config({"file": "cfg.json"}, rehearsal=True,
                          root=str(tmp_path))
    assert cfg["graph"] == {"generator": "delaunay", "log2_vertices": 6}
    entries = [{"name": "a", "workloads": ["x.select"]}, {"name": "b"}]
    assert [m["name"] for m in run.metrics_for(entries, "y.drag")] == ["b"]


def test_benchmark_files_are_complete_and_named_as_the_contract_says():
    run = _load.harness()
    spec = run.load_spec()
    here = os.path.join(_load.ROOT, spec["paths"][0])
    for cell in spec["workloads"]:
        _, conf = run.find_cell(spec, cell["name"])
        cfg = run.load_config(conf)
        assert cfg["name"] == conf["name"] and cfg["reduced"] == conf["reduced"]
        assert os.path.exists(os.path.join(here, "traffic",
                                           cell["traffic"] + ".json"))
        assert os.path.exists(os.path.join(here, "limits",
                                           cell["name"] + ".json"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] != "setup_s":
            assert hasattr(find.module("metrics", m["name"]), "read")
    for cell in spec["workloads"]:
        e2e = run.metrics_for(spec["end_to_end"], cell["name"])
        assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
        assert run.metrics_for(spec["per_layer"], cell["name"])


def test_benchmark_json_keeps_to_the_contract_shape():
    import re

    spec = _load.harness().load_spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and len(c["source"]) <= 200
    cells = {w["name"] for w in spec["workloads"]}
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                           "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                           "layer", "moves"}
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells


RING = '''"""A ring with chords: vertex i joined to i + 1 and i + 3."""
import numpy as np
from graphs import BOX, undirected


def build(params):
    n = int(params["vertices"])
    a = 2 * np.pi * np.arange(n) / n
    pos = 0.5 * BOX + 0.4 * BOX * np.stack([np.cos(a), np.sin(a)], 1)
    i = np.arange(n)
    edges = np.concatenate([np.stack([i, (i + 1) % n], 1),
                            np.stack([i, (i + 3) % n], 1)])
    return pos.astype(np.float32), undirected(edges), 0.8 * np.pi * BOX / n
'''

ONE_BY_ONE = '''"""Closed loop of single-layout Evaluator.evaluate calls."""
import time
import numpy as np
from kit import host_scores, jittered, span
from window import whole_call_rate


class Driver:
    def __init__(self, ctx):
        from repro.api import Evaluator
        self.ev, self.edges = Evaluator(ctx.eval_config), ctx.edges
        self.pool = jittered(np.random.default_rng(ctx.seed), ctx.pos,
                             int(ctx.traffic["pool"]),
                             ctx.traffic["jitter_spacings"] * ctx.spacing)
        self.ev.evaluate(self.pool[0], self.edges)

    def window(self, seconds):
        calls, self.outs = [], []
        t0 = time.perf_counter()
        while not calls or calls[-1][1] - t0 < seconds:
            s = time.perf_counter()
            with span("bench.evaluate"):
                self.outs.append(self.ev.evaluate(
                    self.pool[len(calls) % len(self.pool)], self.edges))
            calls.append((s, time.perf_counter()))
        return {"driver": "one_by_one", "attempted": len(calls),
                "failed": 0, "evaluations": len(calls),
                "layouts_per_s": whole_call_rate(calls, 1)}

    def answers(self, rng):
        return [(f"call {i}", self.pool[i % len(self.pool)],
                 host_scores(self.outs[i])) for i in range(2)]
'''


def test_a_cell_made_only_of_new_files_runs_with_no_edit(tmp_path, capsys,
                                                          monkeypatch):
    """A new generator, configuration, driver, traffic mix, metric and
    cell, each added as a file and an entry, run end to end in a copy of
    the benchmark in which no file that was there is changed."""
    import filecmp
    import importlib.util
    import shutil
    import sys

    monkeypatch.setattr(sys, "path", list(sys.path))
    root = tmp_path / "checkout"
    bench = root / "benchmarks" / "chip"
    shutil.copytree(_load.HERE, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    (root / "src").symlink_to(os.path.join(_load.ROOT, "src"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    (bench / "generators" / "ring.py").write_text(RING)
    (bench / "drivers" / "one_by_one.py").write_text(ONE_BY_ONE)
    (bench / "traffic" / "one_by_one.json").write_text(json.dumps(
        {"driver": "one_by_one", "pool": 3, "jitter_spacings": 0.3}))
    (bench / "metrics" / "evaluations.one_by_one.py").write_text(
        "def read(rec):\n    return rec.get('evaluations')\n")
    (bench / "configs" / "ring_64.json").write_text(json.dumps(
        {"name": "ring_64", "graph": {"generator": "ring", "vertices": 64},
         "eval": {"radius": 0.5, "n_strips": 16}, "ideal_angle_deg": 70.0,
         "reduced": []}))
    shutil.copy(bench / "limits" / "delaunay_n16.select.json",
                bench / "limits" / "ring_64.one_by_one.json")
    spec = _load.harness().load_spec()
    cell = "ring_64.one_by_one"
    spec["configs"].append({"name": "ring_64", "source": "a test ring",
                            "file": "benchmarks/chip/configs/ring_64.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": cell, "config": "ring_64",
                              "traffic": "one_by_one", "chips": 1,
                              "why": "test"})
    next(m for m in spec["end_to_end"]
         if m["name"] == "layouts_per_s")["workloads"].append(cell)
    spec["per_layer"].append(
        {"name": "evaluations.one_by_one", "unit": "calls",
         "better": "higher", "source": "program_counter", "layer": "front",
         "moves": "layouts_per_s", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    assert all(p.read_bytes() == b for p, b in before.items())
    assert not filecmp.dircmp(_load.HERE, bench).diff_files

    loader = importlib.util.spec_from_file_location(
        "chipbench_run_copy", bench / "run.py")
    copy = importlib.util.module_from_spec(loader)
    loader.loader.exec_module(copy)
    for trace, kind in ((0, "end_to_end"), (1, "per_layer")):
        rc = copy.main(["--workload", cell, "--seed", str(2**31 + 3),
                        "--seconds", "0.5", "--trace", str(trace),
                        "--rehearsal"])
        assert rc == 0
        res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert res["correct"] is True, res["check"]
        want = {m["name"] for m in copy.metrics_for(spec[kind], cell)}
        assert set(res["metrics"]) == want
    assert res["metrics"]["evaluations.one_by_one"]["value"] >= 1
