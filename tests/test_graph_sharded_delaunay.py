"""The graph-sharded backend on a DIMACS10 Delaunay graph, through the
normal front door.

``Evaluator(EvalConfig(backend="graph_sharded")).evaluate_batch`` with
``plan=Evaluator.plan(...)`` scores a delaunay_n10 graph (the
``delaunay_n20`` benchmark configuration's rehearsal size: the same
geometry scaled to the n10 vertex spacing) on 1, 2 and 4 forced host
devices, each in a subprocess (the device count must be set before jax
initializes).  Each count must match the benchmark's plain float64
reference (``benchmarks/chip/reference.py``) within the limits of the
``delaunay_n20.sharded`` cell, and the integer metrics must be identical
across counts.  ``Evaluator.plan`` must be the untiered plan the backend
makes for itself, so a caller that plans once reuses one jit entry, and
the lowered program must name every scope of the graph-sharded body.
"""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
COUNTS = (1, 2, 4)
INTS = ("node_occlusion", "edge_crossing", "crossing_count_for_angle",
        "overflow")
SCOPES = ("graph_shard.occlusion", "graph_shard.halo", "strips.build/axis0",
          "strips.build/axis1", "graph_shard.sweep/axis0",
          "graph_shard.sweep/axis1", "graph_shard.reduce", "min_angle",
          "edge_length")

SCRIPT = r"""
import json, os, re, sys
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + sys.argv[1])
import jax
import numpy as np

ndev, bench, cfg = int(sys.argv[1]), sys.argv[2], json.loads(sys.argv[3])
sys.path.insert(0, bench)
import check
import graphs
import reference
from kit import host_scores, jittered
from repro.api import EvalConfig, Evaluator
from repro.distributed import graph_sharded as gs

assert len(jax.devices()) == ndev
pos, edges, spacing = graphs.build(cfg["graph"], bench)
ev = Evaluator(EvalConfig(**dict(cfg["eval"], shards=ndev)))
batch = jittered(np.random.default_rng(5), pos, 2, 0.3 * spacing)
plan = ev.plan(batch, edges)

made = []
dispatch = gs.evaluate_graph_sharded


def spy(mesh, p, *args, **kwargs):
    made.append(p)
    return dispatch(mesh, p, *args, **kwargs)


gs.evaluate_graph_sharded = spy
res = ev.evaluate_batch(batch, edges, plan=plan)
ev.evaluate_batch(batch, edges)
geometry = dict(radius=cfg["eval"]["radius"], n_strips=cfg["eval"]["n_strips"],
                ideal_angle_deg=cfg["ideal_angle_deg"])
layouts = []
for i in range(batch.shape[0]):
    prog = host_scores(res, i)
    layouts.append({"scores": prog, "gaps": check.gaps(
        prog, reference.scores(batch[i], edges, geometry))})
out = {"layouts": layouts, "mesh": ev._mesh().size,
       "untiered": all(t == () for t in plan.strip_tiers),
       "self_plan_is_the_callers": all(p == plan for p in made),
       "jit_entries": gs._jit_graph_sharded._cache_size()}
if ndev == 4:
    text = gs._jit_graph_sharded.lower(
        gs.plan_with_shard_spec(plan, ndev), ev._mesh(), batch[0], edges,
        None, None).as_text(debug_info=True)
    out["missing_scopes"] = [s for s in json.loads(sys.argv[4]) if not
                             re.search(r'[/"]' + re.escape(s) + "/", text)]
print(json.dumps(out))
"""


def rehearsal_config():
    with open(os.path.join(BENCH, "configs", "delaunay_n20.json")) as f:
        cfg = json.load(f)
    for key, over in cfg["rehearsal"].items():
        cfg[key] = dict(cfg[key], **over)
    return cfg


def cell_limits():
    with open(os.path.join(BENCH, "limits",
                           "delaunay_n20.sharded.json")) as f:
        return json.load(f)["limits"]


@pytest.fixture(scope="module")
def runs():
    """``{device count: the subprocess's result}``, the counts run side by
    side."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    cfg = json.dumps(rehearsal_config())
    procs = {n: subprocess.Popen(
        [sys.executable, "-c", SCRIPT, str(n), BENCH, cfg,
         json.dumps(SCOPES)], env=env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True) for n in COUNTS}
    out = {}
    for n, p in procs.items():
        stdout, stderr = p.communicate(timeout=300)
        assert p.returncode == 0, stderr[-3000:]
        out[n] = json.loads(stdout.strip().splitlines()[-1])
    return out


def test_the_rehearsal_is_the_configuration_at_the_n10_spacing():
    cfg = rehearsal_config()
    assert cfg["graph"] == {"generator": "delaunay", "log2_vertices": 10,
                            "seed": 20}
    assert cfg["eval"]["backend"] == "graph_sharded"
    # radius and strip width in vertex spacings are delaunay_n20's
    spacing_n20, spacing_n10 = 100 / 2 ** 10, 100 / 2 ** 5
    assert cfg["eval"]["radius"] / spacing_n10 == 0.125 / spacing_n20
    assert cfg["eval"]["n_strips"] * spacing_n10 == 100.0


@pytest.mark.parametrize("n", COUNTS)
def test_each_shard_count_matches_the_reference_within_the_cell_limits(
        runs, n):
    limits = cell_limits()
    run = runs[n]
    assert run["mesh"] == n
    for layout in run["layouts"]:
        assert layout["scores"]["overflow"] == 0
        over = {k: v for k, v in layout["gaps"].items() if v > limits[k]}
        assert over == {}
        assert layout["scores"]["edge_crossing"] > 0


def test_integer_metrics_are_identical_across_shard_counts(runs):
    ints = {n: [[lay["scores"][k] for k in INTS]
                for lay in runs[n]["layouts"]] for n in COUNTS}
    assert ints[1] == ints[2] == ints[4]


@pytest.mark.parametrize("n", COUNTS)
def test_evaluator_plan_is_the_untiered_plan_the_backend_runs(runs, n):
    run = runs[n]
    assert run["untiered"] and run["self_plan_is_the_callers"]
    assert run["jit_entries"] == 1


def test_the_lowered_program_names_every_graph_shard_scope(runs):
    assert runs[4]["missing_scopes"] == []


def test_the_fused_plan_keeps_its_tiers(monkeypatch):
    from repro.api import EvalConfig, Evaluator
    from repro.core import engine, grid

    monkeypatch.syspath_prepend(BENCH)
    import graphs

    cfg = rehearsal_config()
    pos, edges, _ = graphs.build(cfg["graph"], BENCH)
    kw = {k: v for k, v in cfg["eval"].items() if k not in ("backend",
                                                             "shards")}
    fused = EvalConfig(**kw)
    plan = Evaluator(fused).plan(pos, edges)
    assert plan == engine.plan_readability(pos, edges, **fused.plan_kwargs())
    assert all(len(t) > 0 for t in plan.strip_tiers)
    sharded = EvalConfig(**kw, backend="graph_sharded")
    flat = Evaluator(sharded).plan(pos, edges)
    # the sharded plan adds only its shard spec, with the M_a degree bound
    assert dataclasses.replace(flat, graph_shard=None) == \
        engine.plan_readability(pos, edges,
                                **sharded.plan_kwargs(tier_default=False))
    assert flat.graph_shard.deg_cap == grid.plan_degree_cap(edges)
    assert flat.strip_plans == plan.strip_plans


def test_a_traced_call_records_the_graph_sharded_spans(tmp_path):
    import jax

    from repro import tracing
    from repro.api import EvalConfig, Evaluator

    rng = np.random.default_rng(3)
    pos = rng.uniform(0, 40, (200, 2)).astype(np.float32)
    edges = np.stack([np.arange(199), np.arange(1, 200)], 1).astype(np.int32)
    ev = Evaluator(EvalConfig(radius=1.0, n_strips=16,
                              backend="graph_sharded", shards=1))
    batch = pos[None]
    plan = ev.plan(batch, edges)
    ev.evaluate_batch(batch, edges, plan=plan)
    tracing.reset()
    with jax.profiler.trace(str(tmp_path)):
        ev.evaluate_batch(batch, edges, plan=plan)
    spans = tracing.snapshot()["spans"]
    tracing.reset()
    by_id = {s["id"]: s for s in spans}
    for name in ("graph_sharded.inputs", "graph_sharded.launch"):
        (sp,) = [s for s in spans if s["name"] == name]
        assert by_id[sp["parent"]]["name"] == "engine.dispatch"
