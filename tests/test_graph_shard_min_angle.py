"""M_a split by vertex range on the graph-sharded path.

``engine.evaluate_graph_shard_body`` scores M_a per shard: the 2E
half-edges are sorted by source vertex (replicated), each shard scores
the vertex runs that start in its share of that order, and one psum
joins ``(sum of d_v, counted vertices)``.  On 1, 2 and 4 forced host
devices (one subprocess each: the device count must be set before jax
initializes) the sharded M_a must match the single-layout
``min_angle.minimum_angle`` on graphs built to reach every edge of the
cut rule:

* ``straddle``: a vertex run crosses a cut between shards;
* ``hub``: one vertex holds more half-edges than a shard's share ``q``;
* ``leaves``: degree-1 vertices (their only gap is the 2*pi wrap);
* ``isolated``: vertices without edges, which are not counted;
* ``padded``: a zero edge tail and a parked vertex tail under the traced
  ``n_valid_*`` scalars, whose trash vertex V must not be counted;
* ``empty_shards``: fewer vertex runs than shards.

``overflow_replan``: a degree bound below the hub's run must report
``overflow`` (never drop half-edges silently), and
``engine.replan_on_overflow`` must regrow it to the healthy bound.
``counters``: ``vertex_range_splits`` bumps on the graph-sharded path and
stays 0 on the fused single-host programs.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COUNTS = (1, 2, 4)
CASES = ("straddle", "hub", "leaves", "isolated", "padded", "empty_shards",
         "overflow_replan", "counters")

SCRIPT = r"""
import dataclasses, json, os, sys, types
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + sys.argv[1])
import jax
import numpy as np
from jax.sharding import AxisType

from repro.core import engine, grid
from repro.core.min_angle import minimum_angle
from repro.distributed.graph_sharded import evaluate_graph_sharded

ndev = int(sys.argv[1])
assert len(jax.devices()) == ndev
mesh = jax.make_mesh((ndev,), ("graph",), axis_types=(AxisType.Auto,))
rng = np.random.default_rng(16)


def layout(n_v):
    return rng.uniform(0, 50, (n_v, 2)).astype(np.float32)


def random_edges(n_v, n_e):
    edges = set()
    while len(edges) < n_e:
        v, u = (int(x) for x in rng.integers(0, n_v, 2))
        if v != u:
            edges.add((min(v, u), max(v, u)))
    return np.array(sorted(edges), np.int32)


def star(n_leaves, hub):
    # hub in the middle of the id order, so its run crosses the cuts; a
    # path through the leaves before it puts their runs ahead of its own
    leaves = [v for v in range(n_leaves + 1) if v != hub]
    edges = [(hub, v) for v in leaves]
    edges += [(v, v + 1) for v in range(hub - 1)]
    return np.array(edges, np.int32)


def runs(edges, n_valid=None):
    e = edges if n_valid is None else edges[:n_valid]
    return np.sort(np.concatenate([e[:, 0], e[:, 1]]))


def straddles(edges):
    s = runs(edges)
    q = -(-len(s) // ndev)
    return any(s[i * q] == s[i * q - 1] for i in range(1, ndev)
               if i * q < len(s))


def sharded(plan, pos, edges, **kw):
    r = jax.device_get(evaluate_graph_sharded(mesh, plan, pos, edges, **kw))
    return float(r.minimum_angle), int(r.overflow)


def m_a_plan(pos, edges):
    return engine.plan_readability(pos, edges, metrics=("minimum_angle",))


def compare(pos, edges, **facts):
    m_a, overflow = sharded(m_a_plan(pos, edges), pos, edges)
    ref = float(minimum_angle(pos, edges)[0])
    return dict(m_a=m_a, ref=ref, overflow=overflow, **facts)


out = {}

e = random_edges(80, 200)
out["straddle"] = compare(layout(80), e, straddles=straddles(e))

e = star(120, 60)
out["hub"] = compare(layout(121), e, hub_deg=120,
                     q=-(-2 * len(e) // ndev), straddles=straddles(e))

e = np.concatenate([np.arange(60, dtype=np.int32).reshape(30, 2),
                    random_edges(20, 30) + 60])
deg = np.bincount(e.reshape(-1))
out["leaves"] = compare(layout(80), e, n_deg1=int(np.sum(deg == 1)))

ids = rng.permutation(150)[:50]
e = ids[random_edges(50, 90)].astype(np.int32)
out["isolated"] = compare(layout(150), e,
                          n_isolated=150 - len(np.unique(e)))

n_v, e = 70, random_edges(70, 160)
pos = layout(n_v)
pos_p = np.full((128, 2), -1.0e6, np.float32)
pos_p[:n_v] = pos
edges_p = np.zeros((256, 2), np.int32)
edges_p[:len(e)] = e
m_a, overflow = sharded(m_a_plan(pos, e), pos_p, edges_p,
                        n_valid_vertices=np.int32(n_v),
                        n_valid_edges=np.int32(len(e)))
out["padded"] = dict(m_a=m_a, ref=float(minimum_angle(pos, e)[0]),
                     overflow=overflow)

e = np.array([[0, 1]], np.int32)
pos = np.array([[0.0, 0.0], [1.0, 1.0], [3.0, 0.0]], np.float32)
out["empty_shards"] = compare(pos, e, n_runs=len(np.unique(e)))

e = star(120, 60)
pos = layout(121)
plan = m_a_plan(pos, e)
healthy = grid.plan_degree_cap(e)
starved = dataclasses.replace(plan, graph_shard=grid.plan_graph_shards(
    plan.n_strips, plan.grid_nx, plan.grid_ny, ndev, deg_cap=2))
m_a0, overflow0 = sharded(starved, pos, e)
grown = engine.replan_on_overflow(starved, pos, e,
                                  types.SimpleNamespace(overflow=overflow0))
m_a, overflow = sharded(grown, pos, e)
out["overflow_replan"] = dict(
    m_a=m_a, ref=float(minimum_angle(pos, e)[0]), overflow=overflow,
    starved_overflow=overflow0, grown_deg_cap=grown.graph_shard.deg_cap,
    healthy_deg_cap=healthy)

pos, e = layout(90), random_edges(90, 200)
full = engine.plan_readability(pos, e, radius=1.0, n_strips=16,
                               tier_strips=False)
grid.reset_call_counts()
jax.block_until_ready(engine.evaluate_planned(full, pos, e))
jax.block_until_ready(engine.evaluate_layouts(full, pos[None], e))
fused = dict(grid.CALL_COUNTS)
grid.reset_call_counts()
m_a, overflow = sharded(full, pos, e)
out["counters"] = dict(
    m_a=m_a, ref=float(minimum_angle(pos, e)[0]), overflow=overflow,
    fused_splits=fused["vertex_range_splits"],
    fused_sorts=fused["vertex_sorts"],
    sharded_splits=grid.CALL_COUNTS["vertex_range_splits"])
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    """``{device count: {case: readings}}``, the counts run side by side."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    procs = {n: subprocess.Popen([sys.executable, "-c", SCRIPT, str(n)],
                                 env=env, stdout=subprocess.PIPE,
                                 stderr=subprocess.PIPE, text=True)
             for n in COUNTS}
    out = {}
    for n, p in procs.items():
        stdout, stderr = p.communicate(timeout=300)
        assert p.returncode == 0, stderr[-3000:]
        out[n] = json.loads(stdout.strip().splitlines()[-1])
    return out


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("ndev", COUNTS)
def test_sharded_min_angle_matches_the_single_layout_path(results, ndev,
                                                          case):
    r = results[ndev][case]
    np.testing.assert_allclose(r["m_a"], r["ref"], rtol=1e-6)
    assert r["overflow"] == 0
    if case == "straddle" and ndev > 1:
        assert r["straddles"]
    if case == "hub":
        assert r["straddles"] == (ndev > 1)
        if ndev == 4:
            assert r["hub_deg"] > r["q"]
    if case == "leaves":
        assert r["n_deg1"] >= 60
    if case == "isolated":
        assert r["n_isolated"] >= 100
    if case == "empty_shards" and ndev == 4:
        assert r["n_runs"] < ndev
    if case == "overflow_replan":
        # one shard: the window is all 2E half-edges and cannot overflow,
        # so the replan keeps the starved bound
        assert (r["starved_overflow"] > 0) == (ndev > 1)
        assert r["healthy_deg_cap"] == 128
        assert r["grown_deg_cap"] == (128 if ndev > 1 else 2)
    if case == "counters":
        assert r["fused_splits"] == 0 and r["fused_sorts"] == 2
        assert r["sharded_splits"] == 1
