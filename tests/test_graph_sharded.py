"""Certification of the graph-axis sharded engine (``backend="graph_sharded"``).

ONE layout spatially partitioned across 1/2/4 forced-host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count``) must yield

* integer metrics **bit-identical** to the single-host fused engine
  under the same flat-capacity plan,
* results **invariant to the shard count** (the spatial decomposition is
  an implementation detail, not a semantics knob),
* exactly **one halo exchange per evaluation** — zero for strip-only
  metric subsets (the ``halo_exchanges`` counter in
  :data:`repro.core.grid.CALL_COUNTS` bumps per trace),
* correct counting of occlusion pairs that **straddle shard boundaries**
  (a vertical column of vertices spaced just inside the occlusion
  threshold crosses every cell-row boundary: each adjacent pair must be
  counted exactly once by the owner-cell rule + halo),
* a working **replan-on-overflow** loop under sharding.

Each device count runs in a subprocess (the forced device count must be
set before jax initializes); the parent diffs JSON results across
counts.  The in-process tests cover the typed-error taxonomy of the
distributed dispatch paths (the
:class:`~repro.core.validate.BackendUnavailableError` regression for
``pairwise`` / ``gridded`` / ``graph_sharded``) and the serving
session's degradation ladder (graph_sharded -> single-host fused).
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

SCRIPT = r"""
import os, sys, json, dataclasses
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + sys.argv[1])
import jax
import numpy as np
from jax.sharding import AxisType

from repro.core import engine
from repro.core import grid
from repro.core.keys import EvalConfig, pow2_bucket
from repro.distributed.graph_sharded import evaluate_graph_sharded

ndev = int(sys.argv[1])
assert len(jax.devices()) == ndev

rng = np.random.default_rng(11)
n_v = 300
pos = rng.uniform(0, 80, (n_v, 2)).astype(np.float32)
edges = set()
while len(edges) < 2 * n_v:
    v, u = rng.integers(0, n_v, 2)
    if v != u:
        edges.add((min(v, u), max(v, u)))
edges = np.array(sorted(edges), np.int32)
n_e = edges.shape[0]

# flat strips: the per-device slot maps must be SPMD-uniform, so the
# sharded sweep always runs the flat top capacity (same rule as the
# strip-sharded distributed driver)
plan = engine.plan_readability(pos, edges, radius=2.0, n_strips=48,
                               tier_strips=False)
mesh = jax.make_mesh((ndev,), ("graph",), axis_types=(AxisType.Auto,))


def fetch(res):
    res = jax.device_get(res)
    return {
        "node_occlusion": int(res.node_occlusion),
        "edge_crossing": int(res.edge_crossing),
        "crossing_count_for_angle": int(res.crossing_count_for_angle),
        "overflow": int(res.overflow),
        "edge_crossing_angle": float(res.edge_crossing_angle),
        "minimum_angle": float(res.minimum_angle),
        "edge_length_variation": float(res.edge_length_variation),
    }


out = {"single_host": fetch(engine.evaluate_planned(plan, pos, edges))}

c0 = grid.CALL_COUNTS["halo_exchanges"]
out["natural"] = fetch(evaluate_graph_sharded(mesh, plan, pos, edges))
out["halo_traces"] = grid.CALL_COUNTS["halo_exchanges"] - c0

# padded path: PARK-filled vertex tail + zero edge tail, masked via the
# traced n_valid scalars (the serving session's wire format)
vb, eb = pow2_bucket(n_v + 1), pow2_bucket(n_e + 1)
pos_p = np.full((vb, 2), -1.0e6, np.float32)
pos_p[:n_v] = pos
edges_p = np.zeros((eb, 2), np.int32)
edges_p[:n_e] = edges
out["padded"] = fetch(evaluate_graph_sharded(
    mesh, plan, pos_p, edges_p,
    n_valid_vertices=np.int32(n_v), n_valid_edges=np.int32(n_e)))

# strip-only metric subset: the traced program must contain NO halo
# exchange and build NO occlusion cells (metric pruning is real at
# trace level, under sharding too)
xplan = engine.plan_readability(pos, edges, radius=2.0, n_strips=48,
                                tier_strips=False,
                                metrics=("edge_crossing",))
c_h = grid.CALL_COUNTS["halo_exchanges"]
c_c = grid.CALL_COUNTS["cell_builds"]
xres = jax.device_get(evaluate_graph_sharded(mesh, xplan, pos, edges))
out["crossing_only"] = {"edge_crossing": int(xres.edge_crossing)}
out["crossing_only_halo"] = grid.CALL_COUNTS["halo_exchanges"] - c_h
out["crossing_only_cells"] = grid.CALL_COUNTS["cell_builds"] - c_c

# boundary-straddling occlusion: a vertical column spaced at 0.9 x the
# occlusion threshold crosses every grid cell row, so under 2/4 shards
# many adjacent pairs straddle a shard boundary — each must be counted
# exactly once (owner-cell rule + halo), for exactly n - 1 occlusions
r = 2.0
n_col = 64
col = np.stack([np.full(n_col, 10.0, np.float32),
                np.arange(n_col, dtype=np.float32) * (0.9 * 2.0 * r)],
               axis=1)
cedges = np.array([[i, i + 1] for i in range(n_col - 1)], np.int32)
cplan = engine.plan_readability(col, cedges, radius=r, n_strips=16,
                                tier_strips=False)
cres = jax.device_get(evaluate_graph_sharded(mesh, cplan, col, cedges))
out["boundary_occlusion"] = int(cres.node_occlusion)
assert out["boundary_occlusion"] == n_col - 1, out["boundary_occlusion"]

# replan-on-overflow under sharding: starve the strip capacities, watch
# the sharded result report overflow, grow via the engine's replan, and
# converge to the healthy plan's metrics
starved = dataclasses.replace(
    plan, strip_plans=tuple((ms, 8) for ms, _ in plan.strip_plans),
    strip_tiers=())
r1 = jax.device_get(evaluate_graph_sharded(mesh, starved, pos, edges))
assert int(r1.overflow) > 0, "starved plan must overflow"
grown = engine.replan_on_overflow(starved, pos, edges, r1)
out["replan"] = fetch(evaluate_graph_sharded(mesh, grown, pos, edges))
assert out["replan"]["overflow"] == 0, "grown plan must not overflow"

# serving-session routing: backend="graph_sharded" rides the session
# (validation, pow2 padding, plan cache) and must report the dispatch
from repro.launch.session import EvalSession
sess = EvalSession(EvalConfig(radius=2.0, n_strips=48,
                              backend="graph_sharded"), mesh=mesh)
s = sess.evaluate(pos, edges)
out["session"] = {"node_occlusion": s.node_occlusion,
                  "edge_crossing": s.edge_crossing,
                  "overflow": s.overflow}
assert sess.stats["graph_sharded_dispatches"] > 0, sess.stats
assert sess.health()["dispatch_mode"] == "graph_sharded"

print("RESULT " + json.dumps(out))
"""

INT_KEYS = ("node_occlusion", "edge_crossing", "crossing_count_for_angle",
            "overflow")
FLOAT_KEYS = ("edge_crossing_angle", "minimum_angle",
              "edge_length_variation")


def run_with_devices(ndev: int) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    result = subprocess.run([sys.executable, "-c", SCRIPT, str(ndev)],
                            env=env, capture_output=True, text=True,
                            timeout=900)
    assert result.returncode == 0, result.stdout + "\n" + result.stderr
    line = [l for l in result.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_shard_count_invariance_and_parity():
    outs = {ndev: run_with_devices(ndev) for ndev in (1, 2, 4)}
    for ndev, out in outs.items():
        # bit-identity vs the single-host fused engine, per device count
        for k in INT_KEYS:
            assert out["natural"][k] == out["single_host"][k], (ndev, k)
            assert out["padded"][k] == out["natural"][k], (ndev, k)
        for k in FLOAT_KEYS:
            np.testing.assert_allclose(
                out["natural"][k], out["single_host"][k], rtol=1e-5,
                err_msg=f"{ndev}/single_host/{k}")
        # the collective budget: ONE halo exchange per traced evaluation,
        # ZERO (and zero cell builds) for the strip-only subset
        assert out["halo_traces"] == 1, (ndev, out["halo_traces"])
        assert out["crossing_only_halo"] == 0, (ndev,)
        assert out["crossing_only_cells"] == 0, (ndev,)
        assert out["crossing_only"]["edge_crossing"] == \
            out["natural"]["edge_crossing"], (ndev,)
        # cross-boundary pairs counted exactly once
        assert out["boundary_occlusion"] == 63, (ndev,)
        # a grown plan converges to the healthy counts
        for k in ("node_occlusion", "edge_crossing"):
            assert out["replan"][k] == out["natural"][k], (ndev, k)
            assert out["session"][k] == out["natural"][k], (ndev, k)
    # shard-count invariance: 2- and 4-device runs agree with 1-device
    base = outs[1]
    for ndev in (2, 4):
        for path in ("natural", "padded", "replan", "session"):
            for k in INT_KEYS:
                if k in outs[ndev][path]:
                    assert outs[ndev][path][k] == base[path][k], \
                        (ndev, path, k)
            for k in FLOAT_KEYS:
                if k in outs[ndev][path]:
                    np.testing.assert_allclose(
                        outs[ndev][path][k], base[path][k], rtol=1e-5,
                        err_msg=f"{ndev}/{path}/{k}")


# ---------------------------------------------------------------------------
# in-process: typed-error taxonomy + degradation ladder (1 device is enough)
# ---------------------------------------------------------------------------

def _fixture(n_v=120, seed=5):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0, 40, (n_v, 2)).astype(np.float32)
    edges = set()
    while len(edges) < 2 * n_v:
        v, u = rng.integers(0, n_v, 2)
        if v != u:
            edges.add((min(v, u), max(v, u)))
    return pos, np.array(sorted(edges), np.int32)


def _mesh1():
    import jax
    from jax.sharding import AxisType
    return jax.make_mesh((1,), ("x",), axis_types=(AxisType.Auto,))


def test_graph_sharded_dispatch_failure_is_typed(monkeypatch):
    from repro.api import BackendUnavailableError
    from repro.core import engine
    from repro.distributed import graph_sharded as gs

    pos, edges = _fixture()
    plan = engine.plan_readability(pos, edges, radius=1.0, n_strips=16,
                                   tier_strips=False)

    def boom(*a, **k):
        raise RuntimeError("device lost")

    monkeypatch.setattr(gs, "_jit_graph_sharded", boom)
    with pytest.raises(BackendUnavailableError) as ei:
        gs.evaluate_graph_sharded(_mesh1(), plan, pos, edges)
    assert isinstance(ei.value.__cause__, RuntimeError)
    assert hasattr(ei.value, "request_index")


def test_pairwise_dispatch_failure_is_typed(monkeypatch):
    """Regression: a raw shard_map launch failure used to escape as
    whatever the runtime threw — the session/server ladders couldn't
    catch it.  Now one typed BackendUnavailableError, cause chained."""
    import jax
    from repro.api import BackendUnavailableError
    from repro.distributed import pairwise

    pos, edges = _fixture()

    def bad_jit(fn, **kw):
        def run(*a, **k):
            raise RuntimeError("XlaRuntimeError: computation failed")
        return run

    monkeypatch.setattr(jax, "jit", bad_jit)
    mesh = _mesh1()
    with pytest.raises(BackendUnavailableError) as ei:
        pairwise.sharded_occlusion_count(mesh, pos, 1.0)
    assert isinstance(ei.value.__cause__, RuntimeError)
    assert ei.value.request_index == 0
    with pytest.raises(BackendUnavailableError) as ei:
        pairwise.sharded_crossing_count(mesh, pos, edges)
    assert isinstance(ei.value.__cause__, RuntimeError)
    with pytest.raises(BackendUnavailableError) as ei:
        pairwise.ring_occlusion_count(mesh, pos, 1.0)
    assert isinstance(ei.value.__cause__, RuntimeError)


def test_gridded_dispatch_failure_is_typed(monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.api import BackendUnavailableError
    from repro.core import engine, grid
    from repro.distributed import gridded

    pos, edges = _fixture()
    plan = engine.plan_readability(pos, edges, radius=1.0, n_strips=16,
                                   tier_strips=False)
    max_segments, cap = plan.strip_plans[0]
    segs = grid.build_strip_segments_batched(
        jnp.asarray(pos)[None], jnp.asarray(edges), plan.n_strips,
        max_segments, axis=plan.axes[0])
    buckets = grid.bucketize_segments(
        jax.tree_util.tree_map(lambda a: a[0], segs), plan.n_strips, cap)

    def bad_jit(fn, **kw):
        def run(*a, **k):
            raise RuntimeError("XlaRuntimeError: computation failed")
        return run

    monkeypatch.setattr(jax, "jit", bad_jit)
    with pytest.raises(BackendUnavailableError) as ei:
        gridded.sharded_reversal_stats(_mesh1(), buckets)
    assert isinstance(ei.value.__cause__, RuntimeError)
    assert ei.value.request_index == 0


def test_session_degrades_graph_sharded_to_fused(monkeypatch, caplog):
    """Mesh loss mid-serve: the graph_sharded rung fails, the session
    falls down the ladder to single-host fused, the request still gets
    valid scores, and the degradation is visible in stats/health and
    logged once with its cause."""
    import logging

    from repro.api import EvalConfig, Evaluator
    from repro.core.validate import BackendUnavailableError
    from repro.distributed import graph_sharded as gs

    pos, edges = _fixture()
    ref = Evaluator(EvalConfig(radius=1.0, n_strips=16)).evaluate(pos, edges)

    def boom(*a, **k):
        raise BackendUnavailableError("mesh lost")

    monkeypatch.setattr(gs, "evaluate_graph_sharded", boom)
    ev = Evaluator(EvalConfig(radius=1.0, n_strips=16,
                              backend="graph_sharded"))
    with caplog.at_level(logging.WARNING, logger="repro.launch.session"):
        got = ev.evaluate(pos, edges)
        sess = ev._bound_session()
        sess.restore_mesh()                 # the rung is tried, and fails,
        ev.evaluate(pos + 0.5, edges)       # a second time
    assert int(got.node_occlusion) == int(ref.node_occlusion)
    assert int(got.edge_crossing) == int(ref.edge_crossing)
    assert sess.stats["degraded_dispatches"] >= 2
    assert sess.stats["graph_sharded_dispatches"] == 0
    assert sess.health()["dispatch_mode"] != "graph_sharded"
    logged = [r for r in caplog.records if r.name == "repro.launch.session"]
    assert len(logged) == 1
    assert "mesh lost" in str(logged[0].exc_info[1])


def test_graph_sharded_rejects_bad_shapes():
    from repro.core import engine
    import jax
    from jax.sharding import AxisType

    from repro.distributed.graph_sharded import evaluate_graph_sharded

    pos, edges = _fixture()
    plan = engine.plan_readability(pos, edges, radius=1.0, n_strips=16,
                                   tier_strips=False)
    with pytest.raises(ValueError):
        evaluate_graph_sharded(_mesh1(), plan,
                               np.stack([pos, pos]), edges)
    mesh2d = jax.make_mesh((1, 1), ("a", "b"),
                           axis_types=(AxisType.Auto,) * 2)
    with pytest.raises(ValueError):
        evaluate_graph_sharded(mesh2d, plan, pos, edges)
