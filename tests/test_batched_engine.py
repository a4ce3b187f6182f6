"""Native batched engine: one-scatter batch bucketing + occupancy-tiered
reversal sweep.

Contracts certified here (see also test_engine.py::test_batched_matches_looped
for the per-layout-kind batched==looped sweep):

* integer metrics (N_c, E_c) from the natively batched program are
  bit-identical to looping the single-layout jit over the batch;
* the occupancy-tiered sweep is a pure layout change: tiered and
  flat-capacity plans agree exactly on integer metrics;
* bucket-padded batched evaluation (traced ``n_valid_*`` scalars) is
  exact for integer metrics;
* repeat batched calls under one plan never retrace;
* the ragged one-scatter bucketing reduces to the classic dense
  bucketing when every bucket has the same capacity.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, evaluate_layouts, evaluate_planned, \
    plan_readability
from repro.core import grid as gridlib

N_STRIPS = 64
RADIUS = 2.0


def random_edges(rng, n_vertices, n_edges):
    edges = set()
    while len(edges) < n_edges:
        v, u = rng.integers(0, n_vertices, 2)
        if v != u:
            edges.add((min(v, u), max(v, u)))
    return np.array(sorted(edges), dtype=np.int32)


def make_layout(kind):
    rng = np.random.default_rng(11)
    if kind == "random":
        n = 200
        pos = rng.uniform(0, 100, size=(n, 2)).astype(np.float32)
    elif kind == "grid":
        side = 14
        n = side * side
        xs, ys = np.meshgrid(np.arange(side), np.arange(side))
        pos = np.stack([xs.ravel(), ys.ravel()], axis=1).astype(np.float32)
        pos = pos * 6.0 + rng.normal(0, 0.15, size=pos.shape).astype(np.float32)
    elif kind == "cluster":
        centers = rng.uniform(0, 100, size=(4, 2))
        pts = [c + rng.normal(0, 4.0, size=(50, 2)) for c in centers]
        pos = np.concatenate(pts).astype(np.float32)
        n = pos.shape[0]
    else:
        raise KeyError(kind)
    edges = random_edges(rng, n, 2 * n)
    return jnp.asarray(pos), jnp.asarray(edges)


def make_batch(pos, n=5, sigma=1.0, seed=2):
    rng = np.random.default_rng(seed)
    return jnp.asarray(np.stack(
        [np.asarray(pos) + rng.normal(0, sigma, size=pos.shape)
         for _ in range(n)]).astype(np.float32))


@pytest.fixture(scope="module", params=["random", "grid", "cluster"])
def graph(request):
    return make_layout(request.param)


def assert_int_parity(got, want, i):
    assert int(got.node_occlusion[i]) == int(want.node_occlusion)
    assert int(got.edge_crossing[i]) == int(want.edge_crossing)
    assert int(got.crossing_count_for_angle[i]) == \
        int(want.crossing_count_for_angle)
    assert int(got.overflow[i]) == int(want.overflow)


def test_batched_integer_metrics_bit_identical(graph):
    """The acceptance-criteria contract: N_c / E_c from the native
    batched path == the looped single-layout jit, bit for bit (the grid
    layout is the nasty case: near-axis-parallel edges, ordinate ties)."""
    pos, edges = graph
    batch = make_batch(pos)
    plan = plan_readability(batch, edges, radius=RADIUS, n_strips=N_STRIPS)
    got = evaluate_layouts(plan, batch, edges)
    for i in range(batch.shape[0]):
        assert_int_parity(got, evaluate_planned(plan, batch[i], edges), i)


def test_tiered_vs_untiered_parity(graph):
    """Tiering is a pure data-layout change: a flat-capacity plan
    (strip_tiers cleared -> one tier at the planned cap) must agree
    exactly on integer metrics and to rounding on E_ca."""
    pos, edges = graph
    batch = make_batch(pos)
    plan = plan_readability(batch, edges, radius=RADIUS, n_strips=N_STRIPS)
    assert any(len(t[0]) > 1 for t in plan.strip_tiers), \
        "fixture should actually exercise multi-tier plans"
    flat = dataclasses.replace(plan, strip_tiers=())
    a = evaluate_layouts(plan, batch, edges)
    b = evaluate_layouts(flat, batch, edges)
    for i in range(batch.shape[0]):
        assert int(a.edge_crossing[i]) == int(b.edge_crossing[i])
        assert int(a.node_occlusion[i]) == int(b.node_occlusion[i])
        assert int(a.overflow[i]) == int(b.overflow[i])
        np.testing.assert_allclose(float(a.edge_crossing_angle[i]),
                                   float(b.edge_crossing_angle[i]),
                                   rtol=1e-6)
    # single-layout path too
    sa = evaluate_planned(plan, pos, edges)
    sb = evaluate_planned(flat, pos, edges)
    assert int(sa.edge_crossing) == int(sb.edge_crossing)
    np.testing.assert_allclose(float(sa.edge_crossing_angle),
                               float(sb.edge_crossing_angle), rtol=1e-6)


def test_batched_padded_parity(graph):
    """Bucket-padded batched evaluation (padded vertices parked + masked
    via the traced n_valid scalars, padded edges masked) keeps integer
    metrics bit-identical to the natural-size batched evaluation."""
    from repro.launch.session import PARK, pow2_bucket
    pos, edges = graph
    batch = np.asarray(make_batch(pos))
    B, n_v = batch.shape[0], batch.shape[1]
    n_e = edges.shape[0]
    plan = plan_readability(batch, edges, radius=RADIUS, n_strips=N_STRIPS)
    nat = evaluate_layouts(plan, jnp.asarray(batch), edges)
    vb = pow2_bucket(n_v + 1)
    eb = pow2_bucket(n_e + 1)
    batch_p = np.full((B, vb, 2), PARK, np.float32)
    batch_p[:, :n_v] = batch
    edges_p = np.zeros((eb, 2), np.int32)
    edges_p[:n_e] = np.asarray(edges)
    got = evaluate_layouts(plan, jnp.asarray(batch_p), jnp.asarray(edges_p),
                           np.int32(n_v), np.int32(n_e))
    for i in range(B):
        assert int(got.node_occlusion[i]) == int(nat.node_occlusion[i])
        assert int(got.edge_crossing[i]) == int(nat.edge_crossing[i])
        assert int(got.overflow[i]) == int(nat.overflow[i])
        np.testing.assert_allclose(float(got.minimum_angle[i]),
                                   float(nat.minimum_angle[i]), rtol=1e-6)
        np.testing.assert_allclose(float(got.edge_crossing_angle[i]),
                                   float(nat.edge_crossing_angle[i]),
                                   rtol=1e-6)


def test_batched_no_retrace():
    """Repeat batched calls with one plan and one batch shape hit the jit
    cache; a new batch size retraces exactly once."""
    pos, edges = make_layout("random")
    batch = make_batch(pos, n=4)
    plan = plan_readability(batch, edges, radius=RADIUS, n_strips=N_STRIPS)
    jax.block_until_ready(evaluate_layouts(plan, batch, edges))
    traces = engine.trace_count()
    jax.block_until_ready(evaluate_layouts(plan, batch + 1.0, edges))
    jax.block_until_ready(evaluate_layouts(plan, batch * 0.5, edges))
    assert engine.trace_count() == traces
    jax.block_until_ready(evaluate_layouts(plan, batch[:2], edges))
    assert engine.trace_count() == traces + 1


def test_batched_work_shape():
    """ONE strip build + ONE scatter + ONE tiered sweep per orientation
    for the WHOLE batch (the vmapped path used to pay these per trace as
    B-wide vmapped sort/scatter ops)."""
    pos, edges = make_layout("random")
    batch = make_batch(pos, n=6)
    plan = plan_readability(batch, edges, radius=RADIUS, n_strips=48)
    gridlib.reset_call_counts()
    jax.block_until_ready(evaluate_layouts(plan, batch, edges))
    assert gridlib.CALL_COUNTS == {"strip_builds": 2, "reversal_sweeps": 2,
                                   "cell_builds": 1, "vertex_sorts": 1,
                                   "halo_exchanges": 0,
                                   "vertex_range_splits": 0}


def test_gather_ragged_matches_dense_on_uniform_caps():
    """With uniform caps the ragged gather bucketing reduces exactly to
    the classic dense scatter bucketing — per batch row."""
    rng = np.random.default_rng(0)
    B, n, n_buckets, cap = 3, 500, 16, 64
    keys = rng.integers(0, n_buckets, (B, n)).astype(np.int32)
    val = rng.normal(size=(B, n)).astype(np.float32)
    valid = rng.random((B, n)) > 0.1
    off = np.arange(n_buckets, dtype=np.int64) * cap
    caps = np.full(n_buckets, cap, np.int64)
    flat_v, flat_ok, counts, ov = gridlib.gather_ragged_buckets(
        jnp.asarray(keys), n_buckets, off, caps, jnp.asarray(val),
        valid=jnp.asarray(valid))
    for b in range(B):
        dense_v, dense_ok, dense_counts, dense_ov = \
            gridlib.scatter_to_buckets(
                jnp.asarray(keys[b]), n_buckets, cap, jnp.asarray(val[b]),
                valid=jnp.asarray(valid[b]))
        np.testing.assert_array_equal(np.asarray(dense_v).ravel(),
                                      np.asarray(flat_v[b]))
        np.testing.assert_array_equal(np.asarray(dense_ok).ravel(),
                                      np.asarray(flat_ok[b]))
        np.testing.assert_array_equal(np.asarray(dense_counts),
                                      np.asarray(counts[b]))
        assert int(dense_ov) == int(ov[b])


def test_gather_ragged_per_bucket_caps_overflow():
    """A bucket over its own tier cap drops exactly its excess (counted),
    without touching other buckets' slots."""
    keys = jnp.asarray(np.array([[0] * 5 + [1] * 3 + [2] * 1], np.int32))
    val = jnp.arange(9, dtype=jnp.float32)[None]
    caps = np.array([2, 4, 4], np.int64)
    off = np.array([0, 2, 6], np.int64)
    v, ok, counts, ov = gridlib.gather_ragged_buckets(keys, 3, off, caps,
                                                      val)
    assert int(ov[0]) == 3                   # bucket 0 holds 2 of 5
    np.testing.assert_array_equal(np.asarray(counts[0]), [5, 3, 1])
    np.testing.assert_array_equal(np.asarray(v)[0, :2], [0.0, 1.0])
    np.testing.assert_array_equal(np.asarray(v)[0, 2:5], [5.0, 6.0, 7.0])
    # bucket 1's unused capacity (slot 5) stays invalid; bucket 2's single
    # element lands at its own offset (slot 6) untouched by the overflow
    np.testing.assert_array_equal(np.asarray(ok)[0, 4:7],
                                  [True, False, True])
    np.testing.assert_array_equal(np.asarray(v)[0, 6], 8.0)


def test_replan_grows_tiers():
    """replan_on_overflow floors every strip's tier capacity at growth x
    the old plan's, so the grown plan is never smaller anywhere."""
    pos, edges = make_layout("cluster")
    plan = plan_readability(pos, edges, radius=RADIUS, n_strips=N_STRIPS)
    starved = dataclasses.replace(
        plan, strip_plans=tuple((ms, 8) for ms, _ in plan.strip_plans),
        strip_tiers=())
    res = evaluate_planned(starved, pos, edges)
    assert int(res.overflow) > 0
    grown = engine.replan_on_overflow(starved, pos, edges, res)
    res2 = evaluate_planned(grown, pos, edges)
    assert int(res2.overflow) == 0
    for axis_i in range(len(grown.strip_plans)):
        _, old_caps, _, _ = engine._tier_layout(starved, axis_i)
        _, new_caps, _, _ = engine._tier_layout(grown, axis_i)
        assert (new_caps >= old_caps).all()
    want = evaluate_planned(plan, pos, edges)
    assert int(res2.edge_crossing) == int(want.edge_crossing)
