"""Fused-engine parity: plan-once/evaluate-many must reproduce the unfused
per-metric paths bit-for-bit, batched == looped, and the jit cache must
actually hit (no retrace on the second call with the same plan)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (evaluate_layout, evaluate_layouts, evaluate_planned,
                        plan_readability)
from repro.core import engine
from repro.core import grid as gridlib
from repro.core.crossing import (count_crossings_enhanced,
                                 count_crossings_strips)
from repro.core.crossing_angle import (DEFAULT_IDEAL,
                                       crossing_angle_enhanced,
                                       crossing_angle_strips)
from repro.core.edge_length import edge_length_variation
from repro.core.min_angle import minimum_angle
from repro.core.occlusion import (count_occlusions_enhanced,
                                  count_occlusions_exact,
                                  count_occlusions_gridded)

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
    rng = np.random.default_rng(7)
    if kind == "random":
        n = 250
        pos = rng.uniform(0, 100, size=(n, 2)).astype(np.float32)
    elif kind == "grid":
        # regular lattice + jitter: many near-axis-parallel edges, heavy
        # boundary-ordinate ties — the strip algorithms' nasty case
        side = 16
        n = side * side
        xs, ys = np.meshgrid(np.arange(side), np.arange(side))
        pos = np.stack([xs.ravel(), ys.ravel()], axis=1).astype(np.float32)
        pos = pos * 6.0 + rng.normal(0, 0.15, size=pos.shape).astype(np.float32)
    elif kind == "cluster":
        # gaussian blobs: dense cells / dense strips in a few places
        centers = rng.uniform(0, 100, size=(5, 2))
        pts = [c + rng.normal(0, 4.0, size=(50, 2)) for c in centers]
        pos = np.concatenate(pts).astype(np.float32)
        n = pos.shape[0]
    else:
        raise KeyError(kind)
    edges = random_edges(rng, n, 2 * n)
    return jnp.asarray(pos), jnp.asarray(edges)


@pytest.fixture(scope="module", params=["random", "grid", "cluster"])
def graph(request):
    return make_layout(request.param)


def unfused_reference(pos, edges, orientation="both"):
    """The pre-engine evaluate_layout body: per-metric enhanced calls.

    Each building block runs under ``jax.jit`` (as the engine runs it) so
    the bit-identity assertions compare XLA-compiled against XLA-compiled
    — eager dispatch rounds a few strip-boundary ordinates differently
    (no fused multiply-add) and can flip exact ties on degenerate
    layouts.
    """
    origin, nx, ny, cap, size = gridlib.plan_occlusion_grid(pos, RADIUS)
    occ, occ_ov = jax.jit(count_occlusions_gridded,
                          static_argnums=(1, 2, 3, 4, 5),
                          static_argnames=("cell_block", "cell_size"))(
        pos, RADIUS, origin, nx, ny, cap, cell_block=min(512, nx * ny),
        cell_size=size)
    m_a, _ = jax.jit(minimum_angle)(pos, edges)
    m_l = jax.jit(edge_length_variation)(pos, edges)
    axes = {"vertical": (0,), "both": (0, 1)}[orientation]
    cross, angle = [], []
    for axis in axes:
        ms, scap = gridlib.plan_strips(pos, edges, N_STRIPS, axis=axis)
        kw = dict(n_strips=N_STRIPS, max_segments=ms, cap=scap, axis=axis,
                  strip_block=min(256, N_STRIPS))
        cross.append(jax.jit(functools.partial(
            count_crossings_strips, **kw))(pos, edges))
        angle.append(jax.jit(functools.partial(
            crossing_angle_strips, **kw))(pos, edges))
    e_c = max(int(c) for c, _ in cross)
    ec_ov = max(int(ov) for _, ov in cross)
    best = angle[0]
    for cand in angle[1:]:
        if int(cand[1]) > int(best[1]):
            best = cand
    e_ca, cnt, _, _ = best
    # overflow reference: the engine's strip decomposition is shared by
    # E_c and E_ca, so dropped segments count ONCE (max over
    # orientations), not once per metric
    return dict(node_occlusion=int(occ), minimum_angle=float(m_a),
                edge_length_variation=float(m_l), edge_crossing=e_c,
                edge_crossing_angle=float(e_ca),
                crossing_count_for_angle=int(cnt),
                overflow=int(occ_ov) + ec_ov)


@pytest.mark.parametrize("orientation", ["both", "vertical"])
def test_engine_bitwise_matches_unfused(graph, orientation):
    pos, edges = graph
    want = unfused_reference(pos, edges, orientation)
    plan = plan_readability(pos, edges, radius=RADIUS, n_strips=N_STRIPS,
                            orientation=orientation)
    res = evaluate_planned(plan, pos, edges)
    assert int(res.node_occlusion) == want["node_occlusion"]
    assert int(res.edge_crossing) == want["edge_crossing"]
    assert int(res.crossing_count_for_angle) == want["crossing_count_for_angle"]
    assert int(res.overflow) == want["overflow"]
    # float metrics: bit-identical, not merely close...
    assert float(res.minimum_angle) == want["minimum_angle"]
    assert float(res.edge_length_variation) == want["edge_length_variation"]
    # ...except E_ca: the occupancy-tiered sweep sums the deviation over
    # strips in tier order (fullest strips first) where the flat
    # reference sums in natural strip order — same pairs, same per-pair
    # terms, float sum order differs by design.  Counts stay exact.
    np.testing.assert_allclose(float(res.edge_crossing_angle),
                               want["edge_crossing_angle"], rtol=1e-6)
    # enhanced occlusion is exact (paper Table 3: 0% error)
    assert int(res.node_occlusion) == int(count_occlusions_exact(pos, RADIUS))


def test_evaluate_layout_wrapper_matches_engine(graph):
    """The deprecated wrapper now routes through the cached config-keyed
    Evaluator (plan-cache + padded jitted engine), so it must reproduce
    the jitted engine under an equivalent flat plan: integer metrics
    bit-identical (the padding contract), floats to rounding
    (jit-vs-jit; the old eager-vs-eager comparison died with the
    per-call re-planning this shim no longer does)."""
    pos, edges = graph
    rep = evaluate_layout(pos, edges, radius=RADIUS, method="enhanced",
                          n_strips=N_STRIPS)
    plan = plan_readability(pos, edges, radius=RADIUS, n_strips=N_STRIPS,
                            tier_strips=False)
    want = evaluate_planned(plan, pos, edges)
    assert rep.node_occlusion == int(want.node_occlusion)
    assert rep.edge_crossing == int(want.edge_crossing)
    assert rep.crossing_count_for_angle == int(want.crossing_count_for_angle)
    assert rep.overflow == int(want.overflow) == 0
    np.testing.assert_allclose(rep.minimum_angle, float(want.minimum_angle),
                               rtol=1e-6)
    np.testing.assert_allclose(rep.edge_length_variation,
                               float(want.edge_length_variation), rtol=1e-6)
    np.testing.assert_allclose(rep.edge_crossing_angle,
                               float(want.edge_crossing_angle), rtol=1e-6)
    # the scores carry the natural sizes for the normalized view
    assert (rep.n_vertices, rep.n_edges) == (pos.shape[0], edges.shape[0])
    # second call on the same topology: served from the cached plan,
    # bit-identical
    again = evaluate_layout(pos, edges, radius=RADIUS, method="enhanced",
                            n_strips=N_STRIPS)
    assert again == rep


def test_batched_matches_looped(graph):
    pos, edges = graph
    rng = np.random.default_rng(3)
    batch = jnp.asarray(np.stack(
        [np.asarray(pos) + rng.normal(0, 1.0, size=pos.shape)
         for _ in range(4)]).astype(np.float32))
    plan = plan_readability(batch, edges, radius=RADIUS, n_strips=N_STRIPS)
    got = evaluate_layouts(plan, batch, edges)
    for i in range(batch.shape[0]):
        want = evaluate_planned(plan, batch[i], edges)
        assert int(got.node_occlusion[i]) == int(want.node_occlusion)
        assert int(got.edge_crossing[i]) == int(want.edge_crossing)
        # the natively batched sweep blocks (B * n_strips_t) rows where
        # the B=1 path blocks n_strips_t — same per-pair terms, float
        # reduction shape differs; integer metrics are exact above
        np.testing.assert_allclose(float(got.edge_crossing_angle[i]),
                                   float(want.edge_crossing_angle),
                                   rtol=1e-6)
        assert float(got.minimum_angle[i]) == float(want.minimum_angle)
        assert float(got.edge_length_variation[i]) == \
            float(want.edge_length_variation)
        assert int(got.overflow[i]) == int(want.overflow)


def test_jit_cache_hits_on_same_plan():
    pos, edges = make_layout("random")
    plan = plan_readability(pos, edges, radius=RADIUS, n_strips=N_STRIPS)
    jax.block_until_ready(evaluate_planned(plan, pos, edges))
    traces = engine.trace_count()
    # same plan, same shapes, new values -> cache hit, no retrace
    jax.block_until_ready(evaluate_planned(plan, pos + 1.0, edges))
    jax.block_until_ready(evaluate_planned(plan, pos * 0.5, edges))
    assert engine.trace_count() == traces
    # a different plan must retrace
    plan2 = plan_readability(pos, edges, radius=RADIUS, n_strips=32)
    jax.block_until_ready(evaluate_planned(plan2, pos, edges))
    assert engine.trace_count() == traces + 1


def test_fused_sweep_counts():
    """The fused path runs 2 strip builds + 2 reversal sweeps per trace
    where the unfused path runs 4 + 4 per evaluation."""
    pos, edges = make_layout("random")
    gridlib.reset_call_counts()
    count_crossings_enhanced(pos, edges, n_strips=N_STRIPS,
                             orientation="both")
    crossing_angle_enhanced(pos, edges, n_strips=N_STRIPS,
                            orientation="both")
    assert gridlib.CALL_COUNTS == {"strip_builds": 4, "reversal_sweeps": 4,
                                   "cell_builds": 0, "vertex_sorts": 0,
                                   "halo_exchanges": 0,
                                   "vertex_range_splits": 0}

    plan = plan_readability(pos, edges, radius=RADIUS, n_strips=48)
    gridlib.reset_call_counts()
    jax.block_until_ready(evaluate_planned(plan, pos, edges))
    assert gridlib.CALL_COUNTS == {"strip_builds": 2, "reversal_sweeps": 2,
                                   "cell_builds": 1, "vertex_sorts": 1,
                                   "halo_exchanges": 0,
                                   "vertex_range_splits": 0}


def test_use_kernels_parity():
    """Pallas (interpret mode off-TPU) reversal path: counts identical,
    deviation sum equal up to summation order."""
    pos, edges = make_layout("random")
    plan = plan_readability(pos, edges, radius=RADIUS, n_strips=N_STRIPS)
    ref = evaluate_planned(plan, pos, edges)
    got = evaluate_planned(plan, pos, edges, use_kernels=True)
    assert int(got.edge_crossing) == int(ref.edge_crossing)
    assert int(got.node_occlusion) == int(ref.node_occlusion)
    np.testing.assert_allclose(float(got.edge_crossing_angle),
                               float(ref.edge_crossing_angle), rtol=1e-6)


def test_padded_evaluation_exact(graph):
    """Bucket-padded evaluation (padded vertices parked + masked, padded
    edges masked) is exact: integer metrics bit-identical to the
    natural-size evaluation under the same plan, floats to rounding."""
    from repro.launch.session import PARK, pow2_bucket
    pos, edges = graph
    n_v, n_e = pos.shape[0], edges.shape[0]
    plan = plan_readability(pos, edges, radius=RADIUS, n_strips=N_STRIPS)
    nat = evaluate_planned(plan, pos, edges)
    vb = pow2_bucket(n_v + 1)     # n_v+1 forces a genuinely bigger bucket
    eb = pow2_bucket(n_e + 1)
    pos_p = np.full((vb, 2), PARK, np.float32)
    pos_p[:n_v] = np.asarray(pos)
    edges_p = np.zeros((eb, 2), np.int32)
    edges_p[:n_e] = np.asarray(edges)
    got = evaluate_planned(plan, jnp.asarray(pos_p), jnp.asarray(edges_p),
                           np.int32(n_v), np.int32(n_e))
    assert int(got.node_occlusion) == int(nat.node_occlusion)
    assert int(got.edge_crossing) == int(nat.edge_crossing)
    assert int(got.crossing_count_for_angle) == \
        int(nat.crossing_count_for_angle)
    assert int(got.overflow) == int(nat.overflow)
    np.testing.assert_allclose(float(got.minimum_angle),
                               float(nat.minimum_angle), rtol=1e-6)
    np.testing.assert_allclose(float(got.edge_length_variation),
                               float(nat.edge_length_variation), rtol=1e-6)
    np.testing.assert_allclose(float(got.edge_crossing_angle),
                               float(nat.edge_crossing_angle), rtol=1e-6)


def test_replan_on_overflow_roundtrip():
    """A capacity-starved plan reports overflow; replan_on_overflow grows
    it so the retry is overflow-free and exact."""
    import dataclasses
    pos, edges = make_layout("random")
    plan = plan_readability(pos, edges, radius=RADIUS, n_strips=N_STRIPS)
    want = evaluate_planned(plan, pos, edges)
    starved = dataclasses.replace(
        plan, strip_plans=tuple((128, 8) for _ in plan.strip_plans))
    res = evaluate_planned(starved, pos, edges)
    assert int(res.overflow) > 0
    grown = engine.replan_on_overflow(starved, pos, edges, res)
    assert grown.strip_plans != starved.strip_plans
    res2 = evaluate_planned(grown, pos, edges)
    assert int(res2.overflow) == 0
    assert int(res2.edge_crossing) == int(want.edge_crossing)
    # no overflow -> the plan comes back unchanged (same object)
    assert engine.replan_on_overflow(grown, pos, edges, res2) is grown


def test_exact_method_kernel_routing():
    """method='exact' with use_kernels=True runs the Pallas pairwise
    occlusion, CCW segment-crossing, and fused crossing-angle kernels
    (interpret mode on CPU): counts identical, floats to rounding."""
    pos, edges = make_layout("random")
    ref = evaluate_layout(pos, edges, radius=RADIUS, method="exact")
    got = evaluate_layout(pos, edges, radius=RADIUS, method="exact",
                          use_kernels=True)
    assert got.node_occlusion == ref.node_occlusion
    assert got.edge_crossing == ref.edge_crossing
    assert got.crossing_count_for_angle == ref.crossing_count_for_angle
    np.testing.assert_allclose(got.edge_crossing_angle,
                               ref.edge_crossing_angle, rtol=1e-5)


def test_metric_subsets():
    pos, edges = make_layout("random")
    plan = plan_readability(pos, edges, radius=RADIUS, n_strips=N_STRIPS,
                            metrics=("edge_crossing", "minimum_angle"))
    res = evaluate_planned(plan, pos, edges)
    assert res.node_occlusion is None
    assert res.edge_length_variation is None
    assert res.edge_crossing_angle is None
    want, _ = count_crossings_enhanced(pos, edges, n_strips=N_STRIPS)
    assert int(res.edge_crossing) == int(want)
    m_a, _ = minimum_angle(pos, edges)
    assert float(res.minimum_angle) == float(m_a)


def test_shared_formula_everywhere():
    """bucket_reversal_stats (unfused) goes through the engine's fused
    block: same count, same normalized deviation sum."""
    pos, edges = make_layout("cluster")
    from repro.core.crossing import bucket_reversal_stats
    segs = gridlib.build_strip_segments(pos, edges, 32, 16384)
    buckets = gridlib.bucketize_segments(segs, 32, cap=256)
    cnt_a, dev_a = bucket_reversal_stats(buckets, ideal_angle=DEFAULT_IDEAL)
    cnt_b, dev_b = engine.fused_reversal_stats(buckets, ideal=DEFAULT_IDEAL)
    assert int(cnt_a) == int(cnt_b)
    assert float(dev_a) == float(dev_b)
