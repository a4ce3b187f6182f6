"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes, plus hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import grid as gridlib
from repro.core.crossing_angle import DEFAULT_IDEAL
from repro.kernels import ref
from repro.kernels.ops import (crossing_angle_op, crossing_count_op,
                               occlusion_count_op, strip_reversal_op)


def make_graph(seed, n_vertices, n_edges, scale=100.0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0, scale, size=(n_vertices, 2)).astype(dtype)
    edges = set()
    while len(edges) < n_edges:
        v, u = rng.integers(0, n_vertices, 2)
        if v != u:
            edges.add((min(v, u), max(v, u)))
    return jnp.asarray(pos), jnp.asarray(np.array(sorted(edges), np.int32))


@pytest.mark.parametrize("n,tile", [(64, 128), (200, 128), (512, 256),
                                    (700, 128), (1024, 512)])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_occlusion_kernel_shapes(n, tile, dtype):
    pos, _ = make_graph(n, n, min(n, 32), dtype=dtype)
    r = 3.0
    got = occlusion_count_op(pos, r, tile=tile)
    want = ref.occlusion_count_ref(pos[:, 0], pos[:, 1], r)
    assert int(got) == int(want)


@pytest.mark.parametrize("n_e,tile", [(100, 128), (256, 128), (500, 256)])
def test_crossing_kernel_shapes(n_e, tile):
    pos, edges = make_graph(n_e, max(20, n_e // 3), n_e)
    got = crossing_count_op(pos, edges, tile=tile)
    x1, y1 = pos[edges[:, 0], 0], pos[edges[:, 0], 1]
    x2, y2 = pos[edges[:, 1], 0], pos[edges[:, 1], 1]
    want = ref.crossing_count_ref(x1, y1, x2, y2, edges[:, 0], edges[:, 1])
    assert int(got) == int(want)


@pytest.mark.parametrize("n_e", [100, 300])
def test_crossing_angle_kernel(n_e):
    pos, edges = make_graph(7 * n_e, max(20, n_e // 3), n_e)
    count, dev = crossing_angle_op(pos, edges, ideal=float(DEFAULT_IDEAL),
                                   tile=128)
    x1, y1 = pos[edges[:, 0], 0], pos[edges[:, 0], 1]
    x2, y2 = pos[edges[:, 1], 0], pos[edges[:, 1], 1]
    wc, wd = ref.crossing_angle_ref(x1, y1, x2, y2, edges[:, 0], edges[:, 1],
                                    float(DEFAULT_IDEAL))
    assert int(count) == int(wc)
    np.testing.assert_allclose(float(dev), float(wd), rtol=2e-5)


def test_strip_reversal_kernel_vs_ref():
    pos, edges = make_graph(3, 120, 400)
    segs = gridlib.build_strip_segments_batched(
        jnp.asarray(pos)[None], jnp.asarray(edges), n_strips=32,
        max_segments=8192)
    buckets = gridlib.bucketize_segments(
        jax.tree_util.tree_map(lambda a: a[0], segs), 32, cap=256)
    count, dev = strip_reversal_op(buckets, ideal=float(DEFAULT_IDEAL),
                                   with_angle=True)
    want = 0
    for s in range(32):
        want += int(ref.reversal_count_ref(buckets.yl[s], buckets.yr[s],
                                           buckets.v[s], buckets.u[s],
                                           buckets.valid[s]))
    assert int(count) == want
    assert float(dev) >= 0.0


@settings(max_examples=20, deadline=None)
@given(st.integers(2, 150), st.integers(0, 2 ** 31 - 1),
       st.floats(0.1, 20.0))
def test_occlusion_kernel_property(n, seed, r):
    # Property: kernel count == oracle count for arbitrary point sets/radii.
    rng = np.random.default_rng(seed)
    pos = jnp.asarray(rng.uniform(0, 50, size=(n, 2)).astype(np.float32))
    got = occlusion_count_op(pos, r, tile=128)
    want = ref.occlusion_count_ref(pos[:, 0], pos[:, 1], r)
    assert int(got) == int(want)


@settings(max_examples=15, deadline=None)
@given(st.integers(4, 60), st.integers(0, 2 ** 31 - 1))
def test_crossing_kernel_property(n_v, seed):
    rng = np.random.default_rng(seed)
    pos = jnp.asarray(rng.uniform(0, 10, size=(n_v, 2)).astype(np.float32))
    n_e = min(n_v * (n_v - 1) // 2, 3 * n_v)
    edges = set()
    while len(edges) < n_e:
        v, u = rng.integers(0, n_v, 2)
        if v != u:
            edges.add((min(v, u), max(v, u)))
    edges = jnp.asarray(np.array(sorted(edges), np.int32))
    got = crossing_count_op(pos, edges, tile=128)
    x1, y1 = pos[edges[:, 0], 0], pos[edges[:, 0], 1]
    x2, y2 = pos[edges[:, 1], 0], pos[edges[:, 1], 1]
    want = ref.crossing_count_ref(x1, y1, x2, y2, edges[:, 0], edges[:, 1])
    assert int(got) == int(want)
