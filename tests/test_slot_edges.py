"""The strip builder's slot -> parent-edge map.

:func:`repro.core.grid.slot_edge_ids` replaces a binary search of every
segment slot among the inclusive segment offsets with a histogram of the
offsets and a cumsum.  Both are exact integer computations, so the map
must equal ``searchsorted(offsets, slot, side="right")`` element for
element, and the strip builder must give bit-identical segments with
either one, for one layout (B=1) and for a batch.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import grid as gridlib
from repro.graphs.layouts import lattice_graph


def _search(offsets, max_segments):
    """The binary search the count replaces: the reference."""
    slot = jnp.arange(max_segments, dtype=jnp.int32)
    if offsets.ndim == 1:
        return jnp.searchsorted(offsets, slot, side="right").astype(jnp.int32)
    return jnp.stack([jnp.searchsorted(o, slot, side="right")
                      for o in offsets]).astype(jnp.int32)


_N_SEG = {
    # runs of zero-length edges between spanning ones (equal offsets)
    "zero_length_runs": ([3, 0, 0, 2, 0, 1, 0, 0, 0, 4], 16),
    "leading_zeros": ([0, 0, 0, 5, 1, 2], 12),
    "total_past_max_segments": ([4, 0, 7, 3, 9, 0, 6], 10),
    "all_zero": ([0, 0, 0, 0, 0], 8),
    "one_slot": ([0, 2, 0, 1], 1),
    "one_slot_all_zero": ([0, 0, 0], 1),
    "exact_fit": ([2, 3, 0, 1], 6),
}


@pytest.mark.parametrize("batched", [False, True], ids=["E", "BxE"])
@pytest.mark.parametrize("case", sorted(_N_SEG))
def test_slot_edge_ids_equal_the_search(case, batched):
    n_seg, max_segments = _N_SEG[case]
    n_seg = np.asarray(n_seg, np.int32)
    if batched:
        # the case itself, its reverse, an empty row and a random row
        rng = np.random.default_rng(len(case))
        rand = rng.integers(0, 4, n_seg.size) * (rng.random(n_seg.size) < .5)
        n_seg = np.stack([n_seg, n_seg[::-1], np.zeros_like(n_seg),
                          rand.astype(np.int32)])
    offsets = jnp.asarray(np.cumsum(n_seg, axis=-1, dtype=np.int32))
    got = gridlib.slot_edge_ids(offsets, max_segments)
    want = _search(offsets, max_segments)
    assert got.dtype == jnp.int32
    assert got.shape == offsets.shape[:-1] + (max_segments,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _long_edge_layouts(batch):
    """Lattice plus many long random edges (a small-world graph: long
    edges span tens of strips), a few zero-length and masked edges."""
    pos, edges = lattice_graph(400, seed=3, frac_long=0.4)
    edges = np.concatenate([edges, [[5, 5], [17, 17]]]).astype(np.int32)
    rng = np.random.default_rng(4)
    layouts = pos[None] + rng.normal(0, 0.5, (batch,) + pos.shape).astype(
        np.float32)
    edge_valid = np.ones(edges.shape[0], bool)
    edge_valid[::37] = False
    return layouts, edges, edge_valid


@pytest.mark.parametrize("starved", [False, True], ids=["ample", "starved"])
@pytest.mark.parametrize("B", [1, 3], ids=["single", "batched"])
def test_strip_builders_unchanged_by_the_count(monkeypatch, B, starved):
    n_strips = 64
    layouts, edges, edge_valid = _long_edge_layouts(B)
    ev = jnp.asarray(edge_valid)
    for axis in (0, 1):
        build = lambda: gridlib.build_strip_segments_batched(  # noqa: E731
            jnp.asarray(layouts), jnp.asarray(edges), n_strips,
            max_segments, axis=axis, edge_valid=ev)
        first = gridlib.build_strip_segments_batched(
            jnp.asarray(layouts), jnp.asarray(edges), n_strips, 1,
            axis=axis, edge_valid=ev)
        total = int(np.max(np.asarray(first.overflow))) + 1
        assert total > 10 * n_strips        # long edges fill the strips
        max_segments = total // 2 if starved else total + 7
        got = build()
        with monkeypatch.context() as m:
            m.setattr(gridlib, "slot_edge_ids", _search)
            want = build()
        assert int(np.max(np.asarray(got.overflow))) == (
            int(np.max(np.asarray(want.overflow))))
        assert (int(np.max(np.asarray(got.overflow))) > 0) == starved
        for field in gridlib.StripSegments._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(got, field)),
                np.asarray(getattr(want, field)), err_msg=field)
