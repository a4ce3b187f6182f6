"""Property tests for the batched ragged bucketing and the occupancy
tier planner.

Two invariants carry the whole batched/sharded engine:

* :func:`repro.core.grid.gather_ragged_buckets` is a *lossless
  group-by* whenever capacities cover occupancy: every element lands in
  its own bucket's slot range, in stable (original) order, as a
  contiguous run from the bucket's offset — and when capacities are
  starved it drops exactly the per-bucket excess (counted);
* :func:`repro.core.grid.plan_strip_tiers` always assigns every strip a
  tier capacity covering its exact occupancy (with the planner's
  headroom), using <= 3 descending pow2-boundary tiers that partition
  the strips.

Hypothesis drives the arbitrary-input versions; seeded deterministic
twins pin the same invariants on fixed inputs.
"""

import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import grid as gridlib


# ---------------------------------------------------------------------------
# shared checkers
# ---------------------------------------------------------------------------

def check_ragged_roundtrip(keys, n_buckets, off, caps, valid):
    """Assert the ragged gather invariants for one concrete case."""
    B, M = keys.shape
    # values = flat identity so slots reveal exactly which element they hold
    val = (np.arange(B * M, dtype=np.float32)).reshape(B, M)
    out_val, in_cap, counts, overflow = gridlib.gather_ragged_buckets(
        jnp.asarray(keys), n_buckets, off, caps, jnp.asarray(val),
        valid=jnp.asarray(valid))
    out_val = np.asarray(out_val)
    in_cap = np.asarray(in_cap)
    counts = np.asarray(counts)
    overflow = np.asarray(overflow)

    for b in range(B):
        expect_overflow = 0
        for k in range(n_buckets):
            members = val[b][(keys[b] == k) & valid[b]]
            # counts report true occupancy (pre-capacity-clip)
            assert counts[b, k] == members.size, (b, k)
            kept = members[:caps[k]]           # stable order, first cap
            expect_overflow += members.size - kept.size
            lo = off[k]
            got = out_val[b, lo:lo + caps[k]]
            ok = in_cap[b, lo:lo + caps[k]]
            # contiguous-run invariant: slot j of bucket k holds the
            # j-th member, valid exactly on the first len(kept) slots
            assert ok[:kept.size].all(), (b, k)
            assert not ok[kept.size:].any(), (b, k)
            np.testing.assert_array_equal(got[:kept.size], kept)
        assert overflow[b] == expect_overflow, b


def check_tiers_cover(occ):
    """Assert the tier-planner invariants for one occupancy vector."""
    occ = np.asarray(occ, np.int64)
    n = occ.size
    caps, counts, order = gridlib.plan_strip_tiers(occ)
    assert 1 <= len(caps) <= 3
    assert list(caps) == sorted(caps, reverse=True)
    assert len(caps) == len(counts)
    assert sum(counts) == n
    assert sorted(order) == list(range(n))
    # strip order[i] belongs to the tier owning position i
    tier_of_pos = np.repeat(np.arange(len(caps)), counts)
    assigned = np.empty(n, np.int64)
    assigned[np.asarray(order)] = np.asarray(caps)[tier_of_pos]
    # every tier cap covers its strips' exact occupancy (planner
    # headroom included, so strictly >= the raw occupancy)
    assert (assigned >= occ).all(), (assigned, occ)


def draw_ragged_case(rng, *, starve):
    n_buckets = int(rng.integers(1, 9))
    B = int(rng.integers(1, 4))
    M = int(rng.integers(1, 48))
    keys = rng.integers(0, n_buckets, (B, M)).astype(np.int32)
    valid = rng.random((B, M)) > 0.15
    occ = np.zeros(n_buckets, np.int64)
    for b in range(B):
        occ = np.maximum(occ, np.bincount(
            keys[b][valid[b]], minlength=n_buckets))
    slack = rng.integers(-3 if starve else 0, 4, n_buckets)
    caps = np.maximum(occ + slack, 0).astype(np.int64)
    # buckets tile [0, total) in a drawn permutation order (tiered strip
    # layouts permute buckets, so offsets need not be sorted by id)
    perm = rng.permutation(n_buckets)
    off = np.zeros(n_buckets, np.int64)
    off[perm] = np.concatenate([[0], np.cumsum(caps[perm])])[:-1]
    return keys, n_buckets, off, caps, valid


# ---------------------------------------------------------------------------
# deterministic twins (always run, hypothesis or not)
# ---------------------------------------------------------------------------

def test_gather_ragged_roundtrip_seeded():
    rng = np.random.default_rng(0)
    for case in range(8):
        check_ragged_roundtrip(*draw_ragged_case(rng, starve=False))


def test_gather_ragged_starved_overflow_seeded():
    rng = np.random.default_rng(1)
    for case in range(8):
        check_ragged_roundtrip(*draw_ragged_case(rng, starve=True))


def test_plan_strip_tiers_cover_seeded():
    rng = np.random.default_rng(2)
    for case in range(12):
        n = int(rng.integers(1, 200))
        kind = case % 3
        if kind == 0:
            occ = rng.integers(0, 50, n)
        elif kind == 1:          # power-law-ish skew (the target regime)
            occ = (rng.pareto(1.0, n) * 20).astype(np.int64)
        else:                    # uniform plateau (single tier expected)
            occ = np.full(n, int(rng.integers(0, 100)))
        check_tiers_cover(occ)


# ---------------------------------------------------------------------------
# hypothesis versions (arbitrary inputs; skip without hypothesis)
# ---------------------------------------------------------------------------

@given(st.data())
@settings(max_examples=30, deadline=None)
def test_gather_ragged_roundtrip_property(data):
    n_buckets = data.draw(st.integers(1, 8), label="n_buckets")
    B = data.draw(st.integers(1, 3), label="B")
    M = data.draw(st.integers(1, 32), label="M")
    keys = np.array(
        data.draw(st.lists(st.integers(0, n_buckets - 1),
                           min_size=B * M, max_size=B * M)),
        np.int32).reshape(B, M)
    valid = np.array(
        data.draw(st.lists(st.booleans(), min_size=B * M, max_size=B * M)),
        bool).reshape(B, M)
    occ = np.zeros(n_buckets, np.int64)
    for b in range(B):
        occ = np.maximum(occ, np.bincount(
            keys[b][valid[b]], minlength=n_buckets))
    slack = np.array(
        data.draw(st.lists(st.integers(-3, 3), min_size=n_buckets,
                           max_size=n_buckets)), np.int64)
    caps = np.maximum(occ + slack, 0)
    perm = np.array(
        data.draw(st.permutations(list(range(n_buckets)))), np.int64)
    off = np.zeros(n_buckets, np.int64)
    off[perm] = np.concatenate([[0], np.cumsum(caps[perm])])[:-1]
    check_ragged_roundtrip(keys, n_buckets, off, caps, valid)


@given(st.lists(st.integers(0, 5000), min_size=1, max_size=160))
@settings(max_examples=60, deadline=None)
def test_plan_strip_tiers_cover_property(occ):
    check_tiers_cover(occ)
