"""Request validation: the typed error taxonomy, strict/sanitize/off
semantics, the out-of-range-edge regression (JAX gathers used to clamp
bad indices into wrong-but-finite crossing counts), degenerate-graph
normalization, and the sanitize properties (idempotence; already-valid
inputs pass through byte-identically, so their scores are trivially
bit-identical)."""

import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st
from repro.core import engine
from repro.core.validate import (VALIDATION_MODES, BackendUnavailableError,
                                 CapacityError, InvalidInputError,
                                 ReadabilityError, validate_batch,
                                 validate_request)


def graph(n_v=24, n_e=48, seed=0):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0, 50, (n_v, 2)).astype(np.float32)
    edges = set()
    while len(edges) < n_e:
        v, u = rng.integers(0, n_v, 2)
        if v != u:
            edges.add((min(v, u), max(v, u)))
    return pos, np.array(sorted(edges), np.int32)


# ---------------------------------------------------------------------------
# the error taxonomy
# ---------------------------------------------------------------------------

def test_taxonomy_hierarchy():
    for cls in (InvalidInputError, CapacityError, BackendUnavailableError):
        assert issubclass(cls, ReadabilityError)
    assert issubclass(ReadabilityError, Exception)


def test_errors_carry_request_index():
    err = InvalidInputError("bad", request_index=7, reason="bad_shape")
    assert err.request_index == 7 and err.reason == "bad_shape"
    assert str(err).startswith("[request 7] ")
    assert "[request" not in str(InvalidInputError("bad"))
    assert CapacityError("full", overflow=3).overflow == 3


# ---------------------------------------------------------------------------
# strict mode: reject, with a machine-checkable reason
# ---------------------------------------------------------------------------

def test_strict_rejects_non_finite_positions():
    pos, edges = graph()
    for poison in (np.nan, np.inf, -np.inf):
        bad = pos.copy()
        bad[3, 1] = poison
        with pytest.raises(InvalidInputError) as ei:
            validate_request(bad, edges, mode="strict", index=2)
        assert ei.value.reason == "non_finite_positions"
        assert ei.value.request_index == 2


def test_strict_rejects_out_of_range_edges():
    pos, edges = graph()
    for bad_edge in ((0, pos.shape[0]), (-1, 3), (10_000, 2)):
        bad = np.vstack([edges, [bad_edge]]).astype(np.int32)
        with pytest.raises(InvalidInputError) as ei:
            validate_request(pos, bad, mode="strict")
        assert ei.value.reason == "edge_index_range"


def test_strict_rejects_garbage_shapes_and_dtypes():
    pos, edges = graph()
    with pytest.raises(InvalidInputError) as ei:
        validate_request(pos[:, :1], edges, mode="strict")
    assert ei.value.reason == "bad_shape"
    with pytest.raises(InvalidInputError) as ei:
        validate_request(pos, edges.reshape(-1), mode="strict")
    assert ei.value.reason == "bad_shape"
    with pytest.raises(InvalidInputError) as ei:
        validate_request(pos, edges.astype(np.float32) + 0.5, mode="strict")
    assert ei.value.reason == "bad_dtype"
    # integral-valued float edges are coercible, not garbage
    v = validate_request(pos, edges.astype(np.float64), mode="strict")
    assert v.edges.dtype == np.int32 and np.array_equal(v.edges, edges)


def test_mode_must_be_known():
    pos, edges = graph()
    with pytest.raises(ValueError):
        validate_request(pos, edges, mode="paranoid")
    assert set(VALIDATION_MODES) == {"strict", "sanitize", "off"}


# ---------------------------------------------------------------------------
# sanitize mode: repair + record
# ---------------------------------------------------------------------------

def test_sanitize_drops_poisoned_vertices_and_remaps():
    pos, edges = graph()
    bad = pos.copy()
    bad[5] = np.nan
    v = validate_request(bad, edges, mode="sanitize")
    assert v.flags["dropped_vertices"] == 1
    assert v.flags["sanitized"] is True
    assert v.pos.shape[0] == pos.shape[0] - 1
    assert np.isfinite(v.pos).all()
    # survivors keep their coordinates, edges reference the remapped ids
    keep = np.ones(pos.shape[0], bool)
    keep[5] = False
    assert np.array_equal(v.pos, pos[keep])
    assert v.edges.min() >= 0 and v.edges.max() < v.pos.shape[0]
    n_incident = int(((edges == 5).any(axis=1)).sum())
    assert v.flags.get("dropped_edges", 0) == n_incident
    assert v.edges.shape[0] == edges.shape[0] - n_incident


def test_sanitize_drops_out_of_range_edges():
    pos, edges = graph()
    bad = np.vstack([edges, [[0, 999]], [[-3, 1]]]).astype(np.int32)
    v = validate_request(pos, bad, mode="sanitize")
    assert v.flags["dropped_edges"] == 2
    assert np.array_equal(v.edges, edges)


def test_self_loops_normalized_in_both_checked_modes():
    pos, edges = graph()
    looped = np.vstack([edges, [[4, 4]]]).astype(np.int32)
    for mode in ("strict", "sanitize"):
        v = validate_request(pos, looped, mode=mode)
        assert v.flags["self_loops"] == 1
        assert np.array_equal(v.edges, edges)


def test_off_mode_coerces_only():
    pos, edges = graph()
    bad = pos.copy()
    bad[0] = np.inf
    v = validate_request(bad, np.vstack([edges, [[0, 999]]]), mode="off")
    assert v.flags is None
    assert not np.isfinite(v.pos).all()
    assert v.edges.max() == 999


def test_empty_and_degenerate_graphs_pass_validation():
    for pos, edges in (
        (np.zeros((0, 2), np.float32), np.zeros((0, 2), np.int32)),
        (np.zeros((1, 2), np.float32), np.zeros((0, 2), np.int32)),
        (np.ones((4, 2), np.float32), np.zeros((0, 2), np.int32)),
        (np.ones((4, 2), np.float32), []),
    ):
        for mode in ("strict", "sanitize"):
            v = validate_request(pos, edges, mode=mode)
            assert v.flags is None
            assert v.edges.shape == (0, 2)


# ---------------------------------------------------------------------------
# batch validation
# ---------------------------------------------------------------------------

def test_validate_batch_strict_locates_poisoned_layout():
    pos, edges = graph()
    batch = np.stack([pos, pos + 1, pos + 2])
    batch[1, 0, 0] = np.nan
    for mode in ("strict", "sanitize"):
        # shared-shape batches cannot drop one member: both modes raise,
        # carrying the offending layout's index
        with pytest.raises(InvalidInputError) as ei:
            validate_batch(batch, edges, mode=mode)
        assert ei.value.request_index == 1
        assert ei.value.reason == "non_finite_positions"


def test_validate_batch_repairs_shared_topology_once():
    pos, edges = graph()
    batch = np.stack([pos, pos + 1])
    bad = np.vstack([edges, [[0, 999]], [[2, 2]]]).astype(np.int32)
    with pytest.raises(InvalidInputError):
        validate_batch(batch, bad, mode="strict")
    b2, e2, flags = validate_batch(batch, bad, mode="sanitize")
    assert np.array_equal(e2, edges)
    assert flags["dropped_edges"] == 1 and flags["self_loops"] == 1
    assert np.array_equal(b2, batch)


# ---------------------------------------------------------------------------
# the OOR regression: silent gather clamping produced wrong-but-finite
# crossing counts; the fault layer rejects (strict) or drops-and-flags
# (sanitize) instead
# ---------------------------------------------------------------------------

def test_out_of_range_edge_regression():
    from repro.api import EvalConfig, Evaluator

    pos, edges = graph(n_v=30, n_e=60, seed=3)
    n_v = pos.shape[0]
    oor = edges.copy()
    oor[7] = (int(edges[7, 0]), n_v + 500)      # one endpoint off the end

    # THE OLD PATH (pre-validation engine, reachable today only with
    # validation="off" and a cached plan): the traced gather CLAMPS the
    # bad index to V-1, scoring a phantom edge — finite, plausible, and
    # wrong.  Pin that behavior down as the motivation.
    plan = engine.plan_readability(pos, edges, radius=2.0, n_strips=32)
    clamped = oor.copy()
    clamped[7] = (oor[7, 0], n_v - 1)
    res_oor = engine.evaluate_once(plan, pos, oor)
    res_clamped = engine.evaluate_once(plan, pos, clamped)
    assert int(res_oor.edge_crossing) == int(res_clamped.edge_crossing)

    # the honest count: that edge dropped, not clamped
    dropped = np.delete(oor, 7, axis=0)
    res_dropped = engine.evaluate_once(
        engine.plan_readability(pos, dropped, radius=2.0, n_strips=32),
        pos, dropped)
    assert int(res_oor.edge_crossing) != int(res_dropped.edge_crossing), \
        "pick a seed where the phantom edge changes the count"

    # the fault layer: strict rejects with the typed error...
    strict = Evaluator(EvalConfig(radius=2.0, n_strips=32, backend="eager"))
    with pytest.raises(InvalidInputError) as ei:
        strict.evaluate(pos, oor)
    assert ei.value.reason == "edge_index_range"

    # ...sanitize drops the edge, flags the repair, and matches the
    # honest count exactly
    sane = Evaluator(EvalConfig(radius=2.0, n_strips=32, backend="eager",
                                validation="sanitize"))
    s = sane.evaluate(pos, oor)
    assert s.flags["dropped_edges"] == 1
    assert s.edge_crossing == int(res_dropped.edge_crossing)


# ---------------------------------------------------------------------------
# sanitize properties (hypothesis; skipped when it is not installed)
# ---------------------------------------------------------------------------

def _messy_request(draw):
    n_v = draw(st.integers(min_value=1, max_value=20))
    coords = st.floats(min_value=-100, max_value=100, width=32,
                       allow_nan=True, allow_infinity=True)
    pos = np.array(draw(st.lists(st.tuples(coords, coords),
                                 min_size=n_v, max_size=n_v)), np.float32)
    n_e = draw(st.integers(min_value=0, max_value=30))
    idx = st.integers(min_value=-3, max_value=n_v + 3)
    edges = np.array(draw(st.lists(st.tuples(idx, idx),
                                   min_size=n_e, max_size=n_e)),
                     np.int64).reshape(n_e, 2)
    return pos, edges


@settings(max_examples=60, deadline=None)
@given(st.data())
def test_sanitize_is_idempotent(data):
    pos, edges = _messy_request(data.draw)
    v1 = validate_request(pos, edges, mode="sanitize")
    v2 = validate_request(v1.pos, v1.edges, mode="sanitize")
    # a sanitized request is already valid: the second pass changes
    # nothing and records nothing
    assert v2.flags is None
    assert np.array_equal(v1.pos, v2.pos)
    assert np.array_equal(v1.edges, v2.edges)
    # and it validates strictly
    validate_request(v1.pos, v1.edges, mode="strict")


@settings(max_examples=60, deadline=None)
@given(st.data())
def test_sanitize_passes_valid_inputs_through_byte_identically(data):
    pos, edges = _messy_request(data.draw)
    v1 = validate_request(pos, edges, mode="sanitize")
    # feed the (now valid) request back in: both checked modes must
    # return the SAME bytes, so downstream scores are bit-identical to
    # an unvalidated evaluation by construction
    for mode in ("strict", "sanitize"):
        v = validate_request(v1.pos, v1.edges, mode=mode)
        assert v.flags is None
        assert v.pos.tobytes() == v1.pos.tobytes()
        assert v.edges.tobytes() == v1.edges.tobytes()


@settings(max_examples=40, deadline=None)
@given(st.data())
def test_strict_errors_carry_the_offending_index(data):
    pos, edges = _messy_request(data.draw)
    index = data.draw(st.integers(min_value=0, max_value=31))
    try:
        validate_request(pos, edges, mode="strict", index=index)
    except InvalidInputError as err:
        assert err.request_index == index
        assert str(err).startswith(f"[request {index}] ")
        assert err.reason in ("non_finite_positions", "edge_index_range",
                              "bad_shape", "bad_dtype")
