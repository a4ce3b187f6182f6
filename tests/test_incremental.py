"""Differential certification of the incremental re-evaluation path.

The contract (docs/incremental.md): for a registered dynamic layout,
``session.update(layout_id, moved_idx, new_pos)`` returns integer
metrics **bit-identical** to a from-scratch ``session.evaluate`` of the
moved layout (floats at the documented cross-backend RTOL), while
re-touching only the grid cells / strips whose membership changed.
Both halves are certified here:

* correctness — differential runs against the from-scratch engine on
  every parity-matrix layout family, including the degenerate regimes
  (collinear ties, duplicate positions), plus explicit cell-boundary-
  crossing and strip-membership-change fixtures;
* dirtiness — the work counters in :mod:`repro.core.grid` prove an
  incremental update performs **zero** cell builds, vertex sorts, strip
  builds, or reversal sweeps (the delta program is built from
  non-counting gather/scatter primitives by construction);
* the fallback ladder — a dirty set above ``update_dirty_threshold``,
  a changed strip domain (an extremal vertex moved), or a delta-path
  overflow falls back to a certified-correct full re-evaluation,
  counted in ``stats["delta_fallbacks"]``, never silently wrong.

Sessions here pin ``update_dirty_threshold=1.0`` so the delta path is
taken whenever it is *sound* — threshold tuning is a performance
policy, exercised separately by the explicit fallback tests.
"""

import numpy as np
import pytest

from repro.api import EvalConfig, Evaluator, InvalidInputError
from repro.core import grid as gridlib
from repro.launch.session import EvalSession
from test_parity_matrix import FAMILIES, make_family

RADIUS = 2.0
N_STRIPS = 32
RTOL = 1e-5

INT_FIELDS = ("node_occlusion", "edge_crossing", "crossing_count_for_angle")
FLOAT_FIELDS = ("minimum_angle", "edge_length_variation",
                "edge_crossing_angle")

IDLE_COUNTS = {"strip_builds": 0, "reversal_sweeps": 0, "cell_builds": 0,
               "vertex_sorts": 0, "halo_exchanges": 0,
               "vertex_range_splits": 0}


def make_session(**kw):
    kw.setdefault("update_dirty_threshold", 1.0)
    return EvalSession(EvalConfig(radius=RADIUS, n_strips=N_STRIPS), **kw)


def assert_matches(got, ref, ctx=""):
    for f in INT_FIELDS:
        assert int(getattr(got, f)) == int(getattr(ref, f)), (ctx, f)
    for f in FLOAT_FIELDS:
        np.testing.assert_allclose(getattr(got, f), getattr(ref, f),
                                   rtol=RTOL, err_msg=f"{ctx} {f}")


def interior_vertices(pos, k=3):
    """The k vertices nearest the bounding-box centre — moving them by a
    small displacement can never change the strip domain (lo/hi), so an
    update stays on the delta path (no extremal-vertex fallback)."""
    c = (pos.min(axis=0) + pos.max(axis=0)) / 2
    return np.argsort(((pos - c) ** 2).sum(axis=1))[:k]


# ---------------------------------------------------------------------------
# the differential certification matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", FAMILIES)
def test_incremental_matches_from_scratch(kind):
    """Chained updates on every layout family: each incremental score is
    bit-identical (ints) / RTOL-equal (floats) to evaluating the moved
    layout from scratch in the same session."""
    pos, edges = make_family(kind)
    rng = np.random.default_rng(11)
    sess = make_session()
    first = sess.register_layout("g", pos, edges)
    assert_matches(first, sess.evaluate(pos, edges), f"{kind} register")

    cur = np.array(pos, copy=True)
    movable = interior_vertices(pos, k=12)
    for step in range(3):
        moved = rng.choice(movable, size=3, replace=False)
        new_xy = cur[moved] + rng.normal(0, 1.0, (3, 2)).astype(np.float32)
        got = sess.update("g", moved, new_xy)
        cur[moved] = new_xy
        ref = sess.evaluate(cur, edges)
        assert int(got.overflow) == 0, (kind, step)
        assert_matches(got, ref, f"{kind} step {step}")
    # the matrix is vacuous if everything fell back to the full path
    assert sess.stats["updates"] == 3
    assert sess.stats["delta_hits"] >= 1, sess.stats


def test_cell_boundary_crossing_move():
    """A move of ~2 occlusion-grid cells provably changes the vertex's
    cell membership; the delta path re-buckets only the dirty cells and
    still matches from scratch bit-for-bit."""
    pos, edges = make_family("random")
    sess = make_session()
    sess.register_layout("g", pos, edges)
    lay = sess._layouts["g"]
    v = int(interior_vertices(pos, k=1)[0])
    cell_before = int(lay["vert_cell"][v])

    step = 2.0 * lay["plan_r"].grid_cell_size
    new_xy = pos[v] + np.float32([step, 0.0])
    got = sess.update("g", [v], [new_xy])
    assert got.flags and got.flags.get("incremental") is True

    cell_after = int(lay["vert_cell"][v])
    assert cell_after != cell_before          # membership really changed
    cur = np.array(pos, copy=True)
    cur[v] = new_xy
    assert_matches(got, sess.evaluate(cur, edges), "cell crossing")


def test_strip_membership_change_move():
    """A move of ~2 strip widths changes which strips the incident edges
    span; the per-edge span table is re-derived for the dirty strips
    only and the scores still match from scratch."""
    pos, edges = make_family("random")
    sess = make_session()
    sess.register_layout("g", pos, edges)
    lay = sess._layouts["g"]
    v = int(interior_vertices(pos, k=1)[0])
    incident = np.where((edges == v).any(axis=1))[0]
    assert incident.size > 0
    sf_axis0, _, _, lo, hi = lay["strips"][0]
    width = (hi - lo) / N_STRIPS
    spans_before = np.array(sf_axis0[incident], copy=True)

    new_xy = pos[v] + np.float32([2.5 * width, 0.0])
    got = sess.update("g", [v], [new_xy])
    assert got.flags and got.flags.get("incremental") is True

    spans_after = np.array(lay["strips"][0][0][incident], copy=True)
    assert (spans_after != spans_before).any()  # membership really changed
    cur = np.array(pos, copy=True)
    cur[v] = new_xy
    assert_matches(got, sess.evaluate(cur, edges), "strip crossing")


def test_duplicate_moved_indices_keep_last():
    """A request moving the same vertex twice applies the LAST position
    (the UI-drag semantics) — certified against from scratch."""
    pos, edges = make_family("random")
    sess = make_session()
    sess.register_layout("g", pos, edges)
    v = int(interior_vertices(pos, k=1)[0])
    a = pos[v] + np.float32([0.4, 0.1])
    b = pos[v] + np.float32([-0.7, 0.9])
    got = sess.update("g", [v, v], [a, b])
    cur = np.array(pos, copy=True)
    cur[v] = b
    assert_matches(got, sess.evaluate(cur, edges), "dup keep-last")


# ---------------------------------------------------------------------------
# the dirty-only certificate (work counters)
# ---------------------------------------------------------------------------

def test_update_builds_nothing():
    """An incremental update performs ZERO cell builds / vertex sorts /
    strip builds / reversal sweeps: the delta program re-sorts only the
    affected ragged-bucket rows via non-counting primitives, so the
    counters stay at their idle values even including trace time."""
    pos, edges = make_family("random")
    sess = make_session()
    sess.register_layout("g", pos, edges)
    v = int(interior_vertices(pos, k=1)[0])

    gridlib.reset_call_counts()
    got = sess.update("g", [v], [pos[v] + np.float32([0.5, -0.3])])
    assert gridlib.CALL_COUNTS == IDLE_COUNTS
    assert got.flags and got.flags.get("incremental") is True
    assert sess.stats["updates"] == 1
    assert sess.stats["delta_hits"] == 1
    assert sess.stats["delta_fallbacks"] == 0
    gridlib.reset_call_counts()


# ---------------------------------------------------------------------------
# the fallback ladder
# ---------------------------------------------------------------------------

def test_dirty_threshold_falls_back_to_full_eval():
    """``update_dirty_threshold=0`` rejects every dirty set: the update
    is served by a certified full re-evaluation (counted, correct) and
    the next update still works."""
    pos, edges = make_family("random")
    sess = make_session(update_dirty_threshold=0.0)
    sess.register_layout("g", pos, edges)
    v = int(interior_vertices(pos, k=1)[0])
    new_xy = pos[v] + np.float32([0.5, -0.3])
    got = sess.update("g", [v], [new_xy])
    assert not (got.flags or {}).get("incremental", False)
    assert sess.stats["delta_fallbacks"] == 1
    assert sess.stats["delta_hits"] == 0
    cur = np.array(pos, copy=True)
    cur[v] = new_xy
    assert_matches(got, sess.evaluate(cur, edges), "threshold fallback")
    # the fallback re-primed: the next small move is incremental again
    got2 = sess.update("g", [v], [new_xy + np.float32([0.2, 0.2])])
    cur[v] = new_xy + np.float32([0.2, 0.2])
    assert_matches(got2, sess.evaluate(cur, edges), "post-fallback")


def test_extremal_move_changes_domain_and_falls_back():
    """Moving the max-x vertex far outward changes the strip domain
    (lo/hi), which invalidates every resident strip -> full re-eval,
    still bit-identical to from scratch."""
    pos, edges = make_family("random")
    sess = make_session()
    sess.register_layout("g", pos, edges)
    v = int(np.argmax(pos[:, 0]))
    new_xy = pos[v] + np.float32([50.0, 0.0])
    got = sess.update("g", [v], [new_xy])
    assert sess.stats["delta_fallbacks"] == 1
    cur = np.array(pos, copy=True)
    cur[v] = new_xy
    assert_matches(got, sess.evaluate(cur, edges), "domain fallback")


# ---------------------------------------------------------------------------
# the request taxonomy
# ---------------------------------------------------------------------------

def test_update_error_taxonomy():
    pos, edges = make_family("random")
    sess = make_session()
    with pytest.raises(KeyError):
        sess.update("never-registered", [0], [[0.0, 0.0]])
    sess.register_layout("g", pos, edges)
    n = pos.shape[0]
    with pytest.raises(InvalidInputError):
        sess.update("g", [], [])                       # empty move set
    with pytest.raises(InvalidInputError):
        sess.update("g", [0, 1], [[0.0, 0.0]])         # length mismatch
    with pytest.raises(InvalidInputError):
        sess.update("g", [n + 3], [[0.0, 0.0]])        # index out of range
    with pytest.raises(InvalidInputError):
        sess.update("g", [0], [[np.nan, 0.0]])         # non-finite target
    # the session survives every rejection
    assert sess.update("g", [0], [pos[0] + 0.1]).ok


# ---------------------------------------------------------------------------
# the api front door
# ---------------------------------------------------------------------------

def test_evaluator_update_delegates_to_session():
    pos, edges = make_family("random")
    ev = Evaluator(EvalConfig(radius=RADIUS, n_strips=N_STRIPS),
                   update_dirty_threshold=1.0)
    ev.register_layout("g", pos, edges)
    v = int(interior_vertices(pos, k=1)[0])
    new_xy = pos[v] + np.float32([0.6, -0.2])
    got = ev.update("g", [v], [new_xy])
    assert got.flags and got.flags.get("incremental") is True
    cur = np.array(pos, copy=True)
    cur[v] = new_xy
    assert_matches(got, ev.evaluate(cur, edges), "api fused")


def test_evaluator_update_eager_backend_full_reeval():
    """The non-session backends track layouts host-side and document
    every update as a full re-evaluation — same scores, no flags."""
    pos, edges = make_family("random")
    ev = Evaluator(EvalConfig(radius=RADIUS, n_strips=N_STRIPS,
                              backend="eager"))
    ev.register_layout("g", pos, edges)
    v = int(interior_vertices(pos, k=1)[0])
    new_xy = pos[v] + np.float32([0.6, -0.2])
    got = ev.update("g", [v], [new_xy])
    cur = np.array(pos, copy=True)
    cur[v] = new_xy
    assert_matches(got, ev.evaluate(cur, edges), "api eager")
    with pytest.raises(KeyError):
        ev.update("other", [0], [[0.0, 0.0]])
    with pytest.raises(InvalidInputError):
        ev.update("g", [pos.shape[0] + 1], [[0.0, 0.0]])
