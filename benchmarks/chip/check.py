"""The comparison that decides ``correct``.

Each sampled answer of the window is compared with the plain reference
(:mod:`reference`) on the same positions.  The numbers compared are the
worst, over the sample, of:

* ``n_c_gap``, ``e_c_gap``, ``cross_gap`` — gaps of N_c, E_c and the
  E_ca crossing count relative to the reference's count, or to
  ``COUNT_FLOOR`` where the count is smaller: a pair that sits on a tie
  can flip between float32 and float64 and move a count by one, which
  must not read as a large share of a small count;
* ``m_l_gap`` — the relative gap of M_l;
* ``e_ca_gap``, ``m_a_gap`` — absolute gaps of E_ca and M_a (both lie
  in [0, 1]);
* ``overflow`` — capacity drops reported by the program;
* ``failed`` — answers of the window that came back with an error or
  never came.

An answer that is missing reads ``inf`` on every gap.  Each number has
its limit in ``limits/<workload>.json``; the run is correct when every
number is at or under its limit.
"""

from __future__ import annotations

import math

import reference

COUNT_FLOOR = 100.0
RELATIVE = {"n_c_gap": "node_occlusion", "e_c_gap": "edge_crossing",
            "cross_gap": "crossing_count_for_angle",
            "m_l_gap": "edge_length_variation"}
ABSOLUTE = {"e_ca_gap": "edge_crossing_angle", "m_a_gap": "minimum_angle"}
NUMBERS = tuple(RELATIVE) + tuple(ABSOLUTE) + ("overflow", "failed")


def gaps(prog: dict, ref: dict) -> dict:
    """The compared numbers of one answer."""
    if prog is None:
        return {k: math.inf for k in NUMBERS if k != "failed"}
    out = {}
    for k, f in RELATIVE.items():
        r = float(ref[f])
        floor = 1e-12 if k == "m_l_gap" else COUNT_FLOOR
        out[k] = abs(float(prog[f]) - r) / max(abs(r), floor)
    for k, f in ABSOLUTE.items():
        out[k] = abs(float(prog[f]) - float(ref[f]))
    out["overflow"] = float(prog["overflow"])
    return out


def compare(answers, edges, geometry, failed: int, log=None):
    """Worst numbers over ``answers`` (``(label, pos, prog)`` triples)."""
    worst = {k: 0.0 for k in NUMBERS}
    worst["failed"] = float(failed)
    for label, pos, prog in answers:
        ref = reference.scores(pos, edges, geometry)
        g = gaps(prog, ref)
        if log is not None:
            log(f"check {label}: program {prog} reference {ref}")
        for k, v in g.items():
            worst[k] = max(worst[k], v)
    return worst


def verdict(worst: dict, limits: dict):
    """``(correct, {name: {"value", "limit"}})``; a number without a
    limit is an error, not a pass."""
    table, ok = {}, True
    for k in NUMBERS:
        if k not in limits:
            raise KeyError(f"no limit for {k!r}")
        table[k] = {"value": worst[k], "limit": float(limits[k])}
        ok = ok and worst[k] <= float(limits[k])
    return ok, table
