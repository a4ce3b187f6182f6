"""Arithmetic of a measured window: whole-call rates, percentiles and
due-time latencies.  Kept apart from the drivers so the tests can check
it on numbers built by hand."""

from __future__ import annotations

import math


def percentile(values, q):
    """Nearest-rank percentile (``q`` in 0..100).  ``inf`` stands for a
    request that failed or never came: it counts as missing every
    limit."""
    vals = sorted(values)
    if not vals:
        return None
    k = max(0, min(len(vals) - 1, math.ceil(q / 100.0 * len(vals)) - 1))
    return vals[k]


def whole_call_rate(calls, units_per_call):
    """Units per second over a window of whole calls.

    ``calls`` are ``(start, end)`` times of consecutive calls; the window
    runs from the first start to the last end, so a call that ran past
    the nominal length is counted whole, time and work alike."""
    if not calls:
        return None
    elapsed = calls[-1][1] - calls[0][0]
    return units_per_call * len(calls) / elapsed


def due_latencies(due, done):
    """Seconds from when each request was due to when its answer was
    back; ``None`` in ``done`` (failed or never answered) gives ``inf``."""
    return [math.inf if d is None else d - t for t, d in zip(due, done)]
