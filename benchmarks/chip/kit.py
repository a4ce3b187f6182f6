"""What the traffic drivers (``drivers/<driver>.py``) share.

A traffic file names its ``driver``; the driver is a class ``Driver``
with three steps:

* ``Driver(ctx)`` — set-up: inputs from ``ctx.seed``, the program's
  objects, warm-up calls.  Counted in ``setup_s``.  ``ctx`` holds the
  graph (``pos``, ``edges``, ``spacing``), the ``traffic`` file's
  parameters, the ``seed``, the ``eval_config`` and an open loop's
  ``rate`` override.
* ``window(seconds)`` — the measured window; returns the record that
  the metric readers (``metrics/*.py``) read, with ``driver``,
  ``attempted`` and ``failed``.
* ``answers(rng)`` — after the window: ``(label, positions, program
  scores)`` for the reference to check, drawn from ``rng`` among the
  answers the window produced.

Every call into the program is wrapped in a ``bench.*`` span so that the
trace reduction can attribute device idle time to it.
"""

from __future__ import annotations

import numpy as np

FIELDS = ("node_occlusion", "minimum_angle", "edge_length_variation",
          "edge_crossing", "edge_crossing_angle", "crossing_count_for_angle")


def span(name):
    import jax

    return jax.profiler.TraceAnnotation(name)


def jittered(rng, pos, n, sigma):
    """``n`` candidate layouts: ``pos`` moved by N(0, sigma) per
    coordinate, as a search loop proposes them."""
    noise = rng.standard_normal((n,) + pos.shape, dtype=np.float32)
    return pos[None] + np.float32(sigma) * noise


def host_scores(s, i=None):
    """One layout's metrics from host scores (``i`` indexes a batch)."""
    out = {}
    for f in FIELDS:
        v = getattr(s, f)
        out[f] = None if v is None else (np.asarray(v)[i] if i is not None
                                         else np.asarray(v)).item()
    ov = np.asarray(s.overflow)
    out["overflow"] = int(ov[i] if i is not None else ov)
    return out
