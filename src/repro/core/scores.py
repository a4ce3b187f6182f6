"""The one typed result: :class:`ReadabilityScores`.

Every front end — the raw fused engine, the serving session, the
server, the eager wrapper, the exact all-pairs path, and the
distributed drivers — returns this single pytree (it replaces the old
``EngineResult`` NamedTuple / ``ReadabilityReport`` dataclass /
server-dict trio).  Metric fields are ``None`` when the metric was not
in the config's subset.

The same type serves three altitudes:

* **device** — fresh out of a jitted evaluator: fields are device
  scalars (or ``(B,)`` arrays from the batched program), one
  ``jax.device_get`` fetches everything in one transfer;
* **host** — after :func:`scores_from_result` / :meth:`ReadabilityScores.host`:
  plain Python ints/floats (or numpy arrays for batches), with
  ``n_vertices``/``n_edges`` filled in so :meth:`ReadabilityScores.normalized`
  can turn raw counts into [0, 1] readability scores;
* **batched** — fields carry a leading ``B`` dim
  (:attr:`ReadabilityScores.batch_size` reports it);
  :meth:`ReadabilityScores.unbatch` splits into per-layout scores.

Being a NamedTuple it is automatically a pytree, so it round-trips
through ``jax.jit`` / ``vmap`` / ``device_get`` unchanged.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import numpy as np

from repro import tracing

# Metric-valued fields, in canonical order (same as engine.ALL_METRICS
# plus the paired crossing count for E_ca).
METRIC_FIELDS = ("node_occlusion", "minimum_angle", "edge_length_variation",
                 "edge_crossing", "edge_crossing_angle",
                 "crossing_count_for_angle")
_INT_FIELDS = ("node_occlusion", "edge_crossing", "crossing_count_for_angle")


class ReadabilityScores(NamedTuple):
    """Scores of one layout (scalars) or a batch of layouts ((B,) fields).

    ``overflow`` counts capacity drops (enhanced decompositions only; 0
    means the plan's capacities covered the layout).  ``n_vertices`` /
    ``n_edges`` are host-side sizes filled by the front-door paths;
    they let :meth:`normalized` relate counts to pair budgets.

    ``error`` / ``flags`` are the fault-tolerance fields (host-side
    only; device results leave them ``None``):

    * ``error`` — a :class:`repro.core.validate.ReadabilityError` when
      this slot of a quarantining batch call failed (metric fields are
      then ``None``); :attr:`ok` is the quick check and
      :meth:`raise_for_error` re-raises it.
    * ``flags`` — sanitization/saturation record copied from
      :func:`repro.core.validate.validate_request` (e.g.
      ``{"sanitized": True, "dropped_edges": 2}`` or
      ``{"saturated": True}`` when capacity stayed overflowed in
      sanitize mode).  ``None`` means the request passed untouched.
    """

    node_occlusion: Any = None
    minimum_angle: Any = None
    edge_length_variation: Any = None
    edge_crossing: Any = None
    edge_crossing_angle: Any = None
    crossing_count_for_angle: Any = None
    overflow: Any = None
    n_vertices: Any = None
    n_edges: Any = None
    error: Any = None
    flags: Any = None

    # -- views -------------------------------------------------------------

    def asdict(self) -> dict:
        return dict(self._asdict())

    @property
    def ok(self) -> bool:
        """True when this slot evaluated (no quarantined error)."""
        return self.error is None

    @property
    def saturated(self) -> bool:
        """True when capacities stayed overflowed after the bounded
        replan retries (sanitize mode; counts may be under-reported)."""
        return bool(self.flags) and bool(self.flags.get("saturated"))

    @property
    def shed(self) -> bool:
        """True when admission control shed this request (the bounded
        queue was full / over budget — ``error`` is the typed
        :class:`~repro.core.validate.OverloadedError`)."""
        from repro.core.validate import OverloadedError
        return isinstance(self.error, OverloadedError)

    @property
    def expired(self) -> bool:
        """True when the request's deadline passed before its dispatch
        completed (``error`` is
        :class:`~repro.core.validate.DeadlineExceededError`)."""
        from repro.core.validate import DeadlineExceededError
        return isinstance(self.error, DeadlineExceededError)

    @property
    def cancelled(self) -> bool:
        """True when the request's cancel token fired before dispatch
        (``error`` is :class:`~repro.core.validate.CancelledError`)."""
        from repro.core.validate import CancelledError
        return isinstance(self.error, CancelledError)

    def raise_for_error(self) -> "ReadabilityScores":
        """Raise the quarantined error, if any; else return self."""
        if self.error is not None:
            raise self.error
        return self

    @property
    def batch_size(self):
        """Leading batch dim of the metric fields, or None for scalars."""
        for name in METRIC_FIELDS + ("overflow",):
            v = getattr(self, name)
            if v is not None and getattr(v, "ndim", 0) >= 1:
                return int(v.shape[0])
        return None

    def host(self, n_vertices=None, n_edges=None) -> "ReadabilityScores":
        """Fetch to host (ONE transfer) and cast to Python scalars."""
        return scores_from_result(self,
                                  self.n_vertices if n_vertices is None
                                  else n_vertices,
                                  self.n_edges if n_edges is None
                                  else n_edges)

    def unbatch(self):
        """Split a batched result into per-layout host scores."""
        return scores_from_batch(self, self.n_vertices, self.n_edges)

    def normalized(self) -> "ReadabilityScores":
        """[0, 1] readability view: higher is always better.

        Counts are normalized against their pair budgets (``N_c``
        against C(V, 2), ``E_c`` against C(E, 2) — the Dunne &
        Shneiderman-style readability convention), ``M_l`` is squashed
        by ``1 / (1 + M_l)``; ``M_a`` and ``E_ca`` are already in
        [0, 1].  Batch-aware (elementwise on ``(B,)`` fields).  Needs
        ``n_vertices`` / ``n_edges`` when the respective counts are
        present — front-door results carry them.
        """
        got = jax.device_get(self)
        out = {}
        if got.node_occlusion is not None:
            if got.n_vertices is None:
                raise ValueError("normalized() needs n_vertices to scale "
                                 "node_occlusion; evaluate through "
                                 "repro.api so the sizes are recorded")
            v = int(got.n_vertices)
            pairs = max(v * (v - 1) // 2, 1)
            out["node_occlusion"] = _unit(
                1.0 - np.asarray(got.node_occlusion, np.float64) / pairs)
        if got.edge_crossing is not None:
            if got.n_edges is None:
                raise ValueError("normalized() needs n_edges to scale "
                                 "edge_crossing; evaluate through "
                                 "repro.api so the sizes are recorded")
            e = int(got.n_edges)
            pairs = max(e * (e - 1) // 2, 1)
            out["edge_crossing"] = _unit(
                1.0 - np.asarray(got.edge_crossing, np.float64) / pairs)
        if got.edge_length_variation is not None:
            m_l = np.asarray(got.edge_length_variation, np.float64)
            out["edge_length_variation"] = _unit(1.0 / (1.0 + m_l))
        for name in ("minimum_angle", "edge_crossing_angle"):
            v = getattr(got, name)
            if v is not None:
                out[name] = _unit(np.asarray(v, np.float64))
        return ReadabilityScores(
            crossing_count_for_angle=got.crossing_count_for_angle,
            overflow=got.overflow, n_vertices=got.n_vertices,
            n_edges=got.n_edges, error=got.error, flags=got.flags, **out)


def _unit(x):
    x = np.clip(x, 0.0, 1.0)
    return float(x) if np.ndim(x) == 0 else x


# ---------------------------------------------------------------------------
# host conversions (each fetches every field in ONE device transfer)
# ---------------------------------------------------------------------------

def _cast(v, to):
    return None if v is None else to(v)


def scores_from_result(res, n_vertices=None, n_edges=None
                       ) -> ReadabilityScores:
    """One (unbatched) engine result -> host scores (Python scalars)."""
    with tracing.span("scores.fetch"):
        res = jax.device_get(res)
    return ReadabilityScores(
        node_occlusion=_cast(res.node_occlusion, int),
        minimum_angle=_cast(res.minimum_angle, float),
        edge_length_variation=_cast(res.edge_length_variation, float),
        edge_crossing=_cast(res.edge_crossing, int),
        edge_crossing_angle=_cast(res.edge_crossing_angle, float),
        crossing_count_for_angle=_cast(res.crossing_count_for_angle, int),
        overflow=0 if res.overflow is None else int(res.overflow),
        n_vertices=_cast(n_vertices, int), n_edges=_cast(n_edges, int),
        error=getattr(res, "error", None), flags=getattr(res, "flags", None))


def error_scores(error, n_vertices=None, n_edges=None) -> ReadabilityScores:
    """The per-slot result of a quarantined request: every metric
    ``None``, the typed error attached (``scores.ok`` is False,
    ``scores.raise_for_error()`` re-raises)."""
    return ReadabilityScores(error=error, n_vertices=_cast(n_vertices, int),
                             n_edges=_cast(n_edges, int))


def scores_from_batch(res, n_vertices=None, n_edges=None):
    """Split a batched result (leading B dim on every field) into a list
    of B host :class:`ReadabilityScores`; one transfer."""
    with tracing.span("scores.fetch"):
        res = jax.device_get(res)
    batch = ReadabilityScores(*res).batch_size
    if batch is None:
        raise ValueError("scores_from_batch needs a batched result; "
                         "use scores_from_result for scalars")

    def pick(field, i, cast):
        return None if field is None else cast(field[i])

    return [ReadabilityScores(
        node_occlusion=pick(res.node_occlusion, i, int),
        minimum_angle=pick(res.minimum_angle, i, float),
        edge_length_variation=pick(res.edge_length_variation, i, float),
        edge_crossing=pick(res.edge_crossing, i, int),
        edge_crossing_angle=pick(res.edge_crossing_angle, i, float),
        crossing_count_for_angle=pick(res.crossing_count_for_angle, i, int),
        overflow=0 if res.overflow is None else int(res.overflow[i]),
        n_vertices=_cast(n_vertices, int), n_edges=_cast(n_edges, int))
        for i in range(batch)]
