"""The program's own spans on the trace's clock.

While the profiler collects, the program records its spans in
``repro.tracing``'s buffer, stamped on the host's wall clock.  The trace
holds the harness's ``bench.*`` spans on the trace's clock.  In each
cell one harness span encloses exactly one root span of the program:

* ``select``: ``bench.evaluate_batch`` around ``evaluator.evaluate_batch``;
* ``drag``: ``bench.update`` around ``session.update``;
* ``serve``: ``bench.session_dispatch`` around ``session.evaluate_batch``.

:func:`align` pairs the ``K`` harness spans inside ``bench.window`` with
the ``K`` latest roots, in order.  Nesting bounds the offset between the
clocks to ``[max(bs - ps), min(be - pe)]`` over the pairs; an empty
bracket (a wrong pairing, or clocks that drift) gives ``None``.  The
buffer may still hold spans of an earlier traced run in the same
process, so every program span outside the aligned window is dropped,
``jax.compile`` and ``python.gc`` included.

A program without ``repro.tracing``, or a buffer that dropped spans,
gives ``None``: the readers then report nothing.
"""

from __future__ import annotations

ROOTS = {"select": ("bench.evaluate_batch", "evaluator.evaluate_batch"),
         "drag": ("bench.update", "session.update"),
         "serve": ("bench.session_dispatch", "session.evaluate_batch")}

ROOT_NAMES = frozenset(root for _, root in ROOTS.values())


def buffer():
    """The program's recorded spans, or ``None`` when the program has no
    recorder or its buffer overflowed."""
    try:
        from repro import tracing
    except ImportError:
        return None
    snap = tracing.snapshot()
    if snap["dropped"] > 0:
        return None
    return snap["spans"]


def bench_window(bench):
    """``(lo, hi)`` of the last ``bench.window`` span, or ``None``."""
    win = [(s, e) for n, s, e in bench if n == "bench.window"]
    return win[-1] if win else None


def align(spans, bench, harness, root):
    """The program spans inside the traced window, on the trace's clock.

    ``spans`` are ``repro.tracing.snapshot()["spans"]``; ``bench`` the
    trace's ``(name, start_s, end_s)`` harness spans; ``harness`` and
    ``root`` the pair of names that nest.  Returns a list of dicts with
    ``name``, ``start``, ``end`` (seconds), ``self`` (seconds not spent
    in a child), ``id``, ``parent``, ``call_id`` and ``attrs``; ``None``
    where the spans cannot be aligned."""
    win = bench_window(bench)
    if win is None:
        return None
    lo, hi = win
    outer = sorted((s, e) for n, s, e in bench
                   if n == harness and s >= lo and e <= hi)
    roots = sorted((sp for sp in spans
                    if sp["name"] == root and sp["parent"] is None),
                   key=lambda sp: sp["start_ns"])
    k = len(outer)
    if k == 0 or len(roots) < k:
        return None
    pairs = list(zip(outer, roots[-k:]))
    low = max(bs - r["start_ns"] * 1e-9 for (bs, _), r in pairs)
    high = min(be - r["end_ns"] * 1e-9 for (_, be), r in pairs)
    if low > high:
        return None
    off = 0.5 * (low + high)
    out = []
    for sp in spans:
        s, e = sp["start_ns"] * 1e-9 + off, sp["end_ns"] * 1e-9 + off
        if s >= lo and e <= hi:
            out.append({"name": sp["name"], "start": s, "end": e,
                        "self": e - s, "id": sp["id"],
                        "parent": sp["parent"], "call_id": sp["call_id"],
                        "attrs": sp["attrs"]})
    by_id = {sp["id"]: sp for sp in out}
    for sp in out:
        up = by_id.get(sp["parent"])
        if up is not None:
            up["self"] -= sp["end"] - sp["start"]
    return out


def window(rec, driver):
    """The aligned program spans of a traced run of ``driver``'s
    traffic; ``None`` where there are none to read."""
    tr = rec.get("trace")
    if tr is None or rec.get("driver") != driver:
        return None
    spans = buffer()
    if spans is None:
        return None
    return align(spans, tr["spans"], *ROOTS[driver])


def total_s(spans, names):
    """Seconds spent in the spans called one of ``names``."""
    return sum(sp["end"] - sp["start"] for sp in spans if sp["name"] in names)


def count(spans, name):
    return sum(sp["name"] == name for sp in spans)
