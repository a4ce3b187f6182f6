"""Reduction of a profiler trace to device busy time, idle gaps and op
totals.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
:func:`load` reads it with ``jax.profiler.ProfileData`` into plain
``(name, start_s, end_s)`` tuples: the device's operations (each TPU
plane's ``XLA Ops`` line) and the benchmark's own host spans (the
``bench.*`` ``TraceAnnotation`` events).  Everything after :func:`load`
works on those lists, so the tests feed it events built by hand.
"""

from __future__ import annotations

import bisect
import glob
import heapq
import os
from collections import defaultdict

SPAN_PREFIX = "bench."


def union(intervals):
    """Merge ``(start, end)`` intervals; returns the sorted disjoint list."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_seconds(ops, lo, hi):
    """Seconds of ``[lo, hi]`` in which some device operation ran."""
    return sum(e - s for s, e in clip(union((s, e) for _, s, e in ops),
                                      lo, hi))


def idle_gaps(ops, lo, hi):
    """The idle intervals of ``[lo, hi]``: the complement of the busy
    union."""
    gaps, t = [], lo
    for s, e in clip(union((s, e) for _, s, e in ops), lo, hi):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def timeline(spans, lo, hi):
    """``[lo, hi]`` cut into ``(start, end, label)`` pieces, each labelled
    by the innermost span open over it (the latest to start; of two that
    start together, the first to end), or
    ``outside_spans``.  One sweep over the sorted span boundaries."""
    events = []
    for i, (_, s, e) in enumerate(spans):
        s, e = max(s, lo), min(e, hi)
        if e > s:
            events += [(s, 1, i), (e, 0, i)]
    events.sort()
    heap, active, out, t = [], set(), [], lo

    def label():
        while heap and heap[0][2] not in active:
            heapq.heappop(heap)
        return spans[heap[0][2]][0] if heap else "outside_spans"

    for time, starts, i in events:
        if time > t:
            out.append((t, time, label()))
            t = time
        if starts:
            active.add(i)
            # innermost: latest start, then earliest end
            heapq.heappush(heap, (-spans[i][1], spans[i][2], i))
        else:
            active.discard(i)
    if hi > t:
        out.append((t, hi, label()))
    return out


def _overlap(a, b):
    """Pairs of overlapping pieces of two sorted, disjoint interval lists,
    as ``(index into b, overlap seconds)``."""
    i = j = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            yield j, e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1


def idle_by_span(ops, spans, lo, hi):
    """Idle seconds of ``[lo, hi]`` split by the span open over each part:
    ``{span name: seconds}``.  A gap is cut at every span boundary inside
    it, so a gap that outlasts one span is shared out exactly."""
    out = defaultdict(float)
    tl = timeline(spans, lo, hi)
    for j, sec in _overlap(idle_gaps(ops, lo, hi), tl):
        out[tl[j][2]] += sec
    return dict(out)


def idle_within(ops, spans, name, lo, hi):
    """Idle device seconds inside the spans called ``name``."""
    mine = clip(union((s, e) for n, s, e in spans if n == name), lo, hi)
    return sum(sec for _, sec in _overlap(idle_gaps(ops, lo, hi), mine))


def longest_gaps(ops, spans, lo, hi, n=10):
    """The ``n`` longest idle gaps as ``[label, seconds]``, each labelled
    by the piece of :func:`timeline` that holds its middle."""
    tl = timeline(spans, lo, hi)
    starts = [s for s, _, _ in tl]
    gaps = sorted(idle_gaps(ops, lo, hi), key=lambda g: g[0] - g[1])[:n]
    return [[tl[bisect.bisect_right(starts, 0.5 * (s + e)) - 1][2], e - s]
            for s, e in gaps]


def top_ops(ops, lo, hi, n=10):
    """The ``n`` device operations that took the most time in the
    window, as ``[name, seconds]`` (durations summed over calls)."""
    tot = defaultdict(float)
    for name, s, e in ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            tot[name] += e - s
    return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def op_name(hlo: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``: the
    instruction's name without its HLO text."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _xplane(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(trace_dir, *, allow_host_device=False):
    """``(ops_per_device, spans)`` from the trace under ``trace_dir``.

    ``ops_per_device`` maps each TPU plane's name to its ``XLA Ops``
    events; ``spans`` are the ``bench.*`` host spans.  Times are seconds
    on the trace's own clock.  ``allow_host_device`` (CPU rehearsals
    only) takes the CPU client's executor threads as the device."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(_xplane(trace_dir))
    ops, spans = {}, []
    host_ops = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    evs.extend((op_name(ev.name), ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9)
                               for ev in line.events)
            ops[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
                    elif (allow_host_device
                          and line.name.startswith("tf_XLAPjRtCpuClient")
                          and ev.duration_ns > 0
                          and not ev.name.startswith("end:")
                          and "Threadpool" not in ev.name):
                        host_ops.append((ev.name, ev.start_ns * 1e-9,
                                         (ev.start_ns + ev.duration_ns)
                                         * 1e-9))
    if not ops and allow_host_device:
        ops["/host:CPU"] = host_ops
    return ops, spans


def summarize(ops_per_device, spans, lo, hi):
    """The numbers a run reports from its trace over ``[lo, hi]``:
    busy seconds averaged over the devices, the window length, the idle
    seconds by span, and the ``breakdown`` lists (of the first device)."""
    devs = sorted(ops_per_device)
    if not devs:
        raise ValueError("the trace holds no device plane")
    busy = [busy_seconds(ops_per_device[d], lo, hi) for d in devs]
    first = ops_per_device[devs[0]]
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": hi - lo,
        "n_device_ops": len(first),
        "idle_by_span": idle_by_span(first, spans, lo, hi),
        "breakdown": {"device_ops": top_ops(first, lo, hi),
                      "idle_gaps": longest_gaps(first, spans, lo, hi)},
        "ops": first,
        "spans": spans,
    }


def idle_share(rec, driver):
    """Percent of the traced window in which no device operation ran, in
    a run of ``driver``'s traffic; ``None`` where there is no trace."""
    tr = rec.get("trace")
    if tr is None or rec.get("driver") != driver or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
