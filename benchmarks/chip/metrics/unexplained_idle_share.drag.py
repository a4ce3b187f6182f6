"""Percent of the drag window's device-idle time that no span of the
program explains: the innermost span open over it (harness and aligned
program spans together, ``trace_reduce.idle_by_span``) is a program
root, a ``bench.*`` span, or none."""

import program_spans
import trace_reduce


def read(rec):
    spans = program_spans.window(rec, "drag")
    if spans is None:
        return None
    tr = rec["trace"]
    lo, hi = program_spans.bench_window(tr["spans"])
    prog = [(sp["name"], sp["start"], sp["end"]) for sp in spans]
    idle = trace_reduce.idle_by_span(tr["ops"], tr["spans"] + prog, lo, hi)
    total = sum(idle.values())
    if total <= 0:
        return None
    unexplained = sum(sec for name, sec in idle.items()
                      if name in program_spans.ROOT_NAMES
                      or name.startswith(trace_reduce.SPAN_PREFIX)
                      or name == "outside_spans")
    return 100.0 * unexplained / total
