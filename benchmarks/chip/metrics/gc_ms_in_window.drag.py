"""Milliseconds of Python garbage collection inside the drag window
(the program's ``python.gc`` spans)."""

import program_spans


def read(rec):
    spans = program_spans.window(rec, "drag")
    if spans is None:
        return None
    return 1e3 * program_spans.total_s(spans, ("python.gc",))
