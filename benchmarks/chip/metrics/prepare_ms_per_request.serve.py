"""Host milliseconds per request spent preparing it in the session
(``session.prepare``: validation, topology hash and padding), from the
program's spans."""

import program_spans


def read(rec):
    spans = program_spans.window(rec, "serve")
    if spans is None:
        return None
    n = program_spans.count(spans, "session.prepare")
    if not n:
        return None
    return 1e3 * program_spans.total_s(spans, ("session.prepare",)) / n
