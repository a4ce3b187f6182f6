"""Milliseconds per drag frame spent waiting on the two fetches, the
probe's (``incremental.probe_fetch``) and the delta's scores
(``scores.fetch``), from the program's spans."""

import program_spans

FETCH = ("incremental.probe_fetch", "scores.fetch")


def read(rec):
    spans = program_spans.window(rec, "drag")
    if spans is None:
        return None
    frames = program_spans.count(spans, "session.update")
    if not frames:
        return None
    return 1e3 * program_spans.total_s(spans, FETCH) / frames
