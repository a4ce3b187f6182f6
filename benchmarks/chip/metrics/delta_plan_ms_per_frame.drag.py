"""Host milliseconds per drag frame spent planning and committing the
delta: ``incremental.affected_edges``, ``plan_strips``, ``plan_cells``
and ``commit``, from the program's spans."""

import program_spans

PLAN = ("incremental.affected_edges", "incremental.plan_strips",
        "incremental.plan_cells", "incremental.commit")


def read(rec):
    spans = program_spans.window(rec, "drag")
    if spans is None:
        return None
    frames = program_spans.count(spans, "session.update")
    if not frames:
        return None
    return 1e3 * program_spans.total_s(spans, PLAN) / frames
