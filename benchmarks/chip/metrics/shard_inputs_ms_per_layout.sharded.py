"""Host milliseconds per layout in ``graph_sharded.inputs``: the
conversion and placement of the layout and the edge list that every
graph-sharded dispatch makes, from the program's spans.  A program
without that span reads nothing."""

import program_spans


def read(rec):
    spans = program_spans.window(rec, "select")
    if spans is None:
        return None
    mine = [sp["end"] - sp["start"] for sp in spans
            if sp["name"] == "graph_sharded.inputs"]
    if not mine:
        return None
    return 1e3 * sum(mine) / len(mine)
