"""Host milliseconds per ``Evaluator.evaluate_batch`` call in the
graph-sharded cell: the program's root span ``evaluator.evaluate_batch``
less its ``scores.fetch``, read as the select cells read it."""

import find


def read(rec):
    return find.module("metrics", "front_host_ms_per_call.select").read(rec)
