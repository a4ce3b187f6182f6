"""Host milliseconds per ``Evaluator.evaluate_batch`` call: the
program's root span ``evaluator.evaluate_batch`` less its
``scores.fetch`` (validation, planning, dispatch and the transfer of the
candidates), from the program's spans."""

import program_spans


def read(rec):
    spans = program_spans.window(rec, "select")
    if spans is None:
        return None
    roots = {sp["id"]: sp["end"] - sp["start"] for sp in spans
             if sp["name"] == "evaluator.evaluate_batch"}
    if not roots:
        return None
    host = sum(roots.values()) - sum(
        sp["end"] - sp["start"] for sp in spans
        if sp["name"] == "scores.fetch" and sp["parent"] in roots)
    return 1e3 * host / len(roots)
