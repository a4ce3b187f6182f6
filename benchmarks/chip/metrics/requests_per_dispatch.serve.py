"""Requests per engine dispatch in the window, from the session's own
counters (``EvalSession.stats``)."""


def read(rec):
    if rec.get("driver") != "serve" or not rec["stats"]["dispatches"]:
        return None
    return rec["stats"]["requests"] / rec["stats"]["dispatches"]
