"""95th percentile over requests of the time from when each was due to
when the dispatch that carried it started (host clock)."""

from window import percentile


def read(rec):
    if rec.get("driver") != "serve":
        return None
    return percentile(rec["queue_wait_ms"], 95)
