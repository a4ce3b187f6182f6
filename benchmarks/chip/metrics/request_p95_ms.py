"""95th percentile over the window's requests of the time from when each
was due to when its answer was back (host clock); a failed request
counts as missing."""

from window import percentile


def read(rec):
    if "latency_ms" not in rec:
        return None
    return percentile(rec["latency_ms"], 95)
