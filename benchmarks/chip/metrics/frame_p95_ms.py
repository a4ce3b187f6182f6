"""95th percentile of the window's drag-frame latencies (host clock)."""

from window import percentile


def read(rec):
    if "frame_ms" not in rec:
        return None
    return percentile(rec["frame_ms"], 95)
