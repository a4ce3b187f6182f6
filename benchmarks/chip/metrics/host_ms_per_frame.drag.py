"""Device-idle milliseconds inside the benchmark's frame spans
(``bench.update``), per frame: the host's share of a frame."""

import trace_reduce


def read(rec):
    tr = rec.get("trace")
    if tr is None or rec.get("driver") != "drag" or not rec["frames"]:
        return None
    lo = min(s for n, s, e in tr["spans"] if n == "bench.window")
    hi = max(e for n, s, e in tr["spans"] if n == "bench.window")
    idle = trace_reduce.idle_within(tr["ops"], tr["spans"], "bench.update", lo, hi)
    return 1e3 * idle / rec["frames"]
