"""Milliseconds per layout that the first chip spends in collectives:
operations whose HLO name starts with ``all-reduce``,
``collective-permute`` or ``all-gather`` (the halo exchange and the
psums of partial counts, and any wait there for the slowest chip), over
the traced window."""

import program_spans

PREFIXES = ("all-reduce", "collective-permute", "all-gather")


def read(rec):
    tr = rec.get("trace")
    if tr is None or rec.get("driver") != "select" or not rec["layouts"]:
        return None
    win = program_spans.bench_window(tr["spans"])
    if win is None:
        return None
    lo, hi = win
    sec = sum(max(0.0, min(e, hi) - max(s, lo)) for name, s, e in tr["ops"]
              if name.startswith(PREFIXES))
    return 1e3 * sec / rec["layouts"]
