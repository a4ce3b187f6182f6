"""Device-busy milliseconds per layout scored in the traced window of the
graph-sharded cell, the busy time averaged over the chips
(``trace_reduce.summarize``), read as the select cells read it."""

import find


def read(rec):
    return find.module("metrics", "device_ms_per_layout.select").read(rec)
