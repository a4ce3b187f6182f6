"""Device idle share of the traced window in the graph-sharded cell: 1
minus the union of device-operation intervals over the window, averaged
over the chips, in percent."""

from trace_reduce import idle_share


def read(rec):
    return idle_share(rec, "select")
