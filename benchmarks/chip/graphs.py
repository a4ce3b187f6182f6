"""Graph instances and their base layouts, generated from a config's seed.

Each generator takes the ``graph`` block of a configuration file and
returns ``(pos (V, 2) float32, edges (E, 2) int32, spacing)``: the layout
in the 100 x 100 box the engine's benchmarks use, the undirected edge
list (each pair once, no self-loops) and the mean vertex spacing that
the traffic files scale their moves by.  The block's ``generator`` key
names the file ``generators/<generator>.py``, whose ``build(params)``
makes the graph; a new generator is a new file.
"""

from __future__ import annotations

import numpy as np

BOX = 100.0


def undirected(edges: np.ndarray) -> np.ndarray:
    """Each unordered pair once, self-loops dropped, sorted."""
    e = np.sort(np.asarray(edges, np.int64), axis=1)
    e = e[e[:, 0] != e[:, 1]]
    return np.unique(e, axis=0).astype(np.int32)


def build(graph: dict, here=None):
    """``(pos, edges, spacing)`` of a configuration's ``graph`` block."""
    import find

    return find.module("generators", graph["generator"],
                       here or find.HERE).build(graph)
