"""Pallas kernel micro-benchmarks (interpret mode on CPU) vs the blocked
pure-jnp implementations — correctness-coupled timing for the three
pairwise hot-spot kernels."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from benchmarks.common import timed
from repro.core import count_crossings_exact, count_occlusions_exact
from repro.kernels.ops import crossing_count_op, occlusion_count_op
from repro.launch.compile_cache import use_compile_cache


def run(n_vertices: int = 2048, n_edges: int = 2048):
    rng = np.random.default_rng(0)
    pos = jnp.asarray(rng.uniform(0, 100, (n_vertices, 2)).astype(
        np.float32))
    edges = set()
    while len(edges) < n_edges:
        v, u = rng.integers(0, n_vertices, 2)
        if v != u:
            edges.add((min(v, u), max(v, u)))
    edges = jnp.asarray(np.array(sorted(edges), np.int32))

    rows = []
    t_jnp, want = timed(lambda: count_occlusions_exact(pos, 2.0, block=512))
    t_pl, got = timed(lambda: occlusion_count_op(pos, 2.0, tile=512))
    assert int(got) == int(want)
    rows.append(("occlusion_jnp_blocked", t_jnp, int(want)))
    rows.append(("occlusion_pallas_interp", t_pl, int(got)))

    t_jnp, want = timed(lambda: count_crossings_exact(pos, edges,
                                                      block=256))
    t_pl, got = timed(lambda: crossing_count_op(pos, edges, tile=256))
    assert int(got) == int(want)
    rows.append(("crossing_jnp_blocked", t_jnp, int(want)))
    rows.append(("crossing_pallas_interp", t_pl, int(got)))
    return rows


def main(argv=None):
    use_compile_cache()
    rows = run()
    print("name,us_per_call,derived")
    for name, sec, val in rows:
        print(f"{name},{sec * 1e6:.0f},{val}")
    return rows


if __name__ == "__main__":
    main()
