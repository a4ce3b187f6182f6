"""Paper Table 4: edge-crossing error vs grid (strip) size and
orientation, over Fruchterman-Reingold layouts of ego-Facebook.
Paper claims: error shrinks with smaller strips; taking the max over
both orientations beats either alone."""

from __future__ import annotations

import argparse

import jax.numpy as jnp
import numpy as np

from repro.core import count_crossings_enhanced, count_crossings_exact
from repro.graphs.datasets import paper_graph
from repro.graphs.layouts import fruchterman_reingold, random_layout
from repro.launch.compile_cache import use_compile_cache


def run(scale: float = 0.04, n_layouts: int = 4,
        strip_counts=(128, 512)):
    edges_np, n_v = paper_graph("ego-Facebook", seed=0, scale=scale)
    edges = jnp.asarray(edges_np)
    rows = []
    errs = {(ns, o): [] for ns in strip_counts
            for o in ("vertical", "horizontal", "both")}
    for layout_i in range(n_layouts):
        pos0 = jnp.asarray(random_layout(n_v, seed=layout_i))
        pos = fruchterman_reingold(pos0, edges, n_iter=40, block=256)
        truth = int(count_crossings_exact(pos, edges))
        for ns in strip_counts:
            for orient in ("vertical", "horizontal", "both"):
                got, _ = count_crossings_enhanced(pos, edges, n_strips=ns,
                                                  orientation=orient)
                errs[(ns, orient)].append(
                    abs(int(got) - truth) / max(truth, 1))
    for (ns, orient), es in errs.items():
        rows.append(dict(n_strips=ns, orientation=orient,
                         mean=float(np.mean(es)), std=float(np.std(es))))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.04)
    ap.add_argument("--layouts", type=int, default=4)
    args = ap.parse_args(argv)
    use_compile_cache()
    rows = run(scale=args.scale, n_layouts=args.layouts)
    print("n_strips,orientation,mean_err_pct,std")
    for r in rows:
        print(f"{r['n_strips']},{r['orientation']},"
              f"{100 * r['mean']:.2f},{r['std']:.4f}")
    return rows


if __name__ == "__main__":
    main()
