"""Paper Table 2 (+ Figs 2-3): running time of the single-machine
reference (the Greadability.js stand-in: the naive single-shot jnp
oracle), the exact distributed algorithms, and the enhanced algorithms,
on random layouts of the six SNAP-sized datasets.

CPU container note: datasets are size-scaled (--scale, default 0.08) so
the O(E^2) exact sweep finishes; speedup *ratios* are the deliverable
(the paper's own metric), and the ratio trend vs |V|/|E| reproduces
Figs 2-3. Full-size numbers live in the dry-run/roofline track.
"""

from __future__ import annotations

import argparse

import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, timed
from repro.core import (count_crossings_enhanced, count_crossings_exact,
                        count_occlusions_enhanced, count_occlusions_exact,
                        crossing_angle_enhanced, crossing_angle_exact,
                        edge_length_variation, minimum_angle)
from repro.graphs.datasets import PAPER_DATASETS, paper_graph
from repro.graphs.layouts import random_layout
from repro.kernels import ref
from repro.launch.compile_cache import use_compile_cache


def run(scale: float = 0.08, radius: float = 0.5, n_strips: int = 256):
    rows = []
    for name in PAPER_DATASETS:
        edges_np, n_v = paper_graph(name, seed=0, scale=scale)
        pos = jnp.asarray(random_layout(n_v, seed=1))
        edges = jnp.asarray(edges_np)

        # reference = naive single-shot oracle (Greadability.js role)
        t_ref_occ, occ_ref = timed(
            lambda: count_occlusions_exact(pos, radius, block=2048))
        t_exact_occ, occ_ex = timed(
            lambda: count_occlusions_exact(pos, radius, block=512))
        t_enh_occ, (occ_enh, _) = timed(
            lambda: count_occlusions_enhanced(pos, radius))
        assert int(occ_ex) == int(occ_ref) == int(occ_enh)

        t_ma, _ = timed(lambda: minimum_angle(pos, edges))
        t_ml, _ = timed(lambda: edge_length_variation(pos, edges))

        x1, y1 = pos[edges[:, 0], 0], pos[edges[:, 0], 1]
        x2, y2 = pos[edges[:, 1], 0], pos[edges[:, 1], 1]
        # reference = single-machine blocked jnp (Greadability.js role);
        # the single-shot oracle would need O(E^2) resident memory here
        t_ref_cross, cr_ref = timed(
            lambda: count_crossings_exact(pos, edges, block=1024))
        t_exact_cross, cr_ex = timed(
            lambda: count_crossings_exact(pos, edges, block=256))
        t_enh_cross, (cr_enh, _) = timed(
            lambda: count_crossings_enhanced(pos, edges, n_strips=n_strips,
                                             orientation="both"))
        t_exact_angle, angle_ex = timed(
            lambda: crossing_angle_exact(pos, edges))
        t_enh_angle, angle_enh = timed(
            lambda: crossing_angle_enhanced(pos, edges, n_strips=n_strips))

        base = dict(dataset=name, n_v=n_v, n_e=len(edges_np))
        rows += [
            dict(base, metric="N_c", algo="reference", sec=t_ref_occ,
                 value=int(occ_ref)),
            dict(base, metric="N_c", algo="exact", sec=t_exact_occ,
                 value=int(occ_ex)),
            dict(base, metric="N_c", algo="enhanced", sec=t_enh_occ,
                 value=int(occ_enh),
                 speedup=t_ref_occ / max(t_enh_occ, 1e-9)),
            dict(base, metric="M_a", algo="exact", sec=t_ma),
            dict(base, metric="M_l", algo="exact", sec=t_ml),
            dict(base, metric="E_c", algo="reference", sec=t_ref_cross,
                 value=int(cr_ref)),
            dict(base, metric="E_c", algo="exact", sec=t_exact_cross,
                 value=int(cr_ex)),
            dict(base, metric="E_c", algo="enhanced", sec=t_enh_cross,
                 value=int(cr_enh),
                 speedup=t_ref_cross / max(t_enh_cross, 1e-9)),
            dict(base, metric="E_ca", algo="exact", sec=t_exact_angle,
                 value=float(angle_ex[0])),
            dict(base, metric="E_ca", algo="enhanced", sec=t_enh_angle,
                 value=float(angle_enh[0]),
                 speedup=t_exact_angle / max(t_enh_angle, 1e-9)),
        ]
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.08)
    args = ap.parse_args(argv)
    use_compile_cache()
    rows = run(scale=args.scale)
    print("dataset,n_v,n_e,metric,algo,us_per_call,value,speedup_vs_ref")
    for r in rows:
        speedup = f"{r['speedup']:.2f}" if "speedup" in r else ""
        print(f"{r['dataset']},{r['n_v']},{r['n_e']},{r['metric']},"
              f"{r['algo']},{r['sec'] * 1e6:.0f},{r.get('value', '')},"
              f"{speedup}")
    return rows


if __name__ == "__main__":
    main()
