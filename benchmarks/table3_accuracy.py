"""Paper Table 3: percentage error of the enhanced algorithms vs ground
truth on random layouts of each dataset. Paper claims: N_c exactly 0%,
E_c ~1.5%, E_ca ~4.5%."""

from __future__ import annotations

import argparse

import jax.numpy as jnp
import numpy as np

from repro.core import (count_crossings_enhanced, count_crossings_exact,
                        count_occlusions_enhanced, count_occlusions_exact,
                        crossing_angle_enhanced, crossing_angle_exact)
from repro.graphs.datasets import PAPER_DATASETS, paper_graph
from repro.graphs.layouts import random_layout
from repro.launch.compile_cache import use_compile_cache


def run(scale: float = 0.08, n_strips: int = 512, radius: float = 0.5):
    rows = []
    for name in PAPER_DATASETS:
        edges_np, n_v = paper_graph(name, seed=0, scale=scale)
        pos = jnp.asarray(random_layout(n_v, seed=1))
        edges = jnp.asarray(edges_np)

        occ_ex = int(count_occlusions_exact(pos, radius))
        occ_enh, _ = count_occlusions_enhanced(pos, radius)
        occ_err = abs(int(occ_enh) - occ_ex) / max(occ_ex, 1)

        cr_ex = int(count_crossings_exact(pos, edges))
        cr_enh, _ = count_crossings_enhanced(pos, edges, n_strips=n_strips,
                                             orientation="both")
        cr_err = abs(int(cr_enh) - cr_ex) / max(cr_ex, 1)

        a_ex, _, _ = crossing_angle_exact(pos, edges)
        a_enh, _, _, _ = crossing_angle_enhanced(pos, edges,
                                                 n_strips=n_strips)
        a_err = abs(float(a_enh) - float(a_ex)) / max(abs(float(a_ex)),
                                                      1e-9)
        rows.append(dict(dataset=name, n_v=n_v, n_e=len(edges_np),
                         nc_err=occ_err, ec_err=cr_err, eca_err=a_err))
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.08)
    ap.add_argument("--n-strips", type=int, default=512)
    args = ap.parse_args(argv)
    use_compile_cache()
    rows = run(scale=args.scale, n_strips=args.n_strips)
    print("dataset,n_v,n_e,Nc_err_pct,Ec_err_pct,Eca_err_pct")
    for r in rows:
        print(f"{r['dataset']},{r['n_v']},{r['n_e']},"
              f"{100 * r['nc_err']:.2f},{100 * r['ec_err']:.2f},"
              f"{100 * r['eca_err']:.2f}")
    avg_ec = float(np.mean([r["ec_err"] for r in rows]))
    avg_eca = float(np.mean([r["eca_err"] for r in rows]))
    print(f"# paper claims: Nc 0.0%, Ec ~1.5%, Eca ~4.5% | "
          f"ours: Nc {max(r['nc_err'] for r in rows) * 100:.2f}%, "
          f"Ec {avg_ec * 100:.2f}%, Eca {avg_eca * 100:.2f}%")
    return rows


if __name__ == "__main__":
    main()
