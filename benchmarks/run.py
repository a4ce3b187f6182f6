"""Benchmark driver: one section per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--fast]

Prints ``name,us_per_call,derived`` CSV blocks per section.
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller dataset scales (CI-speed)")
    ap.add_argument("--full", action="store_true",
                    help="larger dataset scales (hours on 1 CPU core)")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    # default sized for the single-core container; --full for the
    # paper-scale sweep (the speedup *ratios* are scale-stable)
    scale = 0.015 if args.fast else (0.08 if args.full else 0.04)

    sections = []

    def section(name, fn):
        print(f"\n===== {name} =====")
        t0 = time.time()
        try:
            fn()
            sections.append((name, "ok", time.time() - t0))
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            sections.append((name, "FAIL", time.time() - t0))

    from benchmarks import (fig4_scaling, table2_runtime, table3_accuracy,
                            table4_grid)

    section("table2_runtime (paper Table 2 / Figs 2-3)",
            lambda: table2_runtime.main(["--scale", str(scale)]))
    section("table3_accuracy (paper Table 3)",
            lambda: table3_accuracy.main(["--scale", str(scale)]))
    section("table4_grid (paper Table 4)",
            lambda: table4_grid.main(["--scale",
                                      str(max(scale / 2, 0.02)),
                                      "--layouts", "3"]))
    section("fig4_scaling (paper Fig 4)",
            lambda: fig4_scaling.main(["--scale", str(scale)]))

    print("\n===== summary =====")
    print("section,status,seconds")
    failed = 0
    for name, status, sec in sections:
        print(f"{name},{status},{sec:.1f}")
        failed += status != "ok"
    sys.exit(1 if failed else 0)


if __name__ == "__main__":
    main()
