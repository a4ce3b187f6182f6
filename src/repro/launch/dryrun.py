import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry run: lower + compile every (architecture x input shape)
on the production meshes.

  PYTHONPATH=src python -m repro.launch.dryrun                # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b
  PYTHONPATH=src python -m repro.launch.dryrun --multi-pod    # 2x16x16
  PYTHONPATH=src python -m repro.launch.dryrun --cell qwen3-4b:train_4k

For each cell prints compile wall time, ``memory_analysis()`` (proves the
partitioned program fits) and ``cost_analysis()`` (FLOPs / bytes feeding
EXPERIMENTS.md SRoofline). Results also land in ``dryrun_results.json``.

NOTE: the XLA_FLAGS line above MUST run before any other import — jax
locks the device count at first backend init.
"""

import argparse
import json
import time
import traceback


def run_cell(arch_id, shape_id, mesh, mesh_name):
    import jax
    from repro.launch.cells import lower_cell, make_cell

    t0 = time.time()
    cell = make_cell(arch_id, shape_id, mesh)
    lowered = lower_cell(cell, mesh)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    rec = {
        "arch": arch_id, "shape": shape_id, "mesh": mesh_name,
        "status": "ok", "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops": cost.get("flops"),
        "bytes_accessed": cost.get("bytes accessed"),
        "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "meta": {k: v for k, v in cell.meta.items()
                 if isinstance(v, (int, float, str))},
    }
    print(f"[{mesh_name}] {arch_id} x {shape_id}: OK "
          f"(lower {t_lower:.1f}s compile {t_compile:.1f}s, "
          f"flops={cost.get('flops', 0):.3e})")
    print(f"    memory_analysis: {mem}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--cell", default=None, help="arch:shape")
    ap.add_argument("--multi-pod", action="store_true",
                    help="run ONLY the 2x16x16 multi-pod mesh")
    ap.add_argument("--both", action="store_true",
                    help="run single-pod AND multi-pod meshes")
    ap.add_argument("--skip-readability", action="store_true")
    ap.add_argument("--out", default="dryrun_results.json")
    args = ap.parse_args()

    import jax
    assert len(jax.devices()) == 512, (
        "dry run needs 512 placeholder devices", len(jax.devices()))

    from repro.configs import all_cells
    from repro.configs.readability import READABILITY_SHAPES
    from repro.launch.mesh import make_production_mesh

    meshes = []
    if args.both:
        meshes = [("pod16x16", make_production_mesh(multi_pod=False)),
                  ("pods2x16x16", make_production_mesh(multi_pod=True))]
    elif args.multi_pod:
        meshes = [("pods2x16x16", make_production_mesh(multi_pod=True))]
    else:
        meshes = [("pod16x16", make_production_mesh(multi_pod=False))]

    cells = []
    for arch_id, shape_id, _ in all_cells():
        if args.arch and arch_id != args.arch:
            continue
        cells.append((arch_id, shape_id))
    if not args.skip_readability and not args.arch:
        cells.extend(("readability", s) for s in READABILITY_SHAPES)
    if args.cell:
        a, s = args.cell.split(":")
        cells = [(a, s)]

    # skipped cells are recorded, not silently dropped
    records = []
    for arch_id, shape_id, reason in all_cells(include_skipped=True):
        if reason and (not args.arch or arch_id == args.arch):
            records.append({"arch": arch_id, "shape": shape_id,
                            "status": "skipped", "reason": reason})
            print(f"SKIP {arch_id} x {shape_id}: {reason}")

    failures = 0
    for mesh_name, mesh in meshes:
        for arch_id, shape_id in cells:
            try:
                records.append(run_cell(arch_id, shape_id, mesh, mesh_name))
            except Exception as e:  # noqa: BLE001 - report and continue
                failures += 1
                records.append({"arch": arch_id, "shape": shape_id,
                                "mesh": mesh_name, "status": "fail",
                                "error": f"{type(e).__name__}: {e}"})
                print(f"[{mesh_name}] {arch_id} x {shape_id}: FAIL {e}")
                traceback.print_exc()

    with open(args.out, "w") as f:
        json.dump(records, f, indent=1)
    ok = sum(1 for r in records if r["status"] == "ok")
    skipped = sum(1 for r in records if r["status"] == "skipped")
    print(f"\ndry run: {ok} ok, {skipped} skipped (documented), "
          f"{failures} failed -> {args.out}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
