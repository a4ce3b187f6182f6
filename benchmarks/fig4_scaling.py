"""Paper Fig 4: strong scaling of ONE spatially partitioned layout.

The paper's headline numbers (17x node occlusion / 146x edge crossing on
a Spark cluster) are about a *single graph too large for one worker*.
This benchmark drives the graph-axis sharded engine
(``backend="graph_sharded"``, :mod:`repro.distributed.graph_sharded`)
at |V| in {1e4, 1e5, 1e6} across 1/2/4 forced-host devices and records,
per (size, device count) cell:

* **measured wall time**.  On an accelerator every device count runs in
  this process on a sub-mesh of ``jax.devices()[:n]`` (a chip belongs to
  one process; the host needs 4 devices).  On the CPU backend each
  device count runs in a subprocess with that many forced host devices
  (the count must be set before jax initializes); those devices
  timeshare the host's cores, so CPU wall time CANNOT show real parallel
  speedup and is only the sanity check that the sharded program runs at
  every mesh size;
* the **work-based strong-scaling curve** — the max per-device share of
  the pair-comparison work under the contiguous strip/cell partition of
  :func:`repro.core.grid.plan_graph_shards`, computed host-side from
  the actual strip/cell occupancies.  This is the quantity the paper's
  Fig 4 slope reflects (their per-machine partition of the same
  decompositions), and the acceptance gate:
  ``work_speedup >= 1.5 at 4 devices, |V|=1e5``;
* **integer-metric parity**: every cell's (N_c, E_c) must be
  bit-identical to the single-host fused engine and invariant across
  device counts — a benchmark that drifts from the reference is
  measuring a different function.

Writes ``BENCH_fig4.json`` at the repo root.

``--smoke`` (optionally with ``--devices N``) runs only the collective
budget certification, in-process: one all-metrics evaluation must bump
the ``halo_exchanges`` counter exactly once, a crossing-only evaluation
must bump it zero times and build zero occlusion cells, and integer
metrics must match single-host bit-for-bit.  CI wires this like
``engine_bench --smoke``.

  PYTHONPATH=src python benchmarks/fig4_scaling.py            # full table
  PYTHONPATH=src python benchmarks/fig4_scaling.py --smoke --devices 4
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys


def _apply_devices_flag():
    """``--devices N`` must act before jax initializes (same pre-import
    scan as ``engine_bench``)."""
    n = None
    for i, arg in enumerate(sys.argv):
        if arg == "--devices":
            if i + 1 >= len(sys.argv):
                sys.exit("--devices needs a value")
            n = int(sys.argv[i + 1])
        elif arg.startswith("--devices="):
            n = int(arg.split("=", 1)[1])
    if n is not None and n > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}").strip()


_apply_devices_flag()

import time  # noqa: E402

import numpy as np  # noqa: E402

from repro.graphs.layouts import lattice_graph  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

# (size, strips, timed reps): strips grow with |V| to keep per-strip
# capacity — and the O(cap^2 x strips) sweep — proportionate; the 1e6
# row runs one timed rep (minutes of CPU per evaluation)
SIZES = ((10_000, 256, 3), (100_000, 512, 3), (1_000_000, 2048, 1))
DEVICE_COUNTS = (1, 2, 4)
RADIUS = 0.5
GATE_SIZE = 100_000
GATE_DEVICES = 4
GATE_SPEEDUP = 1.5


def _frac_long(n_v: int) -> float:
    """Scale the long-edge sprinkle down with size: long edges span
    ~half the strips each, so a constant *fraction* would blow the strip
    capacity (and the O(cap^2) sweep) quadratically at 1e6."""
    return min(0.02, 0.02 * 10_000 / n_v)


def measure_cell(devices, n_v: int, n_strips: int, frac_long: float,
                 reps: int) -> dict:
    """Time one graph-sharded evaluation of the |V|=n_v lattice over a
    1-D mesh of ``devices``; on one device also record the single-host
    fused engine's integers for the parity check."""
    import jax

    from repro.core import engine
    from repro.distributed.graph_sharded import evaluate_graph_sharded
    from repro.launch.elastic import serving_mesh

    pos, edges = lattice_graph(n_v, seed=0, frac_long=frac_long)
    plan = engine.plan_readability(pos, edges, radius=RADIUS,
                                   n_strips=n_strips, tier_strips=False)
    mesh = serving_mesh("graph", devices=devices)

    res = evaluate_graph_sharded(mesh, plan, pos, edges)     # compile + warm
    jax.block_until_ready(res.node_occlusion)
    t0 = time.perf_counter()
    for _ in range(reps):
        res = evaluate_graph_sharded(mesh, plan, pos, edges)
        jax.block_until_ready(res.node_occlusion)
    sec = (time.perf_counter() - t0) / reps

    out = dict(seconds=sec,
               node_occlusion=int(res.node_occlusion),
               edge_crossing=int(res.edge_crossing),
               overflow=int(res.overflow))
    if len(devices) == 1:
        ref = engine.evaluate_planned(plan, pos, edges)
        out["single_host"] = dict(node_occlusion=int(ref.node_occlusion),
                                  edge_crossing=int(ref.edge_crossing))
    return out


_CHILD = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                           + sys.argv[1])
import jax

sys.path.insert(0, %(bench_dir)r)
from fig4_scaling import measure_cell

ndev = int(sys.argv[1])
assert len(jax.devices()) == ndev
out = measure_cell(jax.devices(), int(sys.argv[2]), int(sys.argv[3]),
                   float(sys.argv[4]), int(sys.argv[5]))
print("RESULT " + json.dumps(out))
"""


def _cpu_cell(ndev, n_v, n_strips, reps) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    child = _CHILD % dict(bench_dir=os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run(
        [sys.executable, "-c", child, str(ndev), str(n_v),
         str(n_strips), str(_frac_long(n_v)), str(reps)],
        env=env, capture_output=True, text=True, timeout=3600)
    assert res.returncode == 0, res.stdout + "\n" + res.stderr
    line = [l for l in res.stdout.splitlines()
            if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def _work_model(n_v: int, n_strips: int, n_shards: int):
    """Host-side pair-work totals under the contiguous shard partition.

    Strip work: sum over orientations of per-strip occupancy^2 (the
    O(cap^2) reversal sweep's true work is occupancy-shaped).  Cell
    work: per-cell occupancy^2 plus the forward-neighbour cross
    products (the owner-cell sweep).  Returns (total, max per-device),
    whose ratio is the work-based strong-scaling speedup."""
    from repro.core import engine
    from repro.core import grid as gridlib

    pos, edges = lattice_graph(n_v, seed=0, frac_long=_frac_long(n_v))
    plan = engine.plan_readability(pos, edges, radius=RADIUS,
                                   n_strips=n_strips, tier_strips=False)
    spec = gridlib.plan_graph_shards(plan.n_strips, plan.grid_nx,
                                     plan.grid_ny, n_shards)

    per_dev = np.zeros(n_shards, np.float64)

    # strips, both orientations, contiguous ranges of strips_per_shard
    for axis in plan.axes:
        _, per_strip = gridlib.plan_strip_occupancy(
            pos, edges, plan.n_strips, axis=axis)
        w = np.asarray(per_strip, np.float64) ** 2
        for d in range(n_shards):
            s0 = d * spec.strips_per_shard
            per_dev[d] += w[s0:s0 + spec.strips_per_shard].sum()

    # occlusion cells: owner-cell sweep = own-pairs + forward-neighbour
    # cross products, contiguous ranges of cells_per_shard
    nx, ny = plan.grid_nx, plan.grid_ny
    x0, y0 = plan.grid_origin
    inv = 1.0 / plan.grid_cell_size
    ix = np.clip(((pos[:, 0] - x0) * inv).astype(np.int64), 0, nx - 1)
    iy = np.clip(((pos[:, 1] - y0) * inv).astype(np.int64), 0, ny - 1)
    occ = np.bincount(iy * nx + ix, minlength=nx * ny).astype(np.float64)
    grid2 = occ.reshape(ny, nx)
    cw = grid2 ** 2
    for dx, dy in gridlib.FORWARD_NEIGHBOURHOOD:
        sh = np.zeros_like(grid2)
        ys = slice(max(dy, 0), ny + min(dy, 0))
        xs = slice(max(dx, 0), nx + min(dx, 0))
        yd = slice(max(-dy, 0), ny + min(-dy, 0))
        xd = slice(max(-dx, 0), nx + min(-dx, 0))
        sh[yd, xd] = grid2[ys, xs]
        cw += grid2 * sh
    cw = cw.ravel()
    for d in range(n_shards):
        c0 = d * spec.cells_per_shard
        per_dev[d] += cw[c0:c0 + spec.cells_per_shard].sum()

    return float(per_dev.sum()), float(per_dev.max())


def run_full():
    import jax

    on_cpu = jax.default_backend() == "cpu"
    if not on_cpu and len(jax.devices()) < max(DEVICE_COUNTS):
        sys.exit(f"fig4_scaling needs {max(DEVICE_COUNTS)} devices; "
                 f"this host has {len(jax.devices())}")
    table = []
    for n_v, n_strips, reps in SIZES:
        rows = {}
        ref_ints = None
        for ndev in DEVICE_COUNTS:
            if on_cpu:
                out = _cpu_cell(ndev, n_v, n_strips, reps)
            else:
                out = measure_cell(jax.devices()[:ndev], n_v, n_strips,
                                   _frac_long(n_v), reps)
            total, peak = _work_model(n_v, n_strips, ndev)
            out["work_speedup"] = total / peak
            rows[ndev] = out
            ints = (out["node_occlusion"], out["edge_crossing"])
            if ndev == 1:
                # bit-identity vs the single-host fused engine
                sh = out.pop("single_host")
                assert ints == (sh["node_occlusion"], sh["edge_crossing"]), \
                    (n_v, ints, sh)
                ref_ints = ints
            else:
                # shard-count invariance
                assert ints == ref_ints, (n_v, ndev, ints, ref_ints)
            print(f"|V|={n_v:>9,}  devices={ndev}  "
                  f"wall={out['seconds']:.3f}s  "
                  f"work_speedup={out['work_speedup']:.2f}x  "
                  f"N_c={out['node_occlusion']}  "
                  f"E_c={out['edge_crossing']}", flush=True)
        table.append(dict(
            n_vertices=n_v, n_strips=n_strips, radius=RADIUS,
            frac_long=_frac_long(n_v),
            rows=[dict(devices=d, **rows[d]) for d in DEVICE_COUNTS],
            parity="integer metrics bit-identical to single-host fused "
                   "and invariant across 1/2/4 devices"))

    gate_row = next(t for t in table if t["n_vertices"] == GATE_SIZE)
    gate = next(r for r in gate_row["rows"] if r["devices"] == GATE_DEVICES)
    record = dict(
        benchmark="fig4_graph_sharded_scaling",
        note="wall time on forced host devices timeshares one physical "
             "core (sanity only); work_speedup = total pair-work / max "
             "per-device pair-work under the contiguous strip+cell "
             "partition — the paper fig. 4 quantity",
        paper_reference="arxiv 2411.09809 fig. 4: 17x node occlusion / "
                        "146x edge crossing at 16 Spark machines",
        sizes=table,
        acceptance=dict(
            gate=f">= {GATE_SPEEDUP}x work speedup at {GATE_DEVICES} "
                 f"devices, |V|={GATE_SIZE:,}",
            work_speedup=gate["work_speedup"],
            passed=gate["work_speedup"] >= GATE_SPEEDUP))
    out_path = os.path.join(os.path.dirname(__file__), "..",
                            "BENCH_fig4.json")
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    print(f"wrote {os.path.abspath(out_path)}")
    assert record["acceptance"]["passed"], record["acceptance"]
    return record


def run_smoke() -> int:
    """Collective-budget certification: exactly one halo exchange per
    all-metrics evaluation, zero (and zero cell builds) for a
    crossing-only subset, integer metrics bit-identical to single-host.
    Runs in-process on however many devices ``--devices`` forced."""
    import jax

    from repro.core import engine
    from repro.core import grid
    from repro.distributed.graph_sharded import evaluate_graph_sharded
    from repro.launch.elastic import serving_mesh

    mesh = serving_mesh("graph")
    ndev = mesh.size
    pos, edges = lattice_graph(10_000, seed=0, frac_long=0.02)
    plan = engine.plan_readability(pos, edges, radius=RADIUS,
                                   n_strips=256, tier_strips=False)

    c0 = grid.CALL_COUNTS["halo_exchanges"]
    res = evaluate_graph_sharded(mesh, plan, pos, edges)
    halo = grid.CALL_COUNTS["halo_exchanges"] - c0
    ref = engine.evaluate_planned(plan, pos, edges)
    ok = halo == 1
    print(f"smoke[{ndev} devices]: halo_exchanges per all-metrics "
          f"trace = {halo} (want 1)")
    for f in ("node_occlusion", "edge_crossing"):
        same = int(getattr(res, f)) == int(getattr(ref, f))
        ok &= same
        print(f"smoke[{ndev} devices]: {f} sharded={int(getattr(res, f))} "
              f"single-host={int(getattr(ref, f))} "
              f"({'bit-identical' if same else 'MISMATCH'})")

    xplan = engine.plan_readability(pos, edges, radius=RADIUS,
                                    n_strips=256, tier_strips=False,
                                    metrics=("edge_crossing",))
    c_h = grid.CALL_COUNTS["halo_exchanges"]
    c_c = grid.CALL_COUNTS["cell_builds"]
    xres = evaluate_graph_sharded(mesh, xplan, pos, edges)
    halo_x = grid.CALL_COUNTS["halo_exchanges"] - c_h
    cells_x = grid.CALL_COUNTS["cell_builds"] - c_c
    ok &= halo_x == 0 and cells_x == 0
    ok &= int(xres.edge_crossing) == int(ref.edge_crossing)
    print(f"smoke[{ndev} devices]: crossing-only trace: "
          f"halo_exchanges={halo_x} cell_builds={cells_x} (want 0/0), "
          f"E_c={int(xres.edge_crossing)}")
    print("smoke PASS" if ok else "smoke FAIL")
    return 0 if ok else 1


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="collective-budget counter check only")
    ap.add_argument("--devices", type=int, default=None,
                    help="force N host devices (applied pre-import)")
    args = ap.parse_args(argv)
    use_compile_cache()
    if args.smoke:
        sys.exit(run_smoke())
    return run_full()


if __name__ == "__main__":
    main()
