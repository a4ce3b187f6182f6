"""Fused-engine benchmark: unfused per-metric path vs plan-once fused path.

Times, at |V| in {1k, 10k} (CPU-friendly sizes; same code path on TPU):

  * the OLD unfused evaluation — per-metric enhanced calls, host-side
    re-planning and a blocking device->host sync per metric (4 strip
    builds + 4 reversal sweeps per evaluation with orientation='both');
  * the fused engine single-layout path (2 builds + 2 sweeps, one traced
    program, one transfer) — certified via grid.CALL_COUNTS;
  * batched ``evaluate_layouts`` (B=32) vs a Python loop of single
    evaluations — both the pre-engine per-call path (re-plans + one sync
    per metric: what a caller wrote before this PR) and the plan-reusing
    fused single-layout loop (isolates the pure batching win; on a
    2-core CPU host the workload is compute-bound so this one is modest
    — the dispatch amortization shows on accelerators);
  * **metric subsets** (|V|=1k): the same ``EvalConfig``-driven program
    with ``metrics`` pruned to ``crossing_only`` / ``occlusion_only``
    vs ``all`` — pruning is certified structurally (the crossing-only
    trace builds ZERO cell buckets and runs zero vertex-key sorts; the
    occlusion-only trace runs ZERO strip builds/reversal sweeps, via
    grid.CALL_COUNTS) and timed (each subset must beat the all-metrics
    program).

  * **mesh-sharded batched** (|V|=1k, B=32, 4 devices):
    ``repro.distributed.batched``'s batch-axis sharding vs a per-layout
    ``evaluate_sharded`` loop (>= 1.5x gate, plus bit-identical integer
    parity with the single-host batched program) — the
    ``sharded_batched`` record.  On an accelerator it runs in this
    process on a sub-mesh of ``jax.devices()[:4]`` (a chip belongs to one
    process); on the CPU backend it runs in a clean subprocess with 4
    forced host devices, so the forced-device view cannot perturb the
    single-host timings.

``--config '{"n_strips": 128, ...}'`` overrides the base EvalConfig.
``--smoke`` runs only the subset-pruning sections (single-host AND
sharded-batched; no file write; exits nonzero if a pruned decomposition
was built) — CI uses it so metric-subset pruning regressions fail fast.
``--devices N`` forces N host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count``) before jax
initializes; ``--sharded-only`` prints just the sharded-batched record
(the CPU subprocess leg of the full run).

Writes BENCH_engine.json next to this file (the perf trajectory record).

  PYTHONPATH=src python benchmarks/engine_bench.py
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time


def _apply_devices_flag():
    """``--devices N`` must act before jax initializes: it maps onto the
    same ``XLA_FLAGS=--xla_force_host_platform_device_count`` forcing the
    distributed tests use (N fake host devices on CPU).  Handles both
    ``--devices N`` and ``--devices=N`` (argparse accepts both, so the
    pre-import scan must too)."""
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

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(__file__))
from common import timed  # noqa: E402

from repro.core import (evaluate_layouts, evaluate_planned,  # noqa: E402
                        plan_readability)
from repro.core import engine  # noqa: E402
from repro.core import grid as gridlib  # noqa: E402
from repro.core.crossing import count_crossings_enhanced  # noqa: E402
from repro.core.crossing_angle import crossing_angle_enhanced  # noqa: E402
from repro.core.edge_length import edge_length_variation  # noqa: E402
from repro.core.keys import EvalConfig  # noqa: E402
from repro.graphs.layouts import lattice_graph  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.core.min_angle import minimum_angle  # noqa: E402
from repro.core.occlusion import count_occlusions_enhanced  # noqa: E402
BATCH = 32

# metric subsets benched against the all-metrics program
SUBSETS = {
    "all": None,                                   # base config's metrics
    "crossing_only": ("edge_crossing", "edge_crossing_angle"),
    "occlusion_only": ("node_occlusion",),
}


def make_graph(n_v, seed=0, frac_long=0.02):
    """:func:`repro.graphs.layouts.lattice_graph` as device arrays."""
    pos, edges = lattice_graph(n_v, seed=seed, frac_long=frac_long)
    return jnp.asarray(pos), jnp.asarray(edges)


def unfused_evaluate(pos, edges, n_strips):
    """The pre-engine evaluate_layout body: re-plans per call, one host
    sync per metric, separate strip builds + sweeps for E_c and E_ca."""
    out = {}
    overflow = 0
    c, ov = count_occlusions_enhanced(pos, 0.5)
    out["node_occlusion"] = int(c)
    overflow += int(ov)
    m_a, _ = minimum_angle(pos, edges)
    out["minimum_angle"] = float(m_a)
    out["edge_length_variation"] = float(edge_length_variation(pos, edges))
    c, ov = count_crossings_enhanced(pos, edges, n_strips=n_strips)
    out["edge_crossing"] = int(c)
    overflow += int(ov)
    e_ca, count, _, ov = crossing_angle_enhanced(pos, edges,
                                                 n_strips=n_strips)
    out["edge_crossing_angle"] = float(e_ca)
    out["crossing_count_for_angle"] = int(count)
    out["overflow"] = overflow + int(ov)
    return out


def bench_size(n_v, n_strips, *, batch=True):
    pos, edges = make_graph(n_v)
    rec = {"n_vertices": n_v, "n_edges": int(edges.shape[0]),
           "n_strips": n_strips}

    # -- work-shape certification: builds/sweeps per evaluation ------------
    gridlib.reset_call_counts()
    unfused_evaluate(pos, edges, n_strips)
    rec["unfused_strip_builds"] = gridlib.CALL_COUNTS["strip_builds"]
    rec["unfused_reversal_sweeps"] = gridlib.CALL_COUNTS["reversal_sweeps"]

    t0 = time.perf_counter()
    plan = plan_readability(pos, edges, n_strips=n_strips)
    rec["plan_seconds"] = time.perf_counter() - t0

    gridlib.reset_call_counts()
    jax.block_until_ready(evaluate_planned(plan, pos, edges))  # traces here
    rec["fused_strip_builds"] = gridlib.CALL_COUNTS["strip_builds"]
    rec["fused_reversal_sweeps"] = gridlib.CALL_COUNTS["reversal_sweeps"]

    # -- single-layout timings --------------------------------------------
    t_unfused, _ = timed(unfused_evaluate, pos, edges, n_strips, repeats=3)
    t_fused, _ = timed(lambda: jax.block_until_ready(
        evaluate_planned(plan, pos, edges)), repeats=5)
    rec["unfused_seconds"] = t_unfused
    rec["fused_seconds"] = t_fused
    rec["single_speedup"] = t_unfused / t_fused

    # -- batched (B candidate layouts of one graph, modest perturbations,
    # as produced inside an optimization loop) -----------------------------
    if batch:
        rng = np.random.default_rng(1)
        sigma = 0.3 * 100.0 / np.sqrt(n_v)   # ~0.3 lattice spacings
        b = np.stack([np.asarray(pos) +
                      rng.normal(0, sigma, size=pos.shape).astype(np.float32)
                      for _ in range(BATCH)])
        bplan = plan_readability(b, edges, n_strips=n_strips)
        # occupancy tiers the batched plan chose (new in the native
        # batched engine: per-orientation pow2 capacity tiers)
        rec["strip_tier_caps"] = [list(t[0]) for t in bplan.strip_tiers]
        rec["strip_tier_counts"] = [list(t[1]) for t in bplan.strip_tiers]
        bj = jnp.asarray(b)
        jax.block_until_ready(evaluate_planned(bplan, bj[0], edges))  # warm
        jax.block_until_ready(evaluate_layouts(bplan, bj, edges))     # warm

        # loop of single evaluations as a caller wrote them before the
        # engine existed: per-call re-planning + per-metric host syncs
        # (timed on a few batch members, extrapolated to B)
        k = 4
        t0 = time.perf_counter()
        for i in range(k):
            unfused_evaluate(bj[i], edges, n_strips)
        t_loop_unfused = (time.perf_counter() - t0) * (BATCH / k)

        # loop of fused single evaluations reusing the plan (the new
        # fast path, minus batching).  Both sides fetch their results —
        # a layout optimizer reads the scores, so the loop pays B
        # device->host transfers where the batched dispatch pays ONE
        # (the engine's "all scalars in one transfer" contract).
        def loop_planned():
            return [jax.device_get(evaluate_planned(bplan, bj[i], edges))
                    for i in range(BATCH)]

        t_loop_planned, _ = timed(loop_planned, repeats=2)
        t_batch, _ = timed(lambda: jax.device_get(
            evaluate_layouts(bplan, bj, edges)), repeats=2)
        rec["batch_size"] = BATCH
        rec["loop_single_seconds"] = t_loop_unfused
        rec["loop_single_measured_candidates"] = k
        rec["loop_planned_seconds"] = t_loop_planned
        rec["batched_seconds"] = t_batch
        rec["batched_speedup_vs_single_loop"] = t_loop_unfused / t_batch
        rec["batched_speedup_vs_planned_loop"] = t_loop_planned / t_batch
    return rec


def bench_sharded_batched(base: EvalConfig, n_v: int = 1000,
                          batch: int = BATCH, repeats: int = 2,
                          devices=None):
    """Mesh-sharded batched evaluation vs per-layout ``evaluate_sharded``
    looping — the composition the ISSUE-5 acceptance gate times.

    The loop baseline is what a mesh caller had before the batched
    driver: one strip-sharded dispatch chain per candidate (fresh plan,
    per-metric host syncs) — measured on a few candidates and
    extrapolated like the unfused loop baseline.  The sharded-batched
    path shards the batch axis of ONE natively batched dispatch over
    the same mesh (over ``devices``, default all); integer parity with
    the single-host batched program is asserted as part of the record.
    """
    from repro.distributed.batched import evaluate_layouts_sharded
    from repro.distributed.gridded import evaluate_sharded
    from repro.launch.elastic import serving_mesh

    pos, edges = make_graph(n_v)
    cfg = dataclasses.replace(base, backend="distributed")
    rng = np.random.default_rng(1)
    sigma = 0.3 * 100.0 / np.sqrt(n_v)
    b = np.stack([np.asarray(pos) +
                  rng.normal(0, sigma, size=pos.shape).astype(np.float32)
                  for _ in range(batch)])
    mesh = serving_mesh("batch", devices=devices)
    ndev = mesh.size
    plan = plan_readability(b, edges, **cfg.plan_kwargs())
    bj = jnp.asarray(b)

    jax.block_until_ready(
        evaluate_layouts_sharded(mesh, plan, bj, edges))     # warm
    jax.block_until_ready(evaluate_layouts(plan, bj, edges))  # warm

    # per-layout sharded loop (each call re-plans and rebuilds its
    # shard_map dispatches — the pre-composition cost, honestly timed)
    k = min(4, batch)
    t0 = time.perf_counter()
    for i in range(k):
        evaluate_sharded(mesh, bj[i], edges, config=cfg)
    t_loop = (time.perf_counter() - t0) * (batch / k)

    t_shard, _ = timed(lambda: jax.device_get(
        evaluate_layouts_sharded(mesh, plan, bj, edges)), repeats=repeats)
    t_host, _ = timed(lambda: jax.device_get(
        evaluate_layouts(plan, bj, edges)), repeats=repeats)

    got = jax.device_get(evaluate_layouts_sharded(mesh, plan, bj, edges))
    want = jax.device_get(evaluate_layouts(plan, bj, edges))
    int_parity = (
        np.array_equal(np.asarray(got.edge_crossing),
                       np.asarray(want.edge_crossing))
        and np.array_equal(np.asarray(got.node_occlusion),
                           np.asarray(want.node_occlusion))
        and np.array_equal(np.asarray(got.overflow),
                           np.asarray(want.overflow)))

    return {"devices": ndev, "batch_size": batch, "n_vertices": n_v,
            "n_strips": cfg.n_strips,
            "sharded_loop_seconds": t_loop,
            "sharded_loop_measured_candidates": k,
            "sharded_batched_seconds": t_shard,
            "host_batched_seconds": t_host,
            "speedup_vs_sharded_loop": t_loop / t_shard,
            "int_parity_vs_host_batched": bool(int_parity)}


def smoke_sharded_batched(base: EvalConfig, n_v: int = 300) -> bool:
    """Counter tripwire for the sharded-batched route: metric-subset
    pruning must survive the shard_map composition (a crossing-only
    config traces zero cell builds, an occlusion-only config zero strip
    builds/sweeps, *per shard body*)."""
    from repro.distributed.batched import evaluate_layouts_sharded
    from repro.launch.elastic import serving_mesh

    pos, edges = make_graph(n_v)
    rng = np.random.default_rng(1)
    b = jnp.asarray(np.stack(
        [np.asarray(pos) + rng.normal(0, 0.2, size=pos.shape)
         .astype(np.float32) for _ in range(4)]))
    mesh = serving_mesh("batch")
    ok = True
    for name, metrics in SUBSETS.items():
        if metrics is None:
            continue
        cfg = dataclasses.replace(base, metrics=metrics,
                                  backend="distributed")
        plan = plan_readability(b, edges, **cfg.plan_kwargs())
        gridlib.reset_call_counts()
        jax.block_until_ready(
            evaluate_layouts_sharded(mesh, plan, b, edges))  # traces here
        c = dict(gridlib.CALL_COUNTS)
        if name == "crossing_only":
            good = c["cell_builds"] == 0 and c["vertex_sorts"] == 0
        else:
            good = c["strip_builds"] == 0 and c["reversal_sweeps"] == 0
        print(f"  sharded {name:14s}: counters {c}"
              f"  {'ok' if good else 'PRUNING REGRESSED'}")
        ok = ok and good
    return ok


def bench_metric_subsets(base: EvalConfig, n_v: int = 1000,
                         repeats: int = 5):
    """Per-subset timings + structural pruning proof at one size.

    Counters come from ONE eager ``evaluate_once`` call per subset
    (deterministic python side effects, immune to jit-cache state);
    timings come from the jitted ``evaluate_planned`` steady state."""
    pos, edges = make_graph(n_v)
    rec = {"n_vertices": n_v, "n_strips": base.n_strips,
           "config_digest": base.digest(), "subsets": {}}
    for name, metrics in SUBSETS.items():
        cfg = base if metrics is None else dataclasses.replace(
            base, metrics=metrics)
        plan = plan_readability(pos, edges, **cfg.plan_kwargs())
        gridlib.reset_call_counts()
        engine.evaluate_once(plan, pos, edges)
        counters = dict(gridlib.CALL_COUNTS)
        jax.block_until_ready(evaluate_planned(plan, pos, edges))  # warm
        t, _ = timed(lambda: jax.device_get(
            evaluate_planned(plan, pos, edges)), repeats=repeats)
        rec["subsets"][name] = {"metrics": list(cfg.metrics), "seconds": t,
                                "work_counters": counters}
    t_all = rec["subsets"]["all"]["seconds"]
    for name in ("crossing_only", "occlusion_only"):
        rec["subsets"][name]["speedup_vs_all"] = \
            t_all / rec["subsets"][name]["seconds"]
    cx = rec["subsets"]["crossing_only"]["work_counters"]
    oc = rec["subsets"]["occlusion_only"]["work_counters"]
    rec["pruning"] = {
        # the acceptance criterion: crossing-only builds ZERO cell
        # buckets (and skips the vertex-key sort), occlusion-only runs
        # ZERO reversal sweeps (and builds no strips)
        "crossing_only_zero_cell_builds":
            cx["cell_builds"] == 0 and cx["vertex_sorts"] == 0,
        "occlusion_only_zero_sweeps":
            oc["reversal_sweeps"] == 0 and oc["strip_builds"] == 0,
        "crossing_only_faster_than_all":
            rec["subsets"]["crossing_only"]["speedup_vs_all"] > 1.0,
        "occlusion_only_faster_than_all":
            rec["subsets"]["occlusion_only"]["speedup_vs_all"] > 1.0,
    }
    return rec


def print_subsets(rec):
    for name, sub in rec["subsets"].items():
        extra = (f"  speedup vs all {sub['speedup_vs_all']:.2f}x"
                 if "speedup_vs_all" in sub else "")
        print(f"  {name:14s}: {sub['seconds'] * 1e3:8.1f} ms  "
              f"counters {sub['work_counters']}{extra}")
    print(f"  pruning: {rec['pruning']}")


def _sharded_subprocess(n_mesh: int, config: str) -> dict:
    sub = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--sharded-only",
         "--devices", str(n_mesh), "--config", config],
        capture_output=True, text=True, timeout=1800)
    if sub.returncode != 0:
        print(sub.stdout + "\n" + sub.stderr)
        sys.exit(1)
    line = [l for l in sub.stdout.splitlines()
            if l.startswith("SHARDED_RESULT ")][-1]
    return json.loads(line[len("SHARDED_RESULT "):])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="{}",
                    help="JSON EvalConfig field overrides, e.g. "
                         '\'{"n_strips": 128}\'')
    ap.add_argument("--smoke", action="store_true",
                    help="subset-pruning section only; no BENCH file; "
                         "nonzero exit if pruning regressed (CI gate)")
    ap.add_argument("--devices", type=int, default=None,
                    help="force N host devices (consumed before jax "
                         "import; the sharded-batched sections then run "
                         "on an N-device mesh)")
    ap.add_argument("--sharded-only", action="store_true",
                    help="run ONLY the sharded-batched section and print "
                         "its record as JSON (used by the full bench to "
                         "time the mesh on forced devices in a clean "
                         "subprocess)")
    args = ap.parse_args(argv)
    base = EvalConfig(**{"n_strips": 128, **json.loads(args.config)})
    use_compile_cache()

    if args.sharded_only:
        rec = bench_sharded_batched(base, n_v=1000)
        print("SHARDED_RESULT " + json.dumps(rec))
        return

    if args.smoke:
        print("metric subsets (smoke) ...", flush=True)
        rec = bench_metric_subsets(base, n_v=1000, repeats=3)
        print_subsets(rec)
        print("sharded-batched subsets (smoke) ...", flush=True)
        sharded_ok = smoke_sharded_batched(base)
        # timing gates are advisory in smoke (shared CI runners are
        # noisy); the structural counter gates are the regression tripwire
        ok = (rec["pruning"]["crossing_only_zero_cell_builds"]
              and rec["pruning"]["occlusion_only_zero_sweeps"]
              and sharded_ok)
        if not ok:
            print("SMOKE FAIL: a pruned config still built the "
                  "decomposition it should skip")
            sys.exit(1)
        print("smoke ok: metric-subset pruning intact "
              "(single-host and sharded-batched routes)")
        return

    results = {"backend": jax.default_backend(),
               "sizes": []}
    for n_v, n_strips in ((1000, 128), (10000, 256)):
        print(f"|V|={n_v} ...", flush=True)
        rec = bench_size(n_v, n_strips)
        results["sizes"].append(rec)
        print(f"  work shape : unfused {rec['unfused_strip_builds']} builds/"
              f"{rec['unfused_reversal_sweeps']} sweeps -> fused "
              f"{rec['fused_strip_builds']}/{rec['fused_reversal_sweeps']}")
        print(f"  single     : unfused {rec['unfused_seconds'] * 1e3:8.1f} ms"
              f"  fused {rec['fused_seconds'] * 1e3:8.1f} ms"
              f"  speedup {rec['single_speedup']:.2f}x")
        print(f"  batched B={rec['batch_size']}: single-eval loop "
              f"{rec['loop_single_seconds'] * 1e3:8.1f} ms  planned loop "
              f"{rec['loop_planned_seconds'] * 1e3:8.1f} ms  batched "
              f"{rec['batched_seconds'] * 1e3:8.1f} ms  speedup "
              f"{rec['batched_speedup_vs_single_loop']:.2f}x / "
              f"{rec['batched_speedup_vs_planned_loop']:.2f}x")

    print("metric subsets @1k ...", flush=True)
    subsets = bench_metric_subsets(base, n_v=1000)
    results["metric_subsets"] = subsets
    print_subsets(subsets)

    n_mesh = args.devices or 4
    if jax.default_backend() != "cpu":
        # a chip belongs to one process: time the mesh here, on a
        # sub-mesh of the real devices
        print(f"sharded batched @1k (devices[:{n_mesh}]) ...", flush=True)
        sharded = bench_sharded_batched(base, n_v=1000,
                                        devices=jax.devices()[:n_mesh])
    else:
        # CPU: a clean subprocess, so the forced n-device host view
        # cannot perturb the single-host timings above
        print(f"sharded batched @1k ({n_mesh} forced host devices) ...",
              flush=True)
        sharded = _sharded_subprocess(n_mesh, args.config)
    results["sharded_batched"] = sharded
    print(f"  devices={sharded['devices']} B={sharded['batch_size']}: "
          f"per-layout sharded loop "
          f"{sharded['sharded_loop_seconds'] * 1e3:8.1f} ms  "
          f"sharded batched "
          f"{sharded['sharded_batched_seconds'] * 1e3:8.1f} ms  "
          f"speedup {sharded['speedup_vs_sharded_loop']:.2f}x  "
          f"int parity {sharded['int_parity_vs_host_batched']}")

    ok_shape = all(r["fused_strip_builds"] == 2
                   and r["fused_reversal_sweeps"] == 2
                   and r["unfused_strip_builds"] == 4
                   and r["unfused_reversal_sweeps"] == 4
                   for r in results["sizes"])
    big = results["sizes"][-1]
    results["acceptance"] = {
        **subsets["pruning"],
        "fused_work_shape_2_builds_2_sweeps": ok_shape,
        "single_speedup_10k_ge_1.5x": big["single_speedup"] >= 1.5,
        "batched_speedup_ge_3x": all(
            r["batched_speedup_vs_single_loop"] >= 3.0
            for r in results["sizes"]
            if "batched_speedup_vs_single_loop" in r),
        # the native batched engine must beat a Python loop of the
        # plan-reusing single-layout jit at every size — the vmapped
        # path recorded 0.73x/0.80x, i.e. batching used to cost wall
        # clock instead of amortizing it
        "batched_speedup_vs_planned_loop_ge_1.5x": all(
            r["batched_speedup_vs_planned_loop"] >= 1.5
            for r in results["sizes"]
            if "batched_speedup_vs_planned_loop" in r),
        # the ISSUE-5 gate: mesh-sharded batched must beat per-layout
        # evaluate_sharded looping >= 1.5x at |V|=1k, with integer
        # metrics bit-identical to the single-host batched program
        "sharded_batched_speedup_ge_1.5x":
            sharded["speedup_vs_sharded_loop"] >= 1.5,
        "sharded_batched_int_parity":
            sharded["int_parity_vs_host_batched"],
    }
    out = os.path.join(os.path.dirname(__file__), "..", "BENCH_engine.json")
    with open(os.path.abspath(out), "w") as f:
        json.dump(results, f, indent=2)
    print("acceptance:", results["acceptance"])
    print(f"wrote {os.path.abspath(out)}")


if __name__ == "__main__":
    main()
