#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root
of the checkout.  Everything about it is found by name: the
configuration file the entry's ``config`` names and its graph generator
``generators/<generator>.py``, the traffic file ``traffic/<traffic>.json``
and the driver ``drivers/<driver>.py`` it names, one reader
``metrics/<metric>.py`` per metric, and the correctness limits
``limits/<cell>.json``.

In order, a run: refuses anything but a TPU with at least the cell's
chips; turns on the persistent compilation cache in the checkout; builds
the graph from the configuration's fixed seed and the traffic from
``--seed``; warms every shape the window uses (all of that is
``setup_s``); measures for ``--seconds``; with ``--trace 1`` traces the
window and reduces the trace; reads the peak device memory; checks a
sample of the window's answers against the plain reference; and prints,
as the last line of standard output, one JSON object::

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, "breakdown": {...}, "check": {...}}

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics.  The last lines of standard error give each compared
number beside its limit, as does the ``check`` key.

``--control`` runs the program in bfloat16 (its own lower-precision
path), which the comparison has to refuse.  ``--rate`` overrides an open
loop's arrival rate (for the sweep that finds the highest sustained
rate).  ``--rehearsal`` is for the tests alone: it allows the CPU and
shrinks the cell to the sizes in the files' ``rehearsal`` blocks.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if HERE not in sys.path:
    sys.path.insert(0, HERE)


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", action="store_true")
    p.add_argument("--rate", type=float, default=None)
    p.add_argument("--rehearsal", action="store_true",
                   help=argparse.SUPPRESS)
    return p.parse_args(argv)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def merged(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def load_spec(root=ROOT):
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(spec, workload):
    for cell in spec["workloads"]:
        if cell["name"] == workload:
            conf = next(c for c in spec["configs"]
                        if c["name"] == cell["config"])
            return cell, conf
    raise SystemExit(f"unknown workload {workload!r}; known: "
                     f"{[c['name'] for c in spec['workloads']]}")


def load_config(entry, rehearsal=False, root=ROOT):
    cfg = load_json(os.path.join(root, entry["file"]))
    return merged(cfg, cfg.get("rehearsal", {})) if rehearsal else cfg


def load_traffic(name, rehearsal=False, here=HERE):
    t = load_json(os.path.join(here, "traffic", f"{name}.json"))
    return merged(t, t.get("rehearsal", {})) if rehearsal else t


def metrics_for(entries, workload):
    return [m for m in entries
            if workload in m.get("workloads", [workload])]


def devices(chips, rehearsal):
    """The devices the cell runs on; ``None`` when there is no TPU with
    enough chips (a rehearsal takes the CPU)."""
    import jax

    devs = jax.devices()
    if rehearsal:
        return devs[:1]
    if devs[0].platform != "tpu" or len(devs) < chips:
        return None
    return devs[:chips]


def finite(x):
    return x if math.isfinite(x) else 1e300


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def main(argv=None, t_start=None):
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    spec = load_spec()
    cell, conf_entry = find_cell(spec, args.workload)
    devs = devices(int(cell["chips"]), args.rehearsal)
    if devs is None:
        import jax
        log(f"refusing to run: cell {args.workload} needs {cell['chips']} "
            f"TPU chip(s), JAX sees {jax.devices()}")
        return 3

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    import numpy as np
    from repro.api import EvalConfig

    if not args.rehearsal:
        from repro.launch.compile_cache import use_compile_cache
        cache = use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        log(f"compilation cache: {cache}")

    import check
    import find
    import graphs
    from kit import span

    cfg = load_config(conf_entry, args.rehearsal)
    traffic = load_traffic(cell["traffic"], args.rehearsal)
    pos, edges, spacing = graphs.build(cfg["graph"], HERE)
    eval_kw = dict(cfg["eval"])
    if args.control:
        eval_kw["precision"] = "bfloat16"
    ctx = SimpleNamespace(pos=pos, edges=edges, spacing=spacing,
                          traffic=traffic, seed=args.seed, rate=args.rate,
                          eval_config=EvalConfig(**eval_kw))
    log(f"cell {args.workload}: V={pos.shape[0]} E={edges.shape[0]} "
        f"traffic={cell['traffic']} seed={args.seed}")
    with jax.default_device(devs[0]):
        driver = find.module("drivers", traffic["driver"], HERE).Driver(ctx)
        setup_s = time.perf_counter() - t_start
        log(f"setup_s {setup_s}")

        trace_dir = os.path.join(ROOT, ".bench_trace", args.workload)
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
        with span("bench.window"):
            rec = driver.window(args.seconds)
        if args.trace:
            jax.profiler.stop_trace()
    rec["setup_s"] = setup_s
    if args.trace:
        import trace_reduce
        ops, spans = trace_reduce.load(trace_dir, allow_host_device=args.rehearsal)
        shutil.rmtree(trace_dir, ignore_errors=True)
        win = [s for s in spans if s[0] == "bench.window"]
        lo, hi = win[-1][1], win[-1][2]
        rec["trace"] = trace_reduce.summarize(ops, spans, lo, hi)
        log(f"trace: busy_s {rec['trace']['busy_s']} window_s "
            f"{rec['trace']['window_s']} device ops "
            f"{rec['trace']['n_device_ops']} idle by span "
            f"{rec['trace']['idle_by_span']}")

    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    answers = driver.answers(np.random.default_rng([args.seed, 1]))
    del driver
    geometry = dict(radius=cfg["eval"]["radius"],
                    n_strips=cfg["eval"]["n_strips"],
                    ideal_angle_deg=cfg["ideal_angle_deg"])
    t_ref = time.perf_counter()
    worst = check.compare(answers, edges, geometry, rec["failed"], log=log)
    limits = load_json(os.path.join(HERE, "limits", f"{args.workload}.json"))
    correct, table = check.verdict(worst, limits["limits"])
    log(f"reference over {len(answers)} answers took "
        f"{time.perf_counter() - t_ref} s")

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in metrics_for(spec[kind], args.workload):
        if m["name"] == "setup_s":
            value = setup_s
        else:
            value = find.module("metrics", m["name"], HERE).read(rec)
        if value is not None and math.isfinite(value):
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    for k in ("layouts_per_s", "calls", "first_call_s", "median_call_s",
              "frames", "delta_hits",
              "by_selection", "stats", "rate_per_s", "drain_s"):
        if k in rec:
            log(f"{k} {rec[k]}")
    if "lateness_ms" in rec and rec["lateness_ms"]:
        from window import percentile
        log(f"generator lateness ms: p50 {percentile(rec['lateness_ms'], 50)}"
            f" p95 {percentile(rec['lateness_ms'], 95)} max "
            f"{max(rec['lateness_ms'])} over {len(rec['lateness_ms'])} waits")

    result = {
        "correct": bool(correct),
        "attempted": int(rec["attempted"]),
        "failed": int(rec["failed"]),
        "metrics": metrics,
        "device": {"platform": devs[0].platform,
                   "kind": devs[0].device_kind, "count": len(devs),
                   "memory_peak_bytes": peak},
    }
    if args.trace:
        result["device"]["busy_s"] = rec["trace"]["busy_s"]
        result["device"]["window_s"] = rec["trace"]["window_s"]
        result["breakdown"] = rec["trace"]["breakdown"]
    result["check"] = {k: {"value": finite(v["value"]), "limit": v["limit"]}
                       for k, v in table.items()}
    for k, v in table.items():
        log(f"check {k} {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
