"""Serving benchmark: eager per-request server vs the session server.

Both servers are built from the SAME :class:`repro.core.keys.EvalConfig`
(only the ``backend`` differs), so what is measured is purely the
serving architecture.  Compares, on steady-state mixed-size request
streams at |V| in {200, 1k, 5k, 10k} (layout-local graphs, modest
per-request perturbations — the 'score candidate layouts inside a
generation loop' regime; the 10k row is the large-graph regime a
session may later route to the graph-sharded path, so its serving gain
must stay measurable).  Per-size latency records p50 AND p95 — tail
latency is what a serving SLO prices, and the p95/p50 gap is where
replans/retraces would hide:

  * the eager baseline (``backend="eager"``): host-side re-planning +
    eager fused evaluation per request — what every request paid before
    the session layer existed;
  * the session server (``backend="fused"``): plan-cache + pow2 shape
    buckets + padded jitted evaluation + same-bucket coalescing.  After a
    warmup pass the stats counters must show ZERO replans and ZERO new
    traces — steady state is pure jit-cache-hit dispatching.

``--config '{"metrics": ["edge_crossing"], ...}'`` overrides the base
config, so subset serving (e.g. a crossing-only scoring service) is one
flag away.

The ``validation_overhead`` section prices the fault-tolerance layer
(docs/robustness.md): the same steady-state mixed stream served with
``validation="off"`` vs ``"strict"``, rounds interleaved so machine
drift hits both equally.  The acceptance gate requires strict
validation to cost <= 5% of steady-state throughput AND the
zero-replan / zero-retrace steady state to survive with the layer on.
``--validation-gate`` runs only this section (the CI chaos leg's cost
gate) and merges it into an existing BENCH_serve.json.

The ``overload`` section prices admission control: the same |V|=1k
burst served uncontended (burst == capacity) vs at 2x offered load on a
``max_queue``-bounded session (the excess is shed with
``OverloadedError``), rounds interleaved.  The acceptance gate requires
goodput under 2x overload >= 80% of uncontended capacity, admitted p95
latency within 2x the uncontended p95, deterministic shedding of
exactly the excess, and a clean (zero-replan / zero-retrace /
zero-expiry) admitted steady state.  ``--overload-gate`` runs only this
section and merges it into an existing BENCH_serve.json.

Writes BENCH_serve.json next to the repo root (the serving perf record).

  PYTHONPATH=src python benchmarks/serve_bench.py
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(__file__))
from engine_bench import make_graph  # noqa: E402

from repro.core.keys import EvalConfig  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.serve import ReadabilityServer  # noqa: E402

SIZES = (200, 1000, 5000, 10000)
N_STRIPS = 128
PER_SIZE = 2          # requests per size per mixed round
WARMUP_ROUNDS = 2
TIMED_ROUNDS = 8
EAGER_REPS = 3
SESSION_REPS = 5


def perturbed(pos, rng, n_v):
    sigma = 0.3 * 100.0 / np.sqrt(n_v)    # ~0.3 lattice spacings
    return pos + rng.normal(0, sigma, pos.shape).astype(np.float32)


def lat_ms(fn, reps):
    """(p50, p95) latency in ms over ``reps`` calls.  With single-digit
    rep counts the p95 is an interpolated near-max — still the right
    record: one replan or retrace in the window shows up there first."""
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return (float(np.median(times)) * 1e3,
            float(np.percentile(times, 95)) * 1e3)


def validation_overhead(base, graphs, rng):
    """Price the fault layer: the same steady-state stream served with
    ``validation="off"`` vs ``"strict"``, timed round-robin (drift hits
    both modes equally), plus the counter proof that the zero-replan /
    zero-retrace steady state survives with validation on."""
    servers = {mode: ReadabilityServer(
        dataclasses.replace(base, validation=mode))
        for mode in ("off", "strict")}
    sizes = sorted(graphs)

    def mixed_round(server):
        reqs = [(perturbed(graphs[n][0], rng, n), graphs[n][1])
                for n in sizes for _ in range(PER_SIZE)]
        return server.evaluate_batch(reqs)

    for srv in servers.values():
        for _ in range(WARMUP_ROUNDS):
            mixed_round(srv)
    before = {m: dict(s.stats) for m, s in servers.items()}
    times = {m: [] for m in servers}
    # rounds here are short (small graphs), so take plenty of them: the
    # 5% gate must measure the validation layer, not scheduler noise
    for _ in range(4 * TIMED_ROUNDS):
        for mode, srv in servers.items():
            t0 = time.perf_counter()
            mixed_round(srv)
            times[mode].append(time.perf_counter() - t0)

    n_per_round = PER_SIZE * len(sizes)
    section = {"sizes": sizes}
    for mode, srv in servers.items():
        after = dict(srv.stats)
        delta = {k: after[k] - before[mode][k] for k in
                 ("replans", "traces", "plan_misses", "quarantined",
                  "sanitized", "dispatch_failures")}
        p50 = float(np.median(times[mode]))
        section[mode] = {
            "p50_round_ms": p50 * 1e3,
            "requests_per_sec": n_per_round / p50,
            "steady_state_counters": delta,
        }
    overhead = (section["strict"]["p50_round_ms"]
                / section["off"]["p50_round_ms"]) - 1.0
    section["strict_overhead_fraction"] = overhead
    clean = all(section[m]["steady_state_counters"][k] == 0
                for m in ("off", "strict")
                for k in ("replans", "traces", "plan_misses",
                          "quarantined", "dispatch_failures"))
    section["acceptance"] = {
        "strict_overhead_le_5pct": overhead <= 0.05,
        "steady_state_clean_under_validation": clean,
    }
    print(f"validation overhead: off "
          f"{section['off']['requests_per_sec']:.1f} req/s, strict "
          f"{section['strict']['requests_per_sec']:.1f} req/s "
          f"({overhead * 100:+.1f}%)")
    print("validation acceptance:", section["acceptance"])
    return section


OVERLOAD_BURST = 16      # uncontended burst == steady-state capacity
OVERLOAD_FACTOR = 2      # offered load under overload: factor * burst


def overload_section(base, graphs, rng):
    """Price the bounded queue: uncontended bursts of OVERLOAD_BURST
    requests vs 2x-offered-load bursts against a ``max_queue``-bounded
    session, rounds interleaved (drift hits both equally).  Admitted
    requests carry a generous deadline, so the watchdog guard's cost is
    inside the measured latency too."""
    n = max(k for k in graphs)
    pos, edges = graphs[n]
    cap_srv = ReadabilityServer(base)
    over_srv = ReadabilityServer(base, max_queue=OVERLOAD_BURST,
                                 default_deadline=120.0)

    def burst(server, B):
        return server.evaluate_batch(
            [(perturbed(pos, rng, n), edges) for _ in range(B)])

    offered = OVERLOAD_FACTOR * OVERLOAD_BURST
    for _ in range(WARMUP_ROUNDS):
        burst(cap_srv, OVERLOAD_BURST)
        burst(over_srv, offered)
    before = dict(over_srv.stats)
    cap_times, over_times = [], []
    shed_per_round, bad = [], 0
    for _ in range(TIMED_ROUNDS):
        t0 = time.perf_counter()
        burst(cap_srv, OVERLOAD_BURST)
        cap_times.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        out = burst(over_srv, offered)
        over_times.append(time.perf_counter() - t0)
        shed_per_round.append(sum(r.shed for r in out))
        bad += sum(1 for r in out if not (r.ok or r.shed))
    after = dict(over_srv.stats)
    delta = {k: after[k] - before[k] for k in
             ("replans", "traces", "plan_misses", "shed", "expired",
              "cancelled", "watchdog_abandoned", "quarantined",
              "dispatch_failures")}

    # every request in a burst completes when the burst does, so the
    # per-admitted-request latency IS the burst wall time
    capacity_rps = OVERLOAD_BURST * TIMED_ROUNDS / sum(cap_times)
    goodput_rps = (offered * TIMED_ROUNDS - sum(shed_per_round)) \
        / sum(over_times)
    p95_cap = float(np.percentile(cap_times, 95)) * 1e3
    p95_adm = float(np.percentile(over_times, 95)) * 1e3
    section = {
        "n_vertices": n, "burst": OVERLOAD_BURST, "offered": offered,
        "capacity_rps": capacity_rps, "goodput_rps": goodput_rps,
        "goodput_fraction": goodput_rps / capacity_rps,
        "uncontended_p95_ms": p95_cap, "admitted_p95_ms": p95_adm,
        "admitted_p95_ratio": p95_adm / p95_cap,
        "shed_per_round": shed_per_round,
        "steady_state_counters": delta,
        "queue_high_watermark": after["queue_high_watermark"],
    }
    excess = offered - OVERLOAD_BURST
    section["acceptance"] = {
        "goodput_ge_80pct_capacity": goodput_rps >= 0.8 * capacity_rps,
        "admitted_p95_within_2x_uncontended": p95_adm <= 2.0 * p95_cap,
        "sheds_exactly_the_excess": all(s == excess
                                        for s in shed_per_round),
        "admitted_steady_state_clean": (
            bad == 0 and all(delta[k] == 0 for k in
                             ("replans", "traces", "plan_misses",
                              "expired", "watchdog_abandoned",
                              "quarantined", "dispatch_failures"))),
        "queue_never_exceeds_bound": (after["queue_high_watermark"]
                                      <= OVERLOAD_BURST),
    }
    print(f"overload |V|={n}: capacity {capacity_rps:.1f} req/s, "
          f"goodput at {OVERLOAD_FACTOR}x load {goodput_rps:.1f} req/s "
          f"({section['goodput_fraction'] * 100:.0f}%), admitted p95 "
          f"{p95_adm:.0f} ms vs {p95_cap:.0f} ms uncontended "
          f"({section['admitted_p95_ratio']:.2f}x)")
    print("overload acceptance:", section["acceptance"])
    return section


STREAM_DRAG_N = 10000    # |V| of the dragged layout
STREAM_DRAG_FRAMES = 50  # timed per-frame updates


def stream_drag_section(base, rng):
    """Price the incremental path in the interactive-drag regime: ONE
    registered |V|=10k layout, one vertex dragged a small step per
    frame (the ``session.update`` stream a layout editor generates).
    Per-frame incremental latency vs a warm full re-evaluation of the
    SAME session (plan cache hot, jit cache hot — the honest baseline:
    what each frame would cost without the delta program).  The counter
    proof rides along: every timed frame must take the delta path
    (``delta_hits``) and perform zero cell builds / vertex sorts /
    strip builds / reversal sweeps (docs/incremental.md)."""
    from repro.core import grid as gridlib
    from repro.launch.session import EvalSession

    n = STREAM_DRAG_N
    pos, edges = make_graph(n)
    pos, edges = np.asarray(pos), np.asarray(edges)
    # threshold 1.0: the gate certifies delta-path latency; threshold
    # tuning is a separate policy (tests/test_incremental.py)
    sess = EvalSession(base, update_dirty_threshold=1.0)
    sess.register_layout("drag", pos, edges)
    # drag an interior vertex: a bounding-box-extremal vertex would
    # change the strip domain and legitimately fall back every frame
    c = (pos.min(axis=0) + pos.max(axis=0)) / 2
    v = int(np.argmin(((pos - c) ** 2).sum(axis=1)))
    cur = np.array(pos, copy=True)

    def drag_step():
        return rng.normal(0, 0.2, 2).astype(np.float32)

    # warm both paths: first update traces the delta program, first
    # evaluate warms the full path's jit entry for the moved layout
    tgt = cur[v] + drag_step()
    sess.update("drag", [v], [tgt])
    cur[v] = tgt
    sess.evaluate(cur, edges)

    before = dict(sess.stats)
    gridlib.reset_call_counts()
    frame_times = []
    for _ in range(STREAM_DRAG_FRAMES):
        tgt = cur[v] + drag_step()
        t0 = time.perf_counter()
        sess.update("drag", [v], [tgt])
        frame_times.append(time.perf_counter() - t0)
        cur[v] = tgt
    counts = dict(gridlib.CALL_COUNTS)
    after = dict(sess.stats)

    full_times = []
    for _ in range(5):
        t0 = time.perf_counter()
        sess.evaluate(cur, edges)
        full_times.append(time.perf_counter() - t0)

    update_p50 = float(np.median(frame_times)) * 1e3
    update_p95 = float(np.percentile(frame_times, 95)) * 1e3
    full_p50 = float(np.median(full_times)) * 1e3
    delta_hits = after["delta_hits"] - before["delta_hits"]
    fallbacks = after["delta_fallbacks"] - before["delta_fallbacks"]
    section = {
        "n_vertices": n, "n_edges": int(edges.shape[0]),
        "frames": STREAM_DRAG_FRAMES,
        "update_p50_ms": update_p50, "update_p95_ms": update_p95,
        "full_reeval_p50_ms": full_p50,
        "speedup": full_p50 / update_p50,
        "delta_hits": delta_hits, "delta_fallbacks": fallbacks,
        "build_counters": counts,
    }
    section["acceptance"] = {
        "update_10x_faster_than_full_reeval":
            section["speedup"] >= 10.0,
        "every_frame_incremental": (delta_hits == STREAM_DRAG_FRAMES
                                    and fallbacks == 0),
        "zero_rebuild_work": all(counts[k] == 0 for k in
                                 ("cell_builds", "vertex_sorts",
                                  "strip_builds", "reversal_sweeps")),
    }
    print(f"stream_drag |V|={n}: update {update_p50:.2f}/{update_p95:.2f} "
          f"ms (p50/p95) vs full {full_p50:.2f} ms — "
          f"{section['speedup']:.1f}x, {delta_hits}/{STREAM_DRAG_FRAMES} "
          f"frames incremental")
    print("stream_drag acceptance:", section["acceptance"])
    return section


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="{}",
                    help="JSON EvalConfig field overrides, e.g. "
                         '\'{"metrics": ["edge_crossing"]}\'')
    ap.add_argument("--validation-gate", action="store_true",
                    help="run only the validation_overhead section (the "
                         "CI cost gate on the fault-tolerance layer) and "
                         "merge it into BENCH_serve.json")
    ap.add_argument("--overload-gate", action="store_true",
                    help="run only the overload section (the CI gate on "
                         "admission control: goodput and admitted-p95 "
                         "under 2x offered load) and merge it into "
                         "BENCH_serve.json")
    ap.add_argument("--stream-drag-gate", action="store_true",
                    help="run only the stream_drag section (the CI gate "
                         "on incremental re-evaluation: per-frame "
                         "session.update latency vs warm full re-eval "
                         "at |V|=10k) and merge it into BENCH_serve.json")
    args = ap.parse_args(argv)
    use_compile_cache()
    overrides = json.loads(args.config)
    if "metrics" in overrides:
        overrides["metrics"] = tuple(overrides["metrics"])
    base = EvalConfig(**{"n_strips": N_STRIPS, **overrides})

    out = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "BENCH_serve.json"))
    # the validation section streams the two smaller sizes: host-side
    # validation cost is O(V + E) against a fixed dispatch cost, so the
    # overhead fraction is LARGEST on small graphs — gating there is the
    # conservative choice (and keeps the CI leg fast)
    val_sizes = tuple(n for n in SIZES if n <= 1000) or SIZES[:1]
    val_graphs = {n: (np.asarray(p), np.asarray(e)) for n, (p, e) in
                  ((n, make_graph(n)) for n in val_sizes)}
    if args.validation_gate or args.overload_gate or args.stream_drag_gate:
        sections = {}
        if args.validation_gate:
            sections["validation_overhead"] = validation_overhead(
                base, val_graphs, np.random.default_rng(0))
        if args.overload_gate:
            sections["overload"] = overload_section(
                base, val_graphs, np.random.default_rng(2))
        if args.stream_drag_gate:
            sections["stream_drag"] = stream_drag_section(
                base, np.random.default_rng(3))
        prior = {}
        if os.path.exists(out):
            with open(out) as f:
                prior = json.load(f)
        prior.update(sections)
        with open(out, "w") as f:
            json.dump(prior, f, indent=2)
        print(f"wrote {out}")
        if not all(ok for s in sections.values()
                   for ok in s["acceptance"].values()):
            sys.exit(1)
        return

    graphs = {n: make_graph(n) for n in SIZES}
    graphs = {n: (np.asarray(p), np.asarray(e)) for n, (p, e) in
              graphs.items()}
    rng = np.random.default_rng(0)
    results = {"backend": jax.default_backend(), "n_strips": base.n_strips,
               "config": {"digest": base.digest(),
                          "metrics": list(base.metrics)},
               "sizes": [], "stream": {}}

    eager = ReadabilityServer(dataclasses.replace(base, backend="eager"))
    sess = ReadabilityServer(base)

    def mixed_round(server):
        reqs = [(perturbed(graphs[n][0], rng, n), graphs[n][1])
                for n in SIZES for _ in range(PER_SIZE)]
        return server.evaluate_batch(reqs)

    # -- warmup the session server (compiles + plan cache fills) ----------
    for _ in range(WARMUP_ROUNDS):
        mixed_round(sess)
    warm = dict(sess.stats)

    # -- per-size p50 latency (single requests, steady state) -------------
    for n in SIZES:
        pos, edges = graphs[n]
        t_eager, t_eager95 = lat_ms(
            lambda: eager.evaluate(perturbed(pos, rng, n), edges),
            EAGER_REPS)
        t_sess, t_sess95 = lat_ms(
            lambda: sess.evaluate(perturbed(pos, rng, n), edges),
            SESSION_REPS)
        rec = {"n_vertices": n, "n_edges": int(edges.shape[0]),
               "eager_p50_ms": t_eager, "eager_p95_ms": t_eager95,
               "session_p50_ms": t_sess, "session_p95_ms": t_sess95,
               "speedup": t_eager / t_sess}
        results["sizes"].append(rec)
        print(f"|V|={n:5d}: eager {t_eager:8.1f}/{t_eager95:8.1f} ms "
              f"(p50/p95)  session {t_sess:7.1f}/{t_sess95:7.1f} ms  "
              f"speedup {rec['speedup']:.1f}x", flush=True)

    # -- mixed-size stream throughput (coalesced batches) -----------------
    before = dict(sess.stats)
    t0 = time.perf_counter()
    for _ in range(TIMED_ROUNDS):
        mixed_round(sess)
    dt = time.perf_counter() - t0
    after = dict(sess.stats)
    n_reqs = TIMED_ROUNDS * PER_SIZE * len(SIZES)
    delta = {k: after[k] - before[k] for k in
             ("replans", "traces", "plan_misses", "dispatches", "requests",
              "coalesced", "plan_hits")}
    eager_ms_per_round = sum(PER_SIZE * r["eager_p50_ms"]
                             for r in results["sizes"])
    results["stream"] = {
        "requests": n_reqs, "seconds": dt,
        "requests_per_sec": n_reqs / dt,
        "ms_per_request": dt / n_reqs * 1e3,
        "eager_requests_per_sec_est": (PER_SIZE * len(SIZES))
        / (eager_ms_per_round / 1e3),
        "steady_state_counters": delta,
        "warmup_stats": warm,
    }
    print(f"stream: {n_reqs} mixed requests in {dt:.2f}s "
          f"({n_reqs / dt:.1f} req/s; eager est "
          f"{results['stream']['eager_requests_per_sec_est']:.1f} req/s)")
    print(f"steady-state counters: {delta}")

    results["validation_overhead"] = validation_overhead(
        base, val_graphs, np.random.default_rng(1))
    results["overload"] = overload_section(
        base, val_graphs, np.random.default_rng(2))
    results["stream_drag"] = stream_drag_section(
        base, np.random.default_rng(3))

    by_size = {r["n_vertices"]: r for r in results["sizes"]}
    results["acceptance"] = {
        "session_5x_faster_at_1k": by_size[1000]["speedup"] >= 5.0,
        "zero_replans_after_warmup": delta["replans"] == 0,
        "zero_retraces_after_warmup": delta["traces"] == 0,
        "zero_plan_misses_after_warmup": delta["plan_misses"] == 0,
        "stream_coalesces": delta["coalesced"] == delta["requests"],
        **results["validation_overhead"]["acceptance"],
        **{f"overload_{k}": v
           for k, v in results["overload"]["acceptance"].items()},
        **{f"stream_drag_{k}": v
           for k, v in results["stream_drag"]["acceptance"].items()},
    }
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
    print("acceptance:", results["acceptance"])
    print(f"wrote {out}")
    if not all(results["acceptance"].values()):
        sys.exit(1)


if __name__ == "__main__":
    main()
