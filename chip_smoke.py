#!/usr/bin/env python3
"""Bring-up check: run the readability engine's main path on a TPU.

Drives the user entry points (``Evaluator``, ``EvalSession``) once, at
the sizes users run, and checks every result by the repository's own
means: integer metrics (N_c, E_c, the E_ca crossing count) bit-identical
across paths and against the exact reference, no capacity overflow, no
degraded dispatch.  One line per phase gives its sizes, compile seconds
(first call: trace + compile + one run) and warm seconds.  These timings
are a bring-up record, not benchmark numbers.

The last line of standard output is the contract::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

It refuses to run on anything but a TPU (no CPU fallback) and exits
non-zero on any failed check.  The persistent compilation cache lives
where ``JAX_COMPILATION_CACHE_DIR`` says, else in ``.jax_cache/``.

  python chip_smoke.py               # one chip: the single-chip phases
  python chip_smoke.py --four-chips  # four chips: graph-sharded and
                                     # batch-sharded, each against its
                                     # one-chip reference
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import EvalConfig, EvalSession, Evaluator  # noqa: E402
from repro.core import engine  # noqa: E402
from repro.core.metrics import evaluate_exact  # noqa: E402
from repro.graphs.layouts import lattice_graph  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402

INT_FIELDS = ("node_occlusion", "edge_crossing", "crossing_count_for_angle")
N_STRIPS = 256


class SmokeFailure(Exception):
    pass


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def ints(scores, i=None) -> dict:
    """Integer metrics of one layout (``i`` indexes a batched result)."""
    if i is None:
        return {f: int(getattr(scores, f)) for f in INT_FIELDS}
    return {f: int(np.asarray(getattr(scores, f))[i]) for f in INT_FIELDS}


def jittered(pos, n: int, seed: int, spacing: float = 0.3):
    """``n`` candidate layouts: ``pos`` moved by ~``spacing`` lattice
    spacings (layouts as an optimization loop produces them)."""
    rng = np.random.default_rng(seed)
    sigma = spacing * 100.0 / np.sqrt(pos.shape[0])
    return pos + rng.normal(0, sigma, (n,) + pos.shape).astype(np.float32)


def report(name: str, **fields) -> None:
    print(f"phase {name}: " + json.dumps(fields), flush=True)


def phase_batch(n_v=100_000, batch=32):
    """Batch scoring: B candidate layouts, one dispatch per call."""
    pos, edges = lattice_graph(n_v, seed=0)
    layouts = jittered(pos, batch, seed=1)
    ev = Evaluator(EvalConfig(n_strips=N_STRIPS))
    plan, plan_s = timed(lambda: ev.plan(layouts, edges))
    scores, compile_s = timed(
        lambda: ev.evaluate_batch(layouts, edges, plan=plan))
    warm = [timed(lambda: ev.evaluate_batch(layouts, edges, plan=plan))[1]
            for _ in range(3)]
    check(scores.ok, "batch scores carry an error")
    check(np.all(np.asarray(scores.overflow) == 0),
          f"batch overflow {np.asarray(scores.overflow).tolist()}")
    single, single_s = timed(lambda: ev.evaluate(layouts[0], edges))
    check(single.ok and single.overflow == 0, "single-layout score not ok")
    check(ints(single) == ints(scores, 0),
          f"layout 0: batched {ints(scores, 0)} != single {ints(single)}")
    report("batch", B=batch, V=n_v, E=int(edges.shape[0]), n_strips=N_STRIPS,
           plan_s=plan_s, compile_s=compile_s, warm_s=warm,
           single_compile_s=single_s, layout0=ints(single))


def phase_exact(n_v=10_000):
    """Fused estimate vs the plain all-pairs reference."""
    pos, edges = lattice_graph(n_v, seed=2)
    cfg = EvalConfig(n_strips=N_STRIPS)
    fused, compile_s = timed(lambda: Evaluator(cfg).evaluate(pos, edges))
    exact, exact_s = timed(lambda: evaluate_exact(pos, edges, config=cfg))
    check(fused.ok and fused.overflow == 0, "fused score not ok")
    check(fused.node_occlusion == exact.node_occlusion,
          f"N_c fused {fused.node_occlusion} != exact {exact.node_occlusion}")
    report("exact", V=n_v, E=int(edges.shape[0]), compile_s=compile_s,
           exact_s=exact_s, N_c=fused.node_occlusion,
           E_c=[fused.edge_crossing, exact.edge_crossing],
           E_ca=[fused.edge_crossing_angle, exact.edge_crossing_angle])


def phase_serving(sizes=(1_000, 3_000, 10_000), mix=(6, 5, 5), rounds=4):
    """A session serving mixed-topology request rounds after warm-up."""
    graphs = [lattice_graph(n, seed=10 + i) for i, n in enumerate(sizes)]

    def requests(seed):
        return [(jittered(pos, 1, seed=seed * 100 + k, spacing=0.2)[0], edges)
                for (pos, edges), m in zip(graphs, mix) for k in range(m)]

    sess = EvalSession(EvalConfig(n_strips=N_STRIPS))
    first, compile_s = timed(lambda: sess.evaluate_batch(requests(0)))
    check(all(s.ok for s in first), "warm-up request failed")
    before = sess.stats
    warm, served = [], 0
    for r in range(1, rounds + 1):
        out, t = timed(lambda: sess.evaluate_batch(requests(r)))
        check(all(s.ok and s.overflow == 0 for s in out),
              f"round {r}: a request failed or overflowed")
        warm.append(t)
        served += len(out)
    after = sess.stats
    delta = {k: after[k] - before[k]
             for k in ("replans", "traces", "degraded_dispatches")}
    check(delta == {"replans": 0, "traces": 0, "degraded_dispatches": 0},
          f"steady state moved: {delta}")
    report("serving", V=list(sizes), requests_per_round=sum(mix),
           requests=served, compile_s=compile_s, warm_round_s=warm,
           steady=delta, dispatches=after["dispatches"] - before["dispatches"])


def phase_incremental(n_v=10_000, frames=10, moved=4):
    """Drag frames on a registered layout: delta path, exact ints."""
    pos, edges = lattice_graph(n_v, seed=4)
    ev = Evaluator(EvalConfig(n_strips=N_STRIPS))
    _, register_s = timed(lambda: ev.register_layout("drag", pos, edges))
    cur = pos.copy()
    rng = np.random.default_rng(5)
    step = 0.3 * 100.0 / np.sqrt(n_v)
    # drag vertices away from the box edge whose edges are all lattice
    # edges (a long edge dirties most strips: a full re-evaluation)
    length = np.linalg.norm(pos[edges[:, 0]] - pos[edges[:, 1]], axis=1)
    on_long = np.unique(edges[length > 10 * step])
    inner = ((pos > 15.0) & (pos < 85.0)).all(axis=1)
    inner[on_long] = False
    interior = np.flatnonzero(inner)
    frame_s = []
    for f in range(frames):
        idx = rng.choice(interior, moved, replace=False)
        new = cur[idx] + rng.normal(0, step, (moved, 2)).astype(np.float32)
        got, t = timed(lambda: ev.update("drag", idx, new))
        frame_s.append(t)
        cur[idx] = new
        check(got.ok and (got.flags or {}).get("incremental"),
              f"frame {f} missed the delta path: {got.flags}")
        ref = ev.evaluate(cur, edges)
        check(ints(got) == ints(ref),
              f"frame {f}: delta {ints(got)} != from scratch {ints(ref)}")
    report("incremental", V=n_v, frames=frames, moved_per_frame=moved,
           register_s=register_s, frame_s=frame_s)


def phase_kernels(n_v=10_000):
    """The Pallas kernel route, compiled (never interpreted) on TPU."""
    pos, edges = lattice_graph(n_v, seed=6)
    cfg = EvalConfig(n_strips=N_STRIPS)
    kcfg = EvalConfig(n_strips=N_STRIPS, backend="kernels")
    got, compile_s = timed(lambda: Evaluator(kcfg).evaluate(pos, edges))
    want = Evaluator(cfg).evaluate(pos, edges)
    check(got.ok and ints(got) == ints(want),
          f"kernels {ints(got)} != fused {ints(want)}")
    ex_k, exact_k_s = timed(
        lambda: evaluate_exact(pos, edges, config=cfg, use_kernels=True))
    ex_j = evaluate_exact(pos, edges, config=cfg)
    check(ex_k.node_occlusion == ex_j.node_occlusion,
          f"exact N_c kernels {ex_k.node_occlusion} != jnp "
          f"{ex_j.node_occlusion}")
    plan = engine.plan_readability(pos, edges, **cfg.plan_kwargs())
    text = engine.evaluate_planned.lower(plan, pos, edges,
                                         use_kernels=True).as_text()
    check("tpu_custom_call" in text,
          "the kernel route lowered without a Mosaic custom call")
    report("kernels", V=n_v, E=int(edges.shape[0]), compile_s=compile_s,
           exact_kernels_s=exact_k_s, ints=ints(got),
           exact_E_c=[ex_k.edge_crossing, ex_j.edge_crossing])


def phase_search(n_v=10_000, steps=3):
    """Gradient layout search: a few AdamW steps, exact re-scores."""
    pos, edges = lattice_graph(n_v, seed=8)
    res, search_s = timed(lambda: Evaluator(EvalConfig(
        n_strips=N_STRIPS)).search(pos, edges, steps=steps))
    check(np.isfinite(res.objectives).all(), "non-finite objective")
    for s in res.scores:
        vals = [getattr(s, f) for f in ("minimum_angle",
                                        "edge_length_variation",
                                        "edge_crossing_angle")]
        check(s.ok and np.isfinite(vals).all(), f"non-finite score {s}")
    report("search", V=n_v, steps=res.steps, restarts=res.restarts,
           seconds=search_s,
           objectives=[float(o) for o in res.objectives])


def phase_graph_sharded(n_v=1_000_000, n_strips=2048):
    """One layout spatially partitioned over all chips vs one chip."""
    pos, edges = lattice_graph(n_v, seed=0, frac_long=0.02 * 10_000 / n_v)
    sess = EvalSession(EvalConfig(n_strips=n_strips,
                                  backend="graph_sharded"))
    got, compile_s = timed(lambda: sess.evaluate(pos, edges))
    _, warm_s = timed(lambda: sess.evaluate(pos, edges))
    ref, ref_s = timed(lambda: EvalSession(EvalConfig(
        n_strips=n_strips)).evaluate(pos, edges))
    stats, health = sess.stats, sess.health()
    check(sess.mesh.size == len(jax.devices()),
          f"mesh of {sess.mesh.size} devices")
    check(stats["graph_sharded_dispatches"] > 0
          and stats["degraded_dispatches"] == 0
          and health["breaker_state"] == "closed",
          f"graph-sharded rung not serving: {stats} {health}")
    check(got.ok and got.overflow == 0 and ref.overflow == 0,
          "graph-sharded or reference overflowed")
    check(ints(got) == ints(ref),
          f"graph-sharded {ints(got)} != one chip {ints(ref)}")
    report("graph_sharded", V=n_v, E=int(edges.shape[0]), n_strips=n_strips,
           devices=sess.mesh.size, compile_s=compile_s, warm_s=warm_s,
           one_chip_compile_s=ref_s, ints=ints(got),
           graph_sharded_dispatches=stats["graph_sharded_dispatches"])


def phase_batch_sharded(n_v=10_000, batch=30):
    """Batch axis sharded over all chips (B not a multiple of the chip
    count) vs the one-chip batched program."""
    pos, edges = lattice_graph(n_v, seed=0)
    layouts = jittered(pos, batch, seed=1)
    ev = Evaluator(EvalConfig(n_strips=N_STRIPS, backend="distributed"))
    plan = ev.plan(layouts, edges)
    got, compile_s = timed(
        lambda: ev.evaluate_batch(layouts, edges, plan=plan))
    _, warm_s = timed(lambda: ev.evaluate_batch(layouts, edges, plan=plan))
    ref = jax.device_get(engine.evaluate_layouts(plan, layouts, edges))
    check(ev.mesh.size == len(jax.devices()), f"mesh of {ev.mesh.size}")
    check(np.all(np.asarray(got.overflow) == 0), "sharded batch overflowed")
    for f in INT_FIELDS:
        check(np.array_equal(np.asarray(getattr(got, f)),
                             np.asarray(getattr(ref, f))),
              f"{f}: sharded != one chip")
    report("batch_sharded", B=batch, V=n_v, devices=ev.mesh.size,
           compile_s=compile_s, warm_s=warm_s)


SINGLE_CHIP = (phase_batch, phase_exact, phase_serving, phase_incremental,
               phase_kernels, phase_search)
FOUR_CHIPS = (phase_graph_sharded, phase_batch_sharded)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the two sharded phases, each against "
                         "its one-chip reference (needs 4 chips)")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform}); refusing to "
              "run on anything else", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {use_compile_cache()}", flush=True)
    for phase in (FOUR_CHIPS if args.four_chips else SINGLE_CHIP):
        name = phase.__name__[len("phase_"):]
        try:
            phase()
        except Exception as err:
            print(f"chip_smoke: phase {name} FAILED: "
                  f"{type(err).__name__}: {err}", file=sys.stderr)
            raise
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
