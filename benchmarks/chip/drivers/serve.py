"""The ``serve`` driver: an open loop into a stateless scoring session."""

from __future__ import annotations

import time

import numpy as np

from kit import host_scores, jittered, span
from window import due_latencies


class Driver:
    """Open loop: requests arrive at ``rate_per_s``, each one jittered
    candidate plus the edge list, as a stateless scoring service receives
    them.  The arrival times are one draw of a Poisson process, fixed
    by the traffic's ``arrival_seed``: ``rate * seconds`` arrivals whose
    ``count + 1`` exponential gaps are scaled to fill the window.
    ``--seed`` draws the candidates and which one each request carries,
    so every seed offers the same load, and what is left between runs
    is the spread of the queue itself.

    One server loop drains the requests that are due into one
    ``EvalSession.evaluate_batch`` call, which coalesces them into pow2
    widths up to ``max_coalesce``.  Set-up plans from a candidate drawn
    from the fixed ``plan_seed`` and warms every pow2 width."""

    def __init__(self, ctx):
        from repro.api import EvalSession

        t = ctx.traffic
        self.pos, self.edges = ctx.pos, ctx.edges
        self.rate = float(ctx.rate if ctx.rate is not None
                          else t["rate_per_s"])
        sigma = float(t["jitter_spacings"]) * ctx.spacing
        self.session = EvalSession(ctx.eval_config,
                                   max_coalesce=int(t["max_coalesce"]))
        warm = jittered(np.random.default_rng(int(t["plan_seed"])),
                        self.pos, 1, sigma)[0]
        w = 1
        while w <= int(t["max_coalesce"]):
            with span("bench.session_dispatch"):
                self.session.evaluate_batch([(warm, self.edges)] * w)
            w *= 2
        rng = np.random.default_rng(ctx.seed)
        self.pool = jittered(rng, self.pos, int(t["pool"]), sigma)
        self.seed_rng = rng
        self.arrival_seed = int(t["arrival_seed"])
        self.sample = int(t["sample"])
        self.max_wait = float(t.get("drain_limit_s", 60.0))

    def window(self, seconds):
        rng = self.seed_rng
        n = max(1, int(round(self.rate * seconds)))
        gaps = np.random.default_rng(self.arrival_seed).exponential(
            size=n + 1)
        due = np.cumsum(gaps)[:n] * (seconds / gaps.sum())
        self.which = rng.integers(len(self.pool), size=n)
        done, start = [None] * n, [None] * n
        self.outs = [None] * n
        late = []
        before = self.session.stats
        t0 = time.perf_counter()
        nxt = 0
        while nxt < n:
            now = time.perf_counter() - t0
            if now > seconds + self.max_wait:
                break
            if due[nxt] > now:
                time.sleep(due[nxt] - now)
                late.append(time.perf_counter() - t0 - due[nxt])
                continue
            hi = int(np.searchsorted(due, now, side="right"))
            reqs = [(self.pool[self.which[i]], self.edges)
                    for i in range(nxt, hi)]
            s = time.perf_counter() - t0
            with span("bench.session_dispatch"):
                outs = self.session.evaluate_batch(reqs)
            e = time.perf_counter() - t0
            for i, o in zip(range(nxt, hi), outs):
                start[i] = s
                self.outs[i] = o
                if o.ok:
                    done[i] = e
            nxt = hi
        end = time.perf_counter() - t0
        after = self.session.stats
        lat = due_latencies(due, done)
        wait = [np.inf if s is None else s - d for d, s in zip(due, start)]
        delta = {k: after[k] - before[k]
                 for k in ("requests", "dispatches", "coalesced", "replans",
                           "traces", "degraded_dispatches", "quarantined")}
        return {"driver": "serve", "attempted": n,
                "failed": sum(d is None for d in done),
                "requests": n, "rate_per_s": self.rate,
                "latency_ms": [x * 1e3 for x in lat],
                "queue_wait_ms": [x * 1e3 for x in wait],
                "lateness_ms": [x * 1e3 for x in late],
                "drain_s": end - seconds,
                "stats": delta}

    def answers(self, rng):
        served = [i for i, o in enumerate(self.outs) if o is not None]
        picks = rng.choice(len(served), size=min(self.sample, len(served)),
                           replace=False)
        out = []
        for p in sorted(int(x) for x in picks):
            i = served[p]
            o = self.outs[i]
            out.append((f"request {i}", self.pool[self.which[i]],
                        host_scores(o) if o.ok else None))
        return out
