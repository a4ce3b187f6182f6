"""The ``select`` driver: a search loop scoring K candidates per call."""

from __future__ import annotations

import time

import numpy as np

from kit import host_scores, jittered, span
from window import whole_call_rate


class Driver:
    """Closed loop, one caller: ``batch`` candidate layouts per
    ``Evaluator.evaluate_batch`` call under one plan made in set-up.

    The plan is made from candidates drawn from the traffic's fixed
    ``plan_seed``, so program shapes depend on the configuration alone.
    The window cycles through a pool of ``pool`` batches drawn from
    ``--seed``."""

    def __init__(self, ctx):
        from repro.api import Evaluator

        t = ctx.traffic
        self.pos, self.edges = ctx.pos, ctx.edges
        self.k = int(t["batch"])
        sigma = float(t["jitter_spacings"]) * ctx.spacing
        self.ev = Evaluator(ctx.eval_config)
        plan_batch = jittered(np.random.default_rng(int(t["plan_seed"])),
                              self.pos, self.k, sigma)
        with span("bench.plan"):
            self.plan = self.ev.plan(plan_batch, self.edges)
        rng = np.random.default_rng(ctx.seed)
        self.pool = [jittered(rng, self.pos, self.k, sigma)
                     for _ in range(int(t["pool"]))]
        with span("bench.evaluate_batch"):
            self.ev.evaluate_batch(plan_batch, self.edges, plan=self.plan)
        self.sample = int(t["sample"])

    def window(self, seconds):
        calls, self.outs = [], []
        t0 = time.perf_counter()
        while not calls or calls[-1][1] - t0 < seconds:
            i = len(calls)
            s = time.perf_counter()
            with span("bench.evaluate_batch"):
                out = self.ev.evaluate_batch(self.pool[i % len(self.pool)],
                                             self.edges, plan=self.plan)
            calls.append((s, time.perf_counter()))
            self.outs.append(out)
        n = len(calls) * self.k
        failed = sum(int(np.sum(np.asarray(o.overflow) != 0))
                     for o in self.outs)
        return {"driver": "select", "attempted": n, "failed": failed,
                "calls": len(calls), "layouts": n,
                "elapsed_s": calls[-1][1] - calls[0][0],
                "first_call_s": calls[0][1] - calls[0][0],
                "median_call_s": float(np.median([e - s for s, e in calls])),
                "layouts_per_s": whole_call_rate(calls, self.k)}

    def answers(self, rng):
        picks = rng.choice(len(self.outs) * self.k,
                           size=min(self.sample, len(self.outs) * self.k),
                           replace=False)
        out = []
        for p in sorted(int(x) for x in picks):
            call, member = divmod(p, self.k)
            pos = self.pool[call % len(self.pool)][member]
            out.append((f"call {call} layout {member}", pos,
                        host_scores(self.outs[call], member)))
        return out
