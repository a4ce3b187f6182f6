"""The ``drag`` driver: an editor's drag frames on one registered layout."""

from __future__ import annotations

import time

import numpy as np

from kit import host_scores, span


class Driver:
    """Closed loop of ``Evaluator.update`` frames from an editor's drag
    gestures on one registered layout.

    A gesture picks a vertex uniformly and its ``m - 1`` nearest
    vertices (a lasso selection), then moves them ``step_spacings``
    spacings per frame in one direction for ``frames_out`` frames, and
    back along the same path to where they started.  Gestures stay
    ``margin_spacings`` inside the layout's extent (a draw whose path
    leaves it is drawn again): a drag that moves an extremal vertex
    shifts every strip boundary, and the program then re-evaluates in
    full, which is another path than the one this mix measures.  The
    set of selection sizes is fixed by ``gesture_counts`` (one count per
    size in ``selection_sizes``); the seed draws the vertices, the
    directions and the order.  The window replays the gestures in a
    cycle; since every gesture ends where it began, each pass of the
    cycle repeats the same frames, and set-up runs one whole pass so
    that every dirty-set shape of the window is compiled before it."""

    def __init__(self, ctx):
        from repro.api import Evaluator
        from scipy.spatial import cKDTree

        t = ctx.traffic
        self.pos, self.edges = ctx.pos, ctx.edges
        self.ev = Evaluator(ctx.eval_config)
        with span("bench.register"):
            self.ev.register_layout("drag", self.pos, self.edges)
        rng = np.random.default_rng(ctx.seed)
        sizes = np.repeat(np.asarray(t["selection_sizes"], np.int64),
                          np.asarray(t["gesture_counts"], np.int64))
        sizes = sizes[rng.permutation(sizes.size)]
        tree = cKDTree(self.pos)
        step = float(t["step_spacings"]) * ctx.spacing
        n_out = int(t["frames_out"])
        margin = float(t["margin_spacings"]) * ctx.spacing
        lo = self.pos.min(axis=0) + margin
        hi = self.pos.max(axis=0) - margin
        path = np.r_[np.arange(1, n_out + 1), np.arange(n_out - 1, -1, -1)]
        self.frames = []            # (selection, (m, 2) new positions)
        for m in sizes:
            while True:
                v = int(rng.integers(self.pos.shape[0]))
                _, sel = tree.query(self.pos[v], k=int(m))
                sel = np.atleast_1d(sel).astype(np.int64)
                ang = rng.uniform(0.0, 2 * np.pi)
                d = np.array([np.cos(ang), np.sin(ang)]) * step
                base = self.pos[sel].astype(np.float64)
                far = base + n_out * d
                if np.all((base >= lo) & (base <= hi) & (far >= lo)
                          & (far <= hi)):
                    break
            for k in path:
                self.frames.append(
                    (sel, (base + k * d).astype(np.float32)))
        self._run(len(self.frames), warm=True)
        self.sample = int(t["sample"])

    def _run(self, n, seconds=None, warm=False):
        times, outs = [], []
        t0 = time.perf_counter()
        i = 0
        while (i < n) if seconds is None else \
                (not times or time.perf_counter() - t0 < seconds):
            sel, new = self.frames[i % len(self.frames)]
            s = time.perf_counter()
            with span("bench.update"):
                out = self.ev.update("drag", sel, new)
            times.append(time.perf_counter() - s)
            if not warm:
                outs.append(out)
            i += 1
        return times, outs

    def window(self, seconds):
        times, self.outs = self._run(None, seconds)
        hits = sum(bool((o.flags or {}).get("incremental")) for o in self.outs)
        by_size = {}
        for i, o in enumerate(self.outs):
            m = len(self.frames[i % len(self.frames)][0])
            n, h, ms = by_size.get(m, (0, 0, []))
            by_size[m] = (n + 1, h + bool((o.flags or {}).get("incremental")),
                          ms + [times[i] * 1e3])
        failed = sum(int(not o.ok or o.overflow != 0) for o in self.outs)
        return {"driver": "drag", "attempted": len(times), "failed": failed,
                "frames": len(times), "frame_ms": [t * 1e3 for t in times],
                "delta_hits": hits,
                "by_selection": {m: {"frames": n, "delta_hits": h,
                                     "ms_median": float(np.median(ms)),
                                     "ms_max": max(ms)}
                                 for m, (n, h, ms) in sorted(by_size.items())}}

    def positions_at(self, frame):
        """The whole layout after frame ``frame`` of the cycle."""
        pos = self.pos.copy()
        sel, new = self.frames[frame % len(self.frames)]
        pos[sel] = new
        return pos

    def answers(self, rng):
        picks = rng.choice(len(self.outs), size=min(self.sample,
                                                    len(self.outs)),
                           replace=False)
        return [(f"frame {int(p)}", self.positions_at(int(p)),
                 host_scores(self.outs[int(p)])) for p in sorted(picks)]
