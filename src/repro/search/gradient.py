"""Gradient-guided layout search over the differentiable engine.

:class:`GradientSearch` wires three pieces that already existed but had
never met: the natively batched readability engine (B candidate layouts
per dispatch), the AdamW optimizer in :mod:`repro.optim.adamw` (cosine
schedule, global-norm clipping — present since the seed but orphaned),
and the new differentiable relaxations in :mod:`repro.core.soft`.

One search step is ONE jitted dispatch: ``jax.value_and_grad`` of the
summed per-restart :func:`repro.core.soft.soft_loss` over the ``(B, V,
2)`` restart batch, followed by the AdamW update — so per-step cost is
~one ``evaluate_batch`` (forward + backward of the same bucketed
program; ``benchmarks/search_bench.py`` gates the ratio).  With
``backend="distributed"`` the forward+backward shards over the batch
axis exactly like :func:`repro.distributed.batched.evaluate_layouts_sharded`
(restart rows are independent, zero collectives); the optimizer update
then runs on the gathered global arrays, so sharded and single-host
searches take identical trajectories.

**Exact numbers are the reported numbers**: every ``rescore_every``
steps the current restarts are re-scored by the exact integer engine
(:func:`repro.core.engine.evaluate_layouts`), best-so-far candidates are
tracked by the mean of :meth:`ReadabilityScores.normalized` metric
fields, and :class:`SearchResult` carries only exact scores.  The soft
losses steer; they are never reported as readability.

Temperature anneals geometrically from ``EvalConfig.temperature`` (or
the ``temperature`` override) down to ``final_temperature`` across the
run; it enters the traced program as a device scalar, so annealing
never retraces (counter-proven in ``tests/test_search.py``).

Degenerate inputs route through the PR-6 validation taxonomy:
``validate_batch`` per ``EvalConfig.validation`` (typed
:class:`~repro.core.validate.InvalidInputError` on NaN layouts /
out-of-range edges), V=0 rejected as un-searchable, E=0 padded to one
masked edge row (the engine's usual degenerate contract) so gradients
stay finite and the occlusion term still optimizes.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine, soft
from repro.core.keys import EvalConfig
from repro.core.scores import ReadabilityScores
from repro.core.validate import InvalidInputError, validate_batch
from repro.optim import adamw

# The five normalized metric fields that enter the search objective
# (crossing_count_for_angle is E_ca's paired count, not a readability).
OBJECTIVE_FIELDS = ("node_occlusion", "minimum_angle",
                    "edge_length_variation", "edge_crossing",
                    "edge_crossing_angle")


def batch_objectives(batch_scores: ReadabilityScores) -> np.ndarray:
    """Scalar objective per layout: the mean of the normalized metric
    fields present (higher is better, in [0, 1]).  With unit
    :class:`~repro.core.soft.SoftWeights` this is exactly what
    :func:`~repro.core.soft.soft_loss` descends (up to the relaxation),
    so the exact ranking and the soft objective agree."""
    norm = batch_scores.normalized()
    vals = [np.asarray(getattr(norm, f), np.float64)
            for f in OBJECTIVE_FIELDS if getattr(norm, f) is not None]
    if not vals:
        raise ValueError("no metric fields present to rank by")
    return np.mean(np.stack(vals), axis=0)


class SearchResult(NamedTuple):
    """Outcome of a :class:`GradientSearch` run.

    All scores are **exact** integer-engine scores (host
    :class:`~repro.core.scores.ReadabilityScores`); ``positions`` /
    ``scores`` / ``objectives`` describe the best-so-far layout of each
    restart (selected by exact re-scoring, never by the soft loss).
    ``trajectory`` is one record per exact re-score: step, temperature,
    mean soft loss, mean/best exact objective.  ``counters`` carries the
    proof material (soft-path trace count, re-scores, replans).
    """

    positions: np.ndarray        # (B, V, 2) best-so-far per restart
    scores: tuple                # B host ReadabilityScores (exact)
    objectives: np.ndarray       # (B,) normalized objective per restart
    init_positions: np.ndarray   # (B, V, 2) the starting restarts
    init_scores: tuple           # B host ReadabilityScores of the starts
    init_objectives: np.ndarray  # (B,)
    trajectory: tuple            # per-rescore records (dicts)
    steps: int
    restarts: int
    counters: dict

    @property
    def best_index(self) -> int:
        return int(np.argmax(self.objectives))

    @property
    def best_positions(self) -> np.ndarray:
        return self.positions[self.best_index]

    @property
    def best_scores(self) -> ReadabilityScores:
        return self.scores[self.best_index]

    @property
    def best_objective(self) -> float:
        return float(self.objectives[self.best_index])

    @property
    def improvement(self) -> float:
        """Best final objective minus the best *initial* objective —
        what the search bought over just scoring the starts."""
        return self.best_objective - float(np.max(self.init_objectives))


class GradientSearch:
    """Gradient-guided readability search: B restarts, one dispatch/step.

    Parameters
    ----------
    config:
        The :class:`~repro.core.keys.EvalConfig` — plan geometry, metric
        subset, validation mode, starting ``temperature``, and backend
        (``"distributed"`` shards the per-step forward+backward over the
        batch axis; every other backend runs the single-host jit).
    steps, restarts, rescore_every:
        Optimization length, parallel restart count, and the exact
        re-scoring cadence (a final re-score always happens).
    opt:
        :class:`~repro.optim.adamw.AdamWConfig`.  Default: cosine
        schedule over ``steps`` with peak learning rate ``0.01`` x the
        layout extent, no weight decay (decay would shrink the layout
        toward the origin — that *raises* occlusion), clip_norm 1.0.
    weights:
        :class:`~repro.core.soft.SoftWeights` for the loss mix.
    temperature, final_temperature:
        Geometric annealing endpoints; default ``config.temperature``
        down one decade.
    jitter:
        Restart spread as a fraction of the layout extent (restart 0 is
        always the unperturbed seed layout).
    mesh:
        Device mesh for ``backend="distributed"`` (default: the serving
        bring-up policy, :func:`repro.launch.elastic.serving_mesh`).
    """

    def __init__(self, config: EvalConfig = None, *, steps: int = 100,
                 restarts: int = 8, rescore_every: int = 25,
                 opt: adamw.AdamWConfig = None,
                 weights: soft.SoftWeights = None,
                 temperature: float = None, final_temperature: float = None,
                 jitter: float = 0.05, seed: int = 0, mesh=None):
        self.config = config if config is not None else EvalConfig()
        if steps < 1:
            raise ValueError(f"steps must be >= 1, got {steps}")
        if restarts < 1:
            raise ValueError(f"restarts must be >= 1, got {restarts}")
        self.steps = int(steps)
        self.restarts = int(restarts)
        self.rescore_every = max(1, int(rescore_every))
        self.opt = opt
        self.weights = weights if weights is not None else soft.SoftWeights()
        t0 = (float(temperature) if temperature is not None
              else self.config.temperature)
        t1 = (float(final_temperature) if final_temperature is not None
              else t0 * 0.1)
        if not (t0 > 0 and t1 > 0):
            raise ValueError("temperatures must be > 0, got "
                             f"{t0!r} -> {t1!r}")
        self.temperature = t0
        self.final_temperature = t1
        self.jitter = float(jitter)
        self.seed = int(seed)
        self.mesh = mesh

    # -- pieces -------------------------------------------------------------

    def _temperature_at(self, k: int) -> float:
        """Geometric anneal: t0 at step 0, t1 at the last step."""
        frac = k / max(self.steps - 1, 1)
        return float(self.temperature
                     * (self.final_temperature / self.temperature) ** frac)

    def _mesh(self):
        if self.mesh is None:
            from repro.launch.elastic import serving_mesh
            self.mesh = serving_mesh("eval", shards=self.config.shards)
        return self.mesh

    def _init_batch(self, pos0, edges):
        """Restart batch from a seed layout (or an explicit batch) +
        validation through the taxonomy."""
        pos0 = np.asarray(pos0, np.float32)
        if pos0.ndim == 2:
            rng = np.random.default_rng(self.seed)
            extent = self._extent(pos0)
            batch = np.repeat(pos0[None], self.restarts, axis=0)
            if self.restarts > 1:
                noise = rng.standard_normal(
                    (self.restarts - 1,) + pos0.shape).astype(np.float32)
                batch[1:] += self.jitter * extent * noise
        elif pos0.ndim == 3:
            batch = pos0.copy()
            self.restarts = batch.shape[0]
        else:
            raise InvalidInputError(
                f"search wants a (V, 2) layout or a (B, V, 2) restart "
                f"batch; got shape {pos0.shape}")
        batch, edges, flags = validate_batch(
            batch, np.asarray(edges, np.int32),
            mode=self.config.validation)
        if batch.shape[1] == 0:
            raise InvalidInputError("cannot search over a layout with "
                                    "zero vertices")
        return batch, edges, flags

    @staticmethod
    def _extent(pos) -> float:
        flat = np.asarray(pos, np.float32).reshape(-1, 2)
        if flat.shape[0] == 0:
            return 1.0
        span = np.ptp(flat, axis=0)
        return float(max(span.max(), 1e-6))

    def _resolve_opt(self, extent: float) -> adamw.AdamWConfig:
        if self.opt is not None:
            return self.opt
        return adamw.AdamWConfig(
            peak_lr=0.01 * extent,
            warmup_steps=max(1, min(10, self.steps // 10)),
            total_steps=self.steps, min_lr_frac=0.1,
            weight_decay=0.0, clip_norm=1.0)

    def _make_step(self, plan, opt_cfg, mesh, valid_scalars):
        """The jitted search step: value_and_grad of the summed soft
        loss (optionally shard_mapped over the batch axis), then one
        AdamW update on the global arrays.  Temperature is a traced
        argument — the annealing schedule reuses ONE trace."""
        weights = self.weights
        lr_fn = adamw.cosine_schedule(opt_cfg)

        def loss_fn(pos, edges, tau, *valid):
            losses = soft.soft_loss(plan, pos, edges, tau, weights=weights,
                                    n_valid_vertices=valid[0] if valid
                                    else None,
                                    n_valid_edges=valid[1] if valid
                                    else None)
            return jnp.sum(losses), losses

        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

        if mesh is None:
            def value_and_grad(pos, edges, tau, *valid):
                (_, losses), grads = grad_fn(pos, edges, tau, *valid)
                return losses, grads
        else:
            from jax.sharding import PartitionSpec as P
            from jax import shard_map
            axes = tuple(mesh.axis_names)

            def shard_fn(pos_shard, edges_rep, tau, *valid):
                # per-restart losses and gradients are batch-row-local
                # (same argument as evaluate_layouts_sharded: every
                # bucketing sort is per-row, every reduction per-layout)
                # so the fwd+bwd shards with zero collectives
                (_, losses), grads = grad_fn(pos_shard, edges_rep, tau,
                                             *valid)
                return losses, grads

            sharded = shard_map(
                shard_fn, mesh=mesh,
                in_specs=(P(axes), P(), P()) + tuple(P() for _ in
                                                     valid_scalars),
                out_specs=(P(axes), P(axes)), check_vma=False)

            def value_and_grad(pos, edges, tau, *valid):
                return sharded(pos, edges, tau, *valid)

        def step_fn(pos, m, v, step, edges, tau, *valid):
            losses, grads = value_and_grad(pos, edges, tau, *valid)
            params = {"pos": pos}
            state = {"m": {"pos": m}, "v": {"pos": v}, "step": step}
            new_params, new_state, om = adamw.apply_updates(
                params, {"pos": grads}, state, opt_cfg, lr_fn)
            return (new_params["pos"], new_state["m"]["pos"],
                    new_state["v"]["pos"], new_state["step"], losses,
                    om["grad_norm"])

        return jax.jit(step_fn)

    def _exact_rescore(self, plan, pos_dev, edges_eval, mesh,
                       valid_scalars, n_v, n_e):
        """Exact integer scores of the current restarts (the reported
        numbers), single-host or batch-axis sharded."""
        if mesh is not None:
            from repro.distributed.batched import evaluate_layouts_sharded
            nv = valid_scalars[0] if valid_scalars else None
            ne = valid_scalars[1] if valid_scalars else None
            res = evaluate_layouts_sharded(mesh, plan, pos_dev, edges_eval,
                                           n_valid_vertices=nv,
                                           n_valid_edges=ne)
        else:
            res = engine.evaluate_layouts(plan, pos_dev, edges_eval,
                                          *valid_scalars)
        return jax.device_get(res)._replace(n_vertices=n_v, n_edges=n_e)

    # -- the run ------------------------------------------------------------

    def run(self, pos0, edges) -> SearchResult:
        """Search from ``pos0`` (a ``(V, 2)`` seed layout, jittered into
        ``restarts`` parallel starts, or an explicit ``(B, V, 2)``
        restart batch).  Returns a :class:`SearchResult` of exact
        scores; ``result.best_positions`` is the winning layout."""
        batch, edges_nat, flags = self._init_batch(pos0, edges)
        n_v, n_e = batch.shape[1], edges_nat.shape[0]

        # E=0: the engine's degenerate contract — one masked edge row,
        # n_valid scalars mask it everywhere (finite zero gradients for
        # the edge metrics; occlusion still optimizes)
        valid_scalars = ()
        edges_eval = edges_nat
        if n_e == 0:
            edges_eval = np.zeros((1, 2), np.int32)
            valid_scalars = (jnp.asarray(n_v, jnp.int32),
                             jnp.asarray(0, jnp.int32))

        mesh = None
        if self.config.backend == "distributed":
            mesh = self._mesh()
            pad = (-batch.shape[0]) % mesh.size
            if pad:
                # pad the restart population to the mesh size with extra
                # jittered starts — free diversity instead of dead rows
                rng = np.random.default_rng(self.seed + 1)
                extent = self._extent(batch)
                noise = rng.standard_normal(
                    (pad,) + batch.shape[1:]).astype(np.float32)
                batch = np.concatenate(
                    [batch, batch[:1] + self.jitter * extent * noise])
                self.restarts = batch.shape[0]

        plan = engine.plan_readability(batch, edges_eval,
                                       **self.config.plan_kwargs())
        opt_cfg = self._resolve_opt(self._extent(batch))
        step = self._make_step(plan, opt_cfg, mesh, valid_scalars)

        edges_dev = jnp.asarray(edges_eval, jnp.int32)
        pos = jnp.asarray(batch, jnp.float32)
        m = jnp.zeros_like(pos)
        v = jnp.zeros_like(pos)
        step_count = jnp.zeros((), jnp.int32)

        counters = {"rescores": 0, "replans": 0,
                    "soft_traces_before": soft.trace_count()}

        def rescore(pos_dev, cur_plan):
            counters["rescores"] += 1
            res = self._exact_rescore(cur_plan, pos_dev, edges_dev, mesh,
                                      valid_scalars, n_v, n_e)
            if int(np.max(res.overflow)) > 0:
                # the layouts drifted past the plan's capacities: grow
                # the plan from the offending batch and re-dispatch once
                # (replan_on_overflow floors capacities to cover it)
                counters["replans"] += 1
                cur_plan = engine.replan_on_overflow(
                    cur_plan, np.asarray(pos_dev), edges_eval, res)
                res = self._exact_rescore(cur_plan, pos_dev, edges_dev,
                                          mesh, valid_scalars, n_v, n_e)
            return res, cur_plan

        init_res, plan = rescore(pos, plan)
        init_obj = batch_objectives(init_res)
        init_scores = tuple(init_res.unbatch())
        best_obj = init_obj.copy()
        best_pos = np.asarray(batch, np.float32).copy()
        best_scores = list(init_scores)
        trajectory = [dict(step=0, temperature=self._temperature_at(0),
                           mean_soft_loss=None,
                           mean_objective=float(init_obj.mean()),
                           best_objective=float(best_obj.max()))]

        replanned = False
        for k in range(self.steps):
            tau = jnp.asarray(self._temperature_at(k), jnp.float32)
            if replanned:
                # a grown plan is a new static arg: rebuild the step
                step = self._make_step(plan, opt_cfg, mesh, valid_scalars)
                replanned = False
            pos, m, v, step_count, losses, _ = step(
                pos, m, v, step_count, edges_dev, tau, *valid_scalars)
            last = k == self.steps - 1
            if last or (k + 1) % self.rescore_every == 0:
                plan_before = plan
                res, plan = rescore(pos, plan)
                replanned = plan is not plan_before
                obj = batch_objectives(res)
                scores_list = res.unbatch()
                pos_np = np.asarray(pos)
                improved = obj > best_obj
                for i in np.flatnonzero(improved):
                    best_obj[i] = obj[i]
                    best_pos[i] = pos_np[i]
                    best_scores[i] = scores_list[i]
                trajectory.append(dict(
                    step=k + 1, temperature=float(tau),
                    mean_soft_loss=float(np.mean(np.asarray(losses))),
                    mean_objective=float(obj.mean()),
                    best_objective=float(best_obj.max())))

        counters["soft_traces"] = (soft.trace_count()
                                   - counters.pop("soft_traces_before"))
        if flags:
            counters["validation_flags"] = flags
        return SearchResult(
            positions=best_pos, scores=tuple(best_scores),
            objectives=best_obj, init_positions=batch,
            init_scores=init_scores, init_objectives=init_obj,
            trajectory=tuple(trajectory), steps=self.steps,
            restarts=self.restarts, counters=counters)
