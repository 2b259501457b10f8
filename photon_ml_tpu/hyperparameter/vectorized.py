"""Vectorized hyperparameter sweeps: K candidates, one compiled program.

The search tier (`gp.py`, `search.py`, `game_evaluation.py`) used to pay a
full isolated GAME fit per candidate — XLA compilation, dataset staging and
cold solver iterations re-bought per point, the exact dispatch-amortization
failure the repo already cured elsewhere (SolveBudget's traced operands,
shape-keyed chunk programs).  This module applies the same discipline to
the sweep axis itself:

  * regularization weights ride into the compiled solvers as TRACED
    OPERANDS (`optim.schedule.RegWeights`) — changing lambda or the
    elastic-net mix never retraces;
  * where shapes allow, the candidate axis becomes a `jax.vmap` axis: K
    candidates' block-coordinate descents run as ONE device program per
    (coordinate, visit) against ONE staged copy of the training data
    (the vmap lane, `evaluate_vmapped`);
  * where they don't (streamed/mesh/factored coordinates), candidates run
    sequentially along the SORTED regularization path, strong-to-weak,
    each warm-started from its neighbor's solution over the SAME prepared
    coordinates (the path lane, `evaluate_path`) — still zero fresh traces
    after the first candidate, because only traced operands change.

`SweepEvaluator` is the shared-state owner: coordinates, entity bucketing,
normalization stats, and validation staging are built ONCE and reused by
every candidate — the per-candidate rebuild in
`GameEstimatorEvaluationFunction` routes through here.

Memory math for the vmap lane: the data stays 1x (unmapped vmap operands
broadcast, they are not copied per lane), while per-candidate state scales
Kx — coefficients (K*d fixed effect, K*E*d_local per random effect), the
[K, n] residual score vectors (one per coordinate plus the running total),
and the solver's per-lane work buffers.  With per-device budget B and
1x-fit flat-vector footprint f, K is bounded by roughly
(B - data_bytes) / (f + coefficient_bytes).

Telemetry: `sweep.candidates` counts candidates entering either lane;
`sweep.dispatches` counts device program dispatches the vmap lane issued —
candidates/dispatches >> 1 is the sublinearity
tests/test_sweep.py::test_sweep_telemetry_counters holds.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import telemetry
from photon_ml_tpu.data.game_data import GameDataset
from photon_ml_tpu.game.config import GameTrainingConfig
from photon_ml_tpu.game.coordinate_descent import (
    CoordinateDescentResult, TrackerSummary, _reason_counts,
    run_coordinate_descent,
)
from photon_ml_tpu.game.coordinates import (
    FixedEffectCoordinate, RandomEffectCoordinate,
)
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.game import (
    FixedEffectModel, GameModel, RandomEffectModel,
)
from photon_ml_tpu.models.glm import model_for_task
from photon_ml_tpu.ops import TASK_LOSSES, GLMObjective
from photon_ml_tpu.ops import features as fops
from photon_ml_tpu.optim import RegularizationType, solve
from photon_ml_tpu.optim.schedule import RegWeights


def _host_split(reg, weight: float) -> Tuple[float, float]:
    """reg.split as pure host arithmetic (reg.split stages device scalars;
    the sweep batches K splits into one [K] transfer instead)."""
    w = float(weight)
    if reg.reg_type == RegularizationType.NONE:
        return 0.0, 0.0
    if reg.reg_type == RegularizationType.L1:
        return w, 0.0
    if reg.reg_type == RegularizationType.L2:
        return 0.0, w
    a = float(reg.elastic_net_alpha)
    return a * w, (1.0 - a) * w


# -- cached candidate-axis programs -------------------------------------------
#
# One compiled program per static signature, shared by every SweepEvaluator
# (module-level lru_cache, the _cached_solver idiom): a sweep's warm
# iterations and every later sweep of the same shapes dispatch these without
# tracing anything new.

@functools.lru_cache(maxsize=32)
def _fe_sweep_update(config, reg):
    """Fixed-effect visit with a candidate axis: vmap over (x0, offsets,
    RegWeights), the design matrix/labels/norm unmapped — K solves against
    ONE staged copy of the shard.  Returns per-candidate original-space
    coefficients, training scores, the penalty term (transformed space when
    normalized, matching FixedEffectCoordinate.regularization_term), and
    iteration/reason diagnostics."""

    def one(obj0, x0, off, rw):
        obj = obj0.replace(offsets=off)
        if obj0.norm is not None:
            x0 = obj0.norm.model_to_transformed_space(x0)
        res = solve(obj, x0, config, reg, rw)
        pen = (0.5 * rw.l2_weight * jnp.sum(res.x * res.x)
               + rw.l1_weight * jnp.sum(jnp.abs(res.x)))
        c = (obj0.norm.model_to_original_space(res.x)
             if obj0.norm is not None else res.x)
        return c, fops.matvec(obj0.x, c), pen, res.iterations, res.reason

    return jax.jit(jax.vmap(one, in_axes=(None, 0, 0, 0)))


@functools.lru_cache(maxsize=64)
def _re_sweep_update(loss, config, reg, has_weights):
    """One random-effect bucket visit with a candidate axis: the flat
    per-candidate residual offsets gather into block layout INSIDE the
    program (no [K, Eb, Sb] host staging), then vmap-of-vmap runs
    K x Eb independent entity solves in lockstep."""

    def solve_entity(x, labels, mask, weights, offsets, x0_e, rw):
        obj = GLMObjective(loss, x, labels, weights=weights, offsets=offsets,
                           mask=mask)
        res = solve(obj, x0_e, config, reg, rw)
        return res.x, res.iterations, res.reason

    per_entity = jax.vmap(solve_entity,
                          in_axes=(0, 0, 0, 0 if has_weights else None,
                                   0, 0, None))

    def one_candidate(x, labels, mask, weights, safe_ids, flat_off, x0, rw):
        off = (flat_off[safe_ids] * mask).astype(x.dtype)
        return per_entity(x, labels, mask, weights, off, x0, rw)

    return jax.jit(jax.vmap(one_candidate,
                            in_axes=(None, None, None, None, None, 0, 0, 0)))


@functools.lru_cache(maxsize=32)
def _re_sweep_scorer(kind: str, global_dim: int):
    """Per-candidate entity scoring over the SAME flat shard + lane map:
    vmap over coefficients only."""
    from photon_ml_tpu.parallel.random_effect import (
        scatter_local_to_global, score_by_entity)

    if kind == "plain":
        def f(c, proj, x, lanes):
            return score_by_entity(c, x, lanes)
    elif kind == "matmul":
        def f(c, proj, x, lanes):
            return score_by_entity(c @ proj, x, lanes)
    else:
        def f(c, proj, x, lanes):
            return score_by_entity(
                scatter_local_to_global(c, proj, global_dim), x, lanes)

    return jax.jit(jax.vmap(f, in_axes=(0, None, None, None)))


@functools.lru_cache(maxsize=4)
def _fe_sweep_scorer():
    return jax.jit(jax.vmap(lambda x, c: fops.matvec(x, c),
                            in_axes=(None, 0)))


@jax.jit
def _stacked_penalty(c, rw):
    def one(ck, r):
        return (0.5 * r.l2_weight * jnp.sum(ck * ck)
                + r.l1_weight * jnp.sum(jnp.abs(ck)))
    return jax.vmap(one, in_axes=(0, 0))(c, rw)


@functools.partial(jax.jit, static_argnames=("loss",))
def _sweep_data_term(total_k, base_offsets, labels, weights, *, loss):
    """Per-candidate weighted data-loss sum: [K, n] total scores -> [K]."""
    def one(total):
        z = total + base_offsets
        l = loss.loss(z, labels)
        return jnp.sum(l if weights is None else weights * l)
    return jax.vmap(one)(total_k)


def _neutralized(config: GameTrainingConfig) -> GameTrainingConfig:
    """The config with every regularization weight zeroed — two configs are
    sweep-compatible iff their neutralized forms are equal (only the
    weights may vary across candidates; they ride as traced operands)."""
    coords = {}
    for name, c in config.coordinates.items():
        opt = dataclasses.replace(c.optimization, regularization_weight=0.0)
        lat = getattr(c, "latent_optimization", None)
        if lat is not None:
            coords[name] = dataclasses.replace(
                c, optimization=opt, latent_optimization=dataclasses.replace(
                    lat, regularization_weight=0.0))
        else:
            coords[name] = dataclasses.replace(c, optimization=opt)
    return dataclasses.replace(config, coordinates=coords)


class SweepEvaluator:
    """Shared-state sweep evaluator: ONE prepared dataset (coordinates,
    entity bucketing, normalization stats, device residuals), many
    regularization candidates.

    Lanes:
      * `evaluate_vmapped(configs)` — the candidate axis is a vmap axis;
        K candidates' whole block-coordinate descents run as one device
        program per (coordinate, visit).  Eligibility: single device, every
        coordinate a resident FixedEffectCoordinate (no downsampling) or a
        plain RandomEffectCoordinate; zero-initialized models.
      * `evaluate_path(configs)` — sequential fallback for every other
        shape (streamed FE, multi-device mesh, factored coordinates, warm
        starts): candidates sorted strong-to-weak by total regularization,
        each warm-started from its neighbor's solution over the SAME
        prepared coordinates.  Traced reg weights keep this lane
        compile-free after its first candidate too.
      * `evaluate(configs)` picks automatically; `evaluate_config(config)`
        is the single-candidate entry the GP search loop drives.
    """

    def __init__(self, estimator, data: GameDataset,
                 validation_data: Optional[GameDataset] = None,
                 evaluator_specs: Optional[Sequence[str]] = None):
        self.estimator = estimator
        self.config = estimator.config
        self.mesh = estimator.mesh
        self.data = data
        self.validation_data = validation_data
        self.evaluator_specs = evaluator_specs
        self._loss = TASK_LOSSES[self.config.task_type]
        with telemetry.span("sweep/prepare"):
            self.coords = estimator._build_coordinates(data)
            self.specs = (estimator._validation_specs(evaluator_specs)
                          if validation_data is not None else [])
        self._neutral = _neutralized(self.config)
        # flat device vectors shared by every candidate (the vmap lane's
        # private descent; the path lane re-derives its own inside
        # run_coordinate_descent)
        self._labels = None
        self._weights = None
        self._base_offsets = None
        self._val_lanes_cache: Dict[str, jax.Array] = {}

    # -- shared staging -------------------------------------------------------
    def _flat_vectors(self):
        if self._labels is None:
            self._labels = jnp.asarray(self.data.response)
            self._weights = (None if self.data.weights is None
                             else jnp.asarray(self.data.weights))
            self._base_offsets = (
                jnp.zeros(self.data.num_rows) if self.data.offsets is None
                else jnp.asarray(self.data.offsets))
        return self._labels, self._weights, self._base_offsets

    def compatible(self, config: GameTrainingConfig) -> bool:
        """True iff `config` differs from the prepared one ONLY in
        regularization weights (the traced operands)."""
        try:
            return _neutralized(config) == self._neutral
        except (TypeError, ValueError):
            return False

    def vmap_eligible(self) -> Tuple[bool, str]:
        if self.mesh is not None and self.mesh.size > 1:
            return False, "multi-device mesh (per-coordinate staging path)"
        for name in self.config.updating_sequence:
            c = self.coords[name]
            if isinstance(c, FixedEffectCoordinate):
                if c.streamed:
                    return False, f"{name}: streamed fixed effect"
                if c.config.optimization.downsampling_rate is not None:
                    return False, (f"{name}: downsampling draws a fresh "
                                   "per-update mask")
            elif isinstance(c, RandomEffectCoordinate):
                continue
            else:
                return False, f"{name}: factored coordinate"
        return True, "ok"

    # -- lane dispatch --------------------------------------------------------
    def evaluate(self, configs: Sequence[GameTrainingConfig],
                 initial_model=None) -> List["GameResultT"]:
        ok, _why = self.vmap_eligible()
        if ok and initial_model is None and len(configs) > 1:
            return self.evaluate_vmapped(configs)
        return self.evaluate_path(configs, initial_model=initial_model)

    # -- path lane ------------------------------------------------------------
    @staticmethod
    def _total_reg(config: GameTrainingConfig) -> float:
        total = 0.0
        for c in config.coordinates.values():
            total += float(c.optimization.regularization_weight)
            lat = getattr(c, "latent_optimization", None)
            if lat is not None:
                total += float(lat.regularization_weight)
        return total

    def _apply_weights(self, config: GameTrainingConfig) -> None:
        """Swap ONLY the regularization weights into the prepared
        coordinates (everything else is identical by `compatible`; the
        coordinate keeps its resolved-constraint optimizer config).  The
        weights enter the compiled solves as traced operands, so the swap
        never retraces."""
        for name in self.config.updating_sequence:
            coord = self.coords[name]
            cand = config.coordinates[name]
            opt = dataclasses.replace(
                coord.config.optimization,
                regularization_weight=cand.optimization.regularization_weight)
            lat_old = getattr(coord.config, "latent_optimization", None)
            if lat_old is not None:
                coord.config = dataclasses.replace(
                    coord.config, optimization=opt,
                    latent_optimization=dataclasses.replace(
                        lat_old, regularization_weight=cand
                        .latent_optimization.regularization_weight))
            else:
                coord.config = dataclasses.replace(coord.config,
                                                   optimization=opt)

    def evaluate_config(self, config: GameTrainingConfig, initial_model=None,
                        timing_mode: str = "pipelined",
                        _counted: bool = False):
        """One candidate over the SHARED prepared coordinates — the
        hoisted replacement for GameEstimator(config).fit(data, ...): no
        dataset rebuild, no re-bucketing, no fresh traces (reg weights are
        traced operands of the cached solver programs)."""
        from photon_ml_tpu.game.estimator import GameResult
        if not self.compatible(config):
            raise ValueError(
                "candidate config differs from the prepared sweep state in "
                "more than regularization weights; use a fresh "
                "SweepEvaluator (or a full GameEstimator.fit)")
        if not _counted:
            telemetry.counter("sweep.candidates").inc()
        self._apply_weights(config)
        residency = self.estimator._residency_manager(self.coords, self.data)
        schedules = {name: (c.solver_schedule or config.solver_schedule)
                     for name, c in config.coordinates.items()}
        descent = run_coordinate_descent(
            self.coords, list(config.updating_sequence),
            config.num_outer_iterations, self.data, config.task_type,
            validation_dataset=self.validation_data,
            validation_specs=self.specs,
            initial_models=(dict(initial_model.coordinates)
                            if initial_model is not None else None),
            timing_mode=timing_mode, residency=residency,
            solver_schedules=(schedules if any(schedules.values())
                              else None))
        validation = {name: hist[-1] for name, hist in
                      descent.validation_history.items() if hist}
        return GameResult(model=descent.best_model, config=config,
                          objective_history=descent.objective_history,
                          validation=validation, descent=descent,
                          validation_specs=self.specs,
                          residency=residency.accounting())

    def evaluate_path(self, configs: Sequence[GameTrainingConfig],
                      initial_model=None, warm_start: bool = True,
                      timing_mode: str = "pipelined"):
        """Sequential lane: candidates sorted strong-to-weak by total
        regularization, each warm-started from its path neighbor
        (reference: ModelTraining.scala:160-196's lambda-sweep warm start;
        glmnet's regularization-path discipline).  Results return in the
        CALLER's candidate order."""
        telemetry.counter("sweep.candidates").inc(len(configs))
        order = sorted(range(len(configs)),
                       key=lambda k: -self._total_reg(configs[k]))
        results: List[object] = [None] * len(configs)
        prev = initial_model
        for k in order:
            results[k] = self.evaluate_config(
                configs[k], initial_model=prev, timing_mode=timing_mode,
                _counted=True)
            if warm_start:
                prev = results[k].model
        return results

    # -- vmap lane ------------------------------------------------------------
    def _candidate_regweights(self, configs, name, dtype) -> RegWeights:
        l1s, l2s = [], []
        for cfg in configs:
            opt = cfg.coordinates[name].optimization
            l1, l2 = _host_split(opt.regularization,
                                 opt.regularization_weight)
            l1s.append(l1)
            l2s.append(l2)
        return RegWeights(jnp.asarray(np.asarray(l1s), dtype),
                          jnp.asarray(np.asarray(l2s), dtype))

    def _re_score_args(self, coord):
        red = coord.red
        if red.projection_matrix is not None:
            return "matmul", jnp.asarray(red.projection_matrix)
        if red.projection is not None:
            return "scatter", coord.proj_dev
        return "plain", None

    def evaluate_vmapped(self, configs: Sequence[GameTrainingConfig],
                         num_outer_iterations: Optional[int] = None):
        """The vmap lane: K candidates' block coordinate descents as ONE
        device program per (coordinate, visit) against ONE staged data
        copy.  Residual algebra is identical to run_coordinate_descent
        (partial = total - own; update at base + partial; total = partial +
        new), carried with a [K, n] candidate axis; objectives accumulate
        as device [K] scalars and flush in one batched readback at the
        end.  Validation is evaluated once per candidate on the FINAL
        model (per-visit best-model tracking is a sequential-lane feature;
        use `evaluate_path` when you need it)."""
        from photon_ml_tpu.game.estimator import GameResult
        ok, why = self.vmap_eligible()
        if not ok:
            raise ValueError(f"vmap lane ineligible: {why}")
        for cfg in configs:
            if not self.compatible(cfg):
                raise ValueError(
                    "candidate config differs from the prepared sweep state "
                    "in more than regularization weights")
        K = len(configs)
        num_iters = (num_outer_iterations if num_outer_iterations is not None
                     else self.config.num_outer_iterations)
        telemetry.counter("sweep.candidates").inc(K)
        dispatches = 0
        seq = list(self.config.updating_sequence)
        labels, weights, base_offsets = self._flat_vectors()
        n = self.data.num_rows

        rw: Dict[str, RegWeights] = {}
        models0: Dict[str, object] = {}
        coeffs: Dict[str, jax.Array] = {}
        scores: Dict[str, jax.Array] = {}
        reg_pens: Dict[str, jax.Array] = {}
        for name in seq:
            coord = self.coords[name]
            models0[name] = coord.initial_model()
            if isinstance(coord, FixedEffectCoordinate):
                dtype = coord._canonical
                coeffs[name] = jnp.zeros((K, coord.dim), dtype)
            else:
                dtype = coord.red.dtype
                coeffs[name] = jnp.zeros(
                    (K, coord.red.num_entities, coord.red.local_dim), dtype)
            rw[name] = self._candidate_regweights(configs, name, dtype)
            # zero-initialized models: scores and penalties exactly zero,
            # no device work (mirrors run_coordinate_descent init)
            scores[name] = jnp.zeros((K, n))
            reg_pens[name] = jnp.zeros((K,))
        total = jnp.zeros((K, n))

        history: List[jax.Array] = []          # [K] device scalars, per visit
        iters_acc: Dict[str, jax.Array] = {}   # "it/name" -> [K]
        reasons_acc: Dict[str, jax.Array] = {}  # "it/name" -> [K] or [K, E]

        with telemetry.span("sweep/vmapped", candidates=K):
            for it in range(num_iters):
                for name in seq:
                    coord = self.coords[name]
                    opt = coord.config.optimization
                    partial = total - scores[name]
                    off_k = base_offsets + partial           # [K, n]
                    if isinstance(coord, FixedEffectCoordinate):
                        obj0 = GLMObjective(coord.loss, coord.x, coord.labels,
                                            weights=coord.weights,
                                            norm=coord.norm)
                        c, s, pen, iters, reason = _fe_sweep_update(
                            opt.optimizer, opt.regularization)(
                            obj0, coeffs[name], off_k, rw[name])
                        dispatches += 1
                        it_k = iters
                    else:
                        parts, it_parts, re_parts = [], [], []
                        for bucket in coord.red.buckets:
                            blocks = bucket.blocks
                            lo = bucket.lane_start
                            x0b = coeffs[name][:, lo:lo + bucket.num_entities]
                            cb, ib, rb = _re_sweep_update(
                                coord.loss, opt.optimizer, opt.regularization,
                                blocks.weights is not None)(
                                blocks.x, blocks.labels, blocks.mask,
                                blocks.weights, bucket.safe_ids_dev(), off_k,
                                x0b, rw[name])
                            parts.append(cb)
                            it_parts.append(ib)
                            re_parts.append(rb)
                            dispatches += 1
                        c = (parts[0] if len(parts) == 1
                             else jnp.concatenate(parts, axis=1))
                        kind, proj = self._re_score_args(coord)
                        s = _re_sweep_scorer(kind, coord.red.global_dim)(
                            c, proj, coord.flat_x, coord.lanes)
                        dispatches += 1
                        pen = _stacked_penalty(c, rw[name])
                        it_all = (it_parts[0] if len(it_parts) == 1
                                  else jnp.concatenate(it_parts, axis=1))
                        it_k = jnp.sum(it_all, axis=1)
                        reason = (re_parts[0] if len(re_parts) == 1
                                  else jnp.concatenate(re_parts, axis=1))
                    coeffs[name] = c
                    scores[name] = s
                    reg_pens[name] = pen
                    total = partial + s
                    obj_k = (_sweep_data_term(total, base_offsets, labels,
                                              weights, loss=self._loss)
                             + sum(reg_pens.values()))
                    history.append(obj_k)
                    iters_acc[f"{it}/{name}"] = it_k
                    reasons_acc[f"{it}/{name}"] = reason

            # -- validation: final models, one [K, n_val] pass ----------------
            val_matrix = None
            if self.validation_data is not None and self.specs:
                val_total = jnp.zeros((K, self.validation_data.num_rows))
                for name in seq:
                    coord = self.coords[name]
                    shard = self.validation_data.device_shard(
                        coord.config.feature_shard)
                    if isinstance(coord, FixedEffectCoordinate):
                        val_total = val_total + _fe_sweep_scorer()(
                            shard, coeffs[name])
                    else:
                        lanes = self._validation_lanes(name, models0[name])
                        kind, proj = self._re_score_args(coord)
                        val_total = val_total + _re_sweep_scorer(
                            kind, coord.red.global_dim)(
                            coeffs[name], proj, shard, lanes)
                    dispatches += 1
                val_matrix = np.asarray(val_total)  # photonlint: disable=PH001 -- the one batched validation readback

            # ONE batched readback for objectives + diagnostics
            hist_host, iters_host, reasons_host = jax.device_get(
                [jnp.stack(history) if history else jnp.zeros((0, K)),
                 iters_acc, reasons_acc])

        telemetry.counter("sweep.dispatches").inc(dispatches)

        val_metrics: List[Dict[str, float]] = [{} for _ in range(K)]
        if val_matrix is not None:
            for k in range(K):
                for spec in self.specs:
                    val_metrics[k][spec.name] = float(
                        spec.evaluate(self.validation_data, val_matrix[k]))

        results = []
        for k in range(K):
            models_k: Dict[str, object] = {}
            for name in seq:
                coord = self.coords[name]
                if isinstance(coord, FixedEffectCoordinate):
                    models_k[name] = FixedEffectModel(
                        model_for_task(self.config.task_type,
                                       Coefficients(coeffs[name][k])),
                        coord.config.feature_shard)
                else:
                    models_k[name] = dataclasses.replace(
                        models0[name], coefficients=coeffs[name][k])
            gm = GameModel(models_k, self.config.task_type)
            trackers = {
                key: TrackerSummary(
                    iterations=int(np.sum(np.asarray(iters_host[key][k]))),
                    wall_s=0.0,
                    reasons=_reason_counts(reasons_host[key][k]))
                for key in iters_acc}
            descent = CoordinateDescentResult(
                model=gm, best_model=gm,
                objective_history=[float(v) for v in
                                   np.asarray(hist_host)[:, k]],
                validation_history={s.name: [val_metrics[k][s.name]]
                                    for s in self.specs
                                    if s.name in val_metrics[k]},
                timings={}, trackers=trackers)
            results.append(GameResult(
                model=gm, config=configs[k],
                objective_history=descent.objective_history,
                validation=val_metrics[k], descent=descent,
                validation_specs=self.specs))
        return results

    def _validation_lanes(self, name: str, model0: RandomEffectModel):
        """Validation-row -> entity-lane map for a random-effect
        coordinate, staged once per sweep (entities the training data
        never saw map to -1 and score 0 — the missing-score default)."""
        lanes = self._val_lanes_cache.get(name)
        if lanes is None:
            lanes = model0._device_lanes(self.validation_data)
            self._val_lanes_cache[name] = lanes
        return lanes


# typing alias for the lazy GameResult import (estimator imports this
# module's neighbors; a top-level import back into game.estimator would
# be circular)
GameResultT = object
