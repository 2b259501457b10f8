"""GameEstimator: dataset + config -> trained GAME model(s).

reference: GameEstimator (photon-api/.../estimators/GameEstimator.scala:52):
fit() converts the input data, builds per-coordinate datasets/problems,
prepares loss/validation evaluators, and runs CoordinateDescent once per
optimization configuration (grid), returning (model, evaluations, config)
triples; `fit_grid` here mirrors that multi-config sweep
(GameEstimator.scala:474 train per config).
"""
from __future__ import annotations

import dataclasses
import itertools
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
from jax.sharding import Mesh

from photon_ml_tpu.utils.events import (
    EventEmitter, OptimizationLogEvent, TrainingFinishEvent,
    TrainingStartEvent,
)

from photon_ml_tpu.data.game_data import GameDataset
from photon_ml_tpu.evaluation.evaluators import (
    default_validation_evaluator_for_task, parse_evaluator,
)
from photon_ml_tpu.game.config import (
    CoordinateConfig, FactoredRandomEffectCoordinateConfig,
    FixedEffectCoordinateConfig, GameTrainingConfig, GLMOptimizationConfig,
    RandomEffectCoordinateConfig,
)
from photon_ml_tpu.game.coordinate_descent import (
    CoordinateDescentResult, ValidationSpec, run_coordinate_descent,
)
from photon_ml_tpu.game.coordinates import (
    Coordinate, FactoredRandomEffectCoordinate, FixedEffectCoordinate,
    RandomEffectCoordinate,
)
from photon_ml_tpu.models.game import GameModel


@dataclasses.dataclass
class GameResult:
    """One trained configuration (reference: GameEstimator.GameResult)."""

    model: GameModel
    config: GameTrainingConfig
    objective_history: List[float]
    validation: Dict[str, float]          # final value per evaluator
    descent: CoordinateDescentResult
    validation_specs: List[ValidationSpec] = dataclasses.field(default_factory=list)
    # HBM residency accounting (ResidencyManager.accounting()): budget,
    # per-coordinate block bytes, eviction count, tracked peak — the
    # memory_stats() stand-in the peak-memory tests read
    residency: Optional[dict] = None
    # how the fit's checkpoint was recovered at resume time (CheckpointState
    # .recovery: fallback flag, pruned partial writes, resumed iteration);
    # None when the fit started fresh or checkpointing was off
    checkpoint_recovery: Optional[dict] = None
    # mesh transfer accounting over this fit (TransferStats delta from
    # parallel/mesh_residency.py): bytes staged cold (static coordinate
    # data, once per residency) vs warm (per-visit offsets/x0), and of
    # either kind those whose source was a host array (`host_bytes`: what
    # crossed the host link) — the observable no-retransfer property
    # (tests/test_mesh_residency.py).  None when the fit ran without a mesh.
    mesh_transfer: Optional[dict] = None
    # per entity-keyed coordinate, what its build did with the rows:
    # entities, active / passive / discarded rows, capped entities, padded
    # cells and bucket shapes (RandomEffectDataset.build_counts; the
    # same numbers are the `train.re_build.<coordinate>.*` gauges); per
    # fixed-effect coordinate over a SPARSE shard, what its device shard
    # holds and the host seconds this fit spent packing it
    # (FixedEffectCoordinate.build_stats, `train.fe_build.<coordinate>.*`)
    coordinate_build: Dict[str, dict] = dataclasses.field(
        default_factory=dict)


class GameEstimator:
    def __init__(self, config: GameTrainingConfig, mesh: Optional[Mesh] = None,
                 emitter: Optional[EventEmitter] = None):
        self.config = config
        self.mesh = mesh
        self.emitter = emitter

    def _build_coordinates(self, dataset: GameDataset) -> Dict[str, Coordinate]:
        import dataclasses as _dc
        coords: Dict[str, Coordinate] = {}
        for name in self.config.updating_sequence:
            cfg = self.config.coordinates[name]
            latent = getattr(cfg, "latent_optimization", None)
            if latent is not None and \
                    latent.optimizer.constraints is not None:
                raise ValueError(
                    f"coordinate {name!r}: named feature constraints are "
                    "not supported on the latent-projection problem")
            if cfg.optimization.optimizer.constraints is not None:
                # named constraints resolve through the shard's index map
                # into positional bounds (reference scope: a fixed-effect /
                # single-GLM feature — per-entity random-effect problems
                # live in projected local spaces where global feature names
                # have no stable columns)
                if not isinstance(cfg, FixedEffectCoordinateConfig):
                    raise ValueError(
                        f"coordinate {name!r}: named feature constraints "
                        "are supported on fixed-effect coordinates only "
                        "(the reference's constraint maps are a single-GLM "
                        "feature, GLMSuite.scala:206-280)")
                opt = cfg.optimization.optimizer.resolved_constraints(
                    (dataset.index_maps or {}).get(cfg.feature_shard))
                cfg = _dc.replace(cfg, optimization=_dc.replace(
                    cfg.optimization, optimizer=opt))
            budget = self.config.hbm_budget_bytes
            if isinstance(cfg, FixedEffectCoordinateConfig):
                coords[name] = FixedEffectCoordinate(
                    name, dataset, cfg, self.config.task_type, self.mesh,
                    seed=self.config.seed, hbm_budget_bytes=budget)
            elif isinstance(cfg, FactoredRandomEffectCoordinateConfig):
                coords[name] = FactoredRandomEffectCoordinate(
                    name, dataset, cfg, self.config.task_type, self.mesh,
                    seed=self.config.seed, hbm_budget_bytes=budget)
            else:
                coords[name] = RandomEffectCoordinate(
                    name, dataset, cfg, self.config.task_type, self.mesh,
                    seed=self.config.seed, hbm_budget_bytes=budget)
        return coords

    def _residency_manager(self, coords, dataset: GameDataset):
        """HBM residency bookkeeping (game/residency.py): always built so
        summaries and tests get byte accounting; it only EVICTS when
        hbm_budget_bytes is set and the coordinates' resident blocks bust
        it."""
        import jax as _jax

        from photon_ml_tpu.game.residency import ResidencyManager
        itemsize = np.dtype(_jax.dtypes.canonicalize_dtype(np.float64)).itemsize
        n = dataset.num_rows
        # always-resident flat [n] vectors: per-coordinate scores + total +
        # base offsets + labels (+ weights) + one int32 lane map per
        # entity-keyed coordinate
        flat = (len(self.config.updating_sequence) + 3) * n * itemsize
        flat += sum(4 * n for c in self.config.coordinates.values()
                    if hasattr(c, "random_effect_type"))
        return ResidencyManager(coords, self.config.hbm_budget_bytes,
                                flat_vector_bytes=flat, mesh=self.mesh)

    def _config_fingerprint(
            self, evaluator_specs: Optional[Sequence[str]]) -> str:
        """Identity of everything that makes a checkpoint resumable: the
        full training config EXCEPT the outer iteration count (raising it
        and resuming is the intended use), PLUS the validation evaluator
        specs — the checkpointed best_metric is only comparable under the
        same first evaluator."""
        import hashlib
        import json

        def strip_nones(v):
            # drop None-valued keys so ADDING an optional config field (new
            # release) does not shift every existing fingerprint and
            # silently invalidate old checkpoints
            if isinstance(v, dict):
                return {k: strip_nones(x) for k, x in v.items()
                        if x is not None}
            if isinstance(v, list):
                return [strip_nones(x) for x in v]
            return v

        d = strip_nones(self.config.to_dict())
        d.pop("num_outer_iterations", None)
        d["__evaluator_specs__"] = list(evaluator_specs or [])
        return hashlib.sha256(
            json.dumps(d, sort_keys=True).encode()).hexdigest()[:16]

    def _validation_specs(self, evaluator_specs: Optional[Sequence[str]]
                          ) -> List[ValidationSpec]:
        if not evaluator_specs:
            ev = default_validation_evaluator_for_task(self.config.task_type)
            return [ValidationSpec(ev)]
        out = []
        for spec in evaluator_specs:
            ev, group = parse_evaluator(spec)
            out.append(ValidationSpec(ev, group))
        return out

    def fit(
        self,
        dataset: GameDataset,
        validation_dataset: Optional[GameDataset] = None,
        evaluator_specs: Optional[Sequence[str]] = None,
        initial_model: Optional[GameModel] = None,
        checkpoint_dir: Optional[str] = None,
        timing_mode: str = "pipelined",
    ) -> GameResult:
        """reference: GameEstimator.fit (GameEstimator.scala:175).

        `timing_mode="pipelined"` (default) overlaps host bookkeeping with
        device solves: objectives/metrics are fetched in one batched
        readback per outer iteration and checkpoints serialize on a
        background thread.  `"strict"` syncs after every coordinate update
        — same math bit-for-bit, attributable PhaseTimings spans.

        `initial_model` warm-starts every coordinate it covers (reference:
        GameTrainingParams.useWarmStart — "the previous optimal model is used
        to initialize the next model").

        `checkpoint_dir` persists the model after every outer coordinate-
        descent iteration and RESUMES from the latest record when one is
        already present — the reference has no mid-training recovery (a
        failed Spark driver restarts the job from scratch, SURVEY §5.3)."""
        if self.emitter is not None:
            self.emitter.send_event(TrainingStartEvent(time.time()))
        from photon_ml_tpu import telemetry
        from photon_ml_tpu.game.coordinate_descent import PhaseTimings
        # root span of the whole fit (push/pop: an exception path is healed
        # by Tracer.finish() at export time)
        _fit_span = telemetry.push("fit", task=self.config.task_type,
                                   coordinates=len(self.config.coordinates))
        spans = PhaseTimings()
        # snapshot BEFORE the build: eager mesh staging of FE shards happens
        # inside _build_coordinates and belongs to this fit's cold bytes
        mesh_snap0 = None
        if self.mesh is not None:
            from photon_ml_tpu.parallel.mesh_residency import transfer_snapshot
            mesh_snap0 = transfer_snapshot()
        # coordinate construction includes the RE dataset bucketing — a real
        # cost at corpus scale that round 3's phase timings never saw
        with spans.span("build/coordinates", name="build"):
            coords = self._build_coordinates(dataset)
        residency = self._residency_manager(coords, dataset)
        specs = (self._validation_specs(evaluator_specs)
                 if validation_dataset is not None else [])
        initial_models = (dict(initial_model.coordinates)
                          if initial_model is not None else None)
        resume = None
        fingerprint = None
        if checkpoint_dir is not None:
            from photon_ml_tpu.game.coordinate_descent import read_checkpoint
            fingerprint = self._config_fingerprint(evaluator_specs)
            resume = read_checkpoint(checkpoint_dir, fingerprint)
        # inexact-solve schedules: a coordinate-level schedule overrides the
        # training-level one; all-None collapses to the strict no-schedule
        # path (optim/schedule.py, COMPONENTS.md "Solver schedules")
        schedules = {name: (c.solver_schedule or self.config.solver_schedule)
                     for name, c in self.config.coordinates.items()}
        descent = run_coordinate_descent(
            coords, self.config.updating_sequence,
            self.config.num_outer_iterations, dataset, self.config.task_type,
            validation_dataset=validation_dataset, validation_specs=specs,
            initial_models=initial_models,
            checkpoint_dir=checkpoint_dir, resume=resume,
            checkpoint_fingerprint=fingerprint, timings=spans,
            timing_mode=timing_mode, residency=residency,
            solver_schedules=(schedules if any(schedules.values())
                              else None))
        validation = {name: hist[-1] for name, hist in
                      descent.validation_history.items() if hist}
        if self.emitter is not None:
            self.emitter.send_event(OptimizationLogEvent(
                regularization_weights={
                    n: c.optimization.regularization_weight
                    for n, c in self.config.coordinates.items()},
                objective_history=list(descent.objective_history),
                final_metrics=dict(validation)))
            self.emitter.send_event(TrainingFinishEvent(time.time()))
        mesh_transfer = None
        if mesh_snap0 is not None:
            from photon_ml_tpu.parallel.mesh_residency import (
                TransferStats, transfer_snapshot)
            mesh_transfer = TransferStats.delta(mesh_snap0,
                                                transfer_snapshot())
        telemetry.pop(_fit_span)
        return GameResult(model=descent.best_model, config=self.config,
                          objective_history=descent.objective_history,
                          validation=validation, descent=descent,
                          validation_specs=specs,
                          residency=residency.accounting(),
                          checkpoint_recovery=(resume.recovery
                                               if resume is not None
                                               else None),
                          mesh_transfer=mesh_transfer,
                          coordinate_build={
                              name: c.build_stats
                              for name, c in coords.items()
                              if getattr(c, "build_stats", None)})

    def fit_grid(
        self,
        dataset: GameDataset,
        grid: Dict[str, Sequence[GLMOptimizationConfig]],
        validation_dataset: Optional[GameDataset] = None,
        evaluator_specs: Optional[Sequence[str]] = None,
        warm_start: bool = False,
        checkpoint_dir: Optional[str] = None,
        initial_model: Optional[GameModel] = None,
        timing_mode: str = "pipelined",
    ) -> List[GameResult]:
        """Sweep per-coordinate optimization configs (cartesian product),
        reference: GameTrainingParams.getAllModelConfigs + train-per-config
        (GameEstimator.scala:474).

        With `warm_start`, each combo is initialized from the previous
        combo's trained model (reference: useWarmStart; ModelTraining.scala:
        160-196 does the same across the lambda sweep — pass the grid
        strongest-regularization-first to match).

        With `checkpoint_dir`, each combo checkpoints under its own
        `combo-NNN` subdirectory; re-running an interrupted sweep resumes
        the partial combo mid-descent and replays completed combos as
        instant no-ops (their checkpoints already cover every iteration)."""
        names = list(grid)
        results: List[GameResult] = []
        # `initial_model` seeds the sweep (cross-job warm start); with
        # warm_start each combo then chains from the previous combo's model,
        # without it every combo starts independently from the seed
        previous: Optional[GameModel] = initial_model
        for i, combo in enumerate(itertools.product(*(grid[n] for n in names))):
            coords = dict(self.config.coordinates)
            for n, opt in zip(names, combo):
                coords[n] = dataclasses.replace(coords[n], optimization=opt)
            cfg = dataclasses.replace(self.config, coordinates=coords)
            sub = GameEstimator(cfg, self.mesh, emitter=self.emitter)
            combo_ckpt = (None if checkpoint_dir is None else
                          os.path.join(checkpoint_dir, f"combo-{i:03d}"))
            results.append(sub.fit(
                dataset, validation_dataset, evaluator_specs,
                initial_model=previous if warm_start else initial_model,
                checkpoint_dir=combo_ckpt, timing_mode=timing_mode))
            previous = results[-1].model
        return results


def select_best_result(results: Sequence[GameResult]) -> GameResult:
    """Best by the first validation evaluator, using that evaluator's own
    metric direction (reference: cli/game/training/Driver.selectBestModel:375)."""
    if not results:
        raise ValueError("no results")
    with_val = [r for r in results if r.validation and r.validation_specs]
    if not with_val:
        return results[0]
    spec = with_val[0].validation_specs[0]
    best = with_val[0]
    for r in with_val[1:]:
        if spec.evaluator.better_than(r.validation[spec.name],
                                      best.validation[spec.name]):
            best = r
    return best
