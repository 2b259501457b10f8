"""Training coordinates: the per-block-update unit of GAME.

Rebuild of the reference's Coordinate tower:
  - Coordinate (photon-lib/.../algorithm/Coordinate.scala:27-80):
    updateModel(model, partial scores) = re-offset own dataset with the other
    coordinates' scores, then optimize
  - FixedEffectCoordinate (photon-api/.../algorithm/FixedEffectCoordinate.scala:34-167)
  - RandomEffectCoordinate (photon-api/.../algorithm/RandomEffectCoordinate.scala:39-222)
  - RandomEffectCoordinateInProjectedSpace (.../RandomEffectCoordinateInProjectedSpace.scala)
    — projection is folded into the dataset build here (data/batching.py)

A coordinate owns its (device-resident) training data and knows how to
(re)fit its model given the current residual offsets; scores are returned in
the dataset's canonical row order so CoordinateDescent can combine them with
plain array arithmetic.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.data.batching import (
    FixedEffectDataConfig, FixedEffectDataset, RandomEffectDataset,
    build_random_effect_dataset,
)
from photon_ml_tpu.data.game_data import GameDataset
from photon_ml_tpu.data.samplers import downsampler_for_task
from photon_ml_tpu.data.stats import BasicStatisticalSummary
from photon_ml_tpu.game.config import (
    FactoredRandomEffectCoordinateConfig, FixedEffectCoordinateConfig,
    RandomEffectCoordinateConfig,
)
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.game import (
    FactoredRandomEffectModel, FixedEffectModel, RandomEffectModel,
)
from photon_ml_tpu.parallel.factored import (
    FactoredSolveResult, ProjectionRows, fit_factored_random_effects,
    gaussian_projection_matrix,
)
from photon_ml_tpu.models.glm import model_for_task
from photon_ml_tpu.ops import TASK_LOSSES, GLMObjective
from photon_ml_tpu.ops import features as fops
from photon_ml_tpu.ops.normalization import (
    NormalizationContext, NormalizationType, build_normalization_context,
)
from photon_ml_tpu.optim import ADMMConfig, SolveResult, solve
from photon_ml_tpu.parallel.fixed_effect import (
    _cached_solver, fit_fixed_effect, fit_fixed_effect_admm,
    score_fixed_effect_admm,
)
from photon_ml_tpu.parallel.random_effect import (
    fit_random_effects, score_by_entity,
)
from photon_ml_tpu.telemetry import annotate, gauge

logger = logging.getLogger(__name__)


@jax.jit
def _penalty(c, l1, l2):
    """0.5*l2*||c||^2 + l1*||c||_1 as ONE program (reg terms re-evaluate
    every coordinate update; op-by-op each evaluation would be several
    dispatches)."""
    return 0.5 * l2 * jnp.sum(c * c) + l1 * jnp.sum(jnp.abs(c))


class FixedEffectCoordinate:
    """Global GLM over one feature shard (reference:
    FixedEffectCoordinate.scala).  Normalization is trained-in /
    mapped-out per update; down-sampling draws a fresh mask per update
    (reference: DistributedOptimizationProblem.runWithSampling:143).

    Memory modes (no reference equivalent — Spark is out-of-core by
    construction): "resident" keeps the device shard pinned for the fit;
    "streamed" keeps the shard on HOST and runs every solve as a double-
    buffered chunk stream (ChunkedGLMObjective + the host-stepped
    LBFGS/TRON in optim/streaming.py) bounded by two chunks of HBM; "auto"
    streams iff an hbm_budget_bytes is set that the resident shard would
    bust (> budget/2, leaving the other half for the RE coordinates, flat
    vectors and accumulators)."""

    def __init__(self, name: str, dataset: GameDataset,
                 config: FixedEffectCoordinateConfig, task_type: str,
                 mesh=None, seed: int = 7,
                 hbm_budget_bytes: Optional[int] = None):
        self.name = name
        self.config = config
        self.task_type = task_type
        self.loss = TASK_LOSSES[task_type]
        self.mesh = mesh
        self.hbm_budget_bytes = hbm_budget_bytes
        self._dataset = dataset
        host_x = dataset.feature_shards[config.feature_shard]
        is_dense = isinstance(host_x, np.ndarray)
        self.dim = host_x.shape[1]
        self._canonical = jnp.dtype(jax.dtypes.canonicalize_dtype(
            host_x.dtype if is_dense else np.float64))
        shard_bytes = self._resident_shard_bytes(host_x)
        # mesh data-axis width: resident blocks shard 1/D per device, so
        # budgets (per-device semantics) compare against shard_bytes / D
        self._data_div = 1
        if mesh is not None:
            from photon_ml_tpu.parallel.mesh import DATA_AXIS
            self._data_div = max(int(mesh.shape.get(DATA_AXIS, 1)), 1)

        # --- memory-mode resolution -----------------------------------------
        if config.memory_mode == "streamed":
            self.streamed = True
        elif config.memory_mode == "resident":
            self.streamed = False
        else:  # auto: stream iff the PER-DEVICE resident footprint busts
            # half the per-device budget (the other half stays for RE
            # blocks, flat vectors and accumulators)
            self.streamed = (hbm_budget_bytes is not None and is_dense
                             and shard_bytes // self._data_div
                             > hbm_budget_bytes // 2)
        if self.streamed:
            if not is_dense:
                raise ValueError(
                    f"coordinate {name!r}: memory_mode='streamed' requires a "
                    "dense host shard (chunking a sparse matrix would re-pack "
                    "ELL per chunk per pass); use the resident sparse path")
            if config.optimization.downsampling_rate is not None:
                raise ValueError(
                    f"coordinate {name!r}: downsampling is not supported in "
                    "streamed mode yet (the draw is a device-resident [n] "
                    "program); use memory_mode='resident'")

        self.labels = dataset.device_vector("response")
        self.weights = dataset.device_vector("weights")
        self._key = jax.random.PRNGKey(seed)
        # shard coefficients over the mesh feature axis: explicit config wins,
        # otherwise automatic whenever the mesh carries a feature axis > 1
        # (so `--mesh 4x2` actually shards; reference wide-model regime,
        # GameEstimator.scala:667-669)
        from photon_ml_tpu.parallel.mesh import FEATURE_AXIS
        self.shard_features = (config.shard_features
                               if config.shard_features is not None
                               else mesh is not None
                               and mesh.shape.get(FEATURE_AXIS, 1) > 1)
        self._feature_div = 1
        if mesh is not None:
            self._feature_div = max(int(mesh.shape.get(FEATURE_AXIS, 1)), 1)
        # feature sharding must have a consumer: with a feature axis > 1 the
        # consensus-ADMM lane trains on it (dense, unnormalized, resident,
        # unconstrained coordinates); anything else must not pretend — an
        # explicit shard_features=True with NO mesh is a config error, and a
        # blocked lane warns once per coordinate instead of silently
        # training monolithically
        if config.shard_features is True and mesh is None:
            raise ValueError(
                f"coordinate {name!r}: shard_features=True but no mesh — "
                "nothing consumes the feature axis; build the estimator "
                "with make_mesh(num_feature=...) or drop shard_features")
        admm_blockers = []
        if self.shard_features and self._feature_div > 1:
            if self.streamed:
                admm_blockers.append("memory_mode='streamed'")
            if not is_dense:
                admm_blockers.append("sparse feature shard")
            if config.normalization != NormalizationType.NONE:
                admm_blockers.append(
                    f"normalization={config.normalization.value!r}")
            opt_cfg = config.optimization.optimizer
            if (opt_cfg.box_lower is not None or opt_cfg.box_upper is not None
                    or opt_cfg.constraints is not None):
                admm_blockers.append("box/named coefficient constraints")
        self._admm_eligible = (self.shard_features and self._feature_div > 1
                               and not admm_blockers)
        if self.shard_features and self._feature_div > 1 and admm_blockers:
            logger.warning(
                "coordinate %r: shard_features is on but the feature-axis "
                "ADMM lane is blocked by %s — training falls back to the "
                "monolithic solver (coefficients merely ANNOTATED over the "
                "feature axis, no memory scaling)", name,
                ", ".join(admm_blockers))
        elif config.shard_features is True and self._feature_div <= 1:
            logger.warning(
                "coordinate %r: shard_features=True but the mesh feature "
                "axis has width 1 — no solver consumes it; build the mesh "
                "with make_mesh(num_feature=...) to light up the ADMM lane",
                name)

        self.norm: Optional[NormalizationContext] = None
        if config.normalization != NormalizationType.NONE:
            if not is_dense:
                raise ValueError(
                    "normalization requires a dense feature shard (stats over "
                    "a sparse shard would densify it); use normalization=NONE "
                    "for sparse/wide coordinates")
            imap = dataset.index_maps.get(config.feature_shard)
            intercept = (imap.intercept_index if imap is not None
                         else self.dim - 1)  # intercept-last convention
            # stats in the CANONICAL dtype so they match what a device copy
            # of the shard would yield (host float64 -> float32 without x64)
            summ = BasicStatisticalSummary.from_features(
                np.asarray(host_x, dtype=self._canonical),
                None if self.weights is None else np.asarray(self.weights))
            self.norm = build_normalization_context(
                config.normalization,
                mean=jnp.asarray(summ.mean), variance=jnp.asarray(summ.variance),
                max_magnitude=jnp.asarray(summ.max_magnitude),
                intercept_index=intercept)

        self._x = None
        self._stream = None
        if self.streamed:
            from photon_ml_tpu.data.streaming import ChunkPlan
            from photon_ml_tpu.ops.chunked import ChunkedGLMObjective
            n = host_x.shape[0]
            row_bytes = (self.dim + 4) * self._canonical.itemsize
            if config.chunk_rows is not None:
                plan = ChunkPlan.build(n, chunk_rows=config.chunk_rows,
                                       row_multiple=self._data_div)
            elif hbm_budget_bytes is not None:
                # two chunks fit in the coordinate's half of the budget;
                # on a mesh the budget is per device and each chunk shards
                # 1/D per device, so the aggregate chunk budget scales by D
                plan = ChunkPlan.build(
                    n,
                    hbm_budget_bytes=(hbm_budget_bytes // 2) * self._data_div,
                    bytes_per_row=row_bytes, row_multiple=self._data_div)
            else:
                plan = ChunkPlan.build(n, chunk_rows=max(n // 8, 1),
                                       row_multiple=self._data_div)
            cast = lambda a: (None if a is None else
                              np.asarray(a, dtype=self._canonical))
            # ONE persistent chunked objective: per-update residual offsets
            # swap in via replace() (prefetcher stats accumulate across the
            # fit for the transfer accounting in `solver_diagnostics()`).  Under a mesh each
            # staged chunk shards rows over the "data" axis and GSPMD
            # inserts the accumulation psums.
            self._stream = ChunkedGLMObjective(
                self.loss, cast(host_x), cast(dataset.response), plan,
                weights=cast(dataset.weights), norm=self.norm,
                mesh=mesh if self._data_div > 1 else None)
            # a stale full device copy from an earlier consumer would defeat
            # the budget — streaming stages chunks from the host copy
            dataset.release_device_shard(config.feature_shard)
        elif hbm_budget_bytes is None:
            # no budget: materialize eagerly, exactly the pre-out-of-core
            # behavior (transfer cost lands in build/coordinates, not in the
            # first solve span).  A data axis over several devices stages
            # its padded + sharded copy into the residency layer instead of
            # a full single-device copy; over one device the dataset's own
            # device copy IS that layout, and solve and score both read it.
            if self._admm_eligible:
                # the ADMM lane trains AND scores through the column grid,
                # so eager-stage that layout (the monolithic "x" entry only
                # materializes if/when a polish pass asks for it)
                from photon_ml_tpu.parallel.fixed_effect import (
                    stage_admm_grid)
                stage_admm_grid(self._mesh_key(), self.mesh,
                                self._mesh_x_source())
            elif self._data_div > 1:
                from photon_ml_tpu.parallel.fixed_effect import (
                    staged_fixed_effect_x)
                staged_fixed_effect_x(self._mesh_key(), self.mesh,
                                      self._mesh_x_source())
            else:
                self.x  # noqa: B018 — property materializes the device copy

    # --- device residency -----------------------------------------------------
    def _resident_shard_bytes(self, host_x) -> int:
        from photon_ml_tpu.data.game_data import ReleasedHostShard
        if isinstance(host_x, (np.ndarray, ReleasedHostShard)):
            itemsize = jnp.dtype(jax.dtypes.canonicalize_dtype(
                host_x.dtype)).itemsize
            return int(host_x.shape[0]) * int(host_x.shape[1]) * itemsize
        # scipy CSR -> PaddedSparse ELL estimate: [n, k] indices + values
        import numpy as _np
        k = int(_np.diff(host_x.indptr).max()) if host_x.nnz else 1
        itemsize = jnp.dtype(jax.dtypes.canonicalize_dtype(
            host_x.dtype)).itemsize
        return int(host_x.shape[0]) * k * (4 + itemsize)

    def _mesh_key(self):
        """Residency key of this coordinate's staged mesh arrays (the
        per-coordinate invalidation unit, parallel/mesh_residency.py)."""
        return (self.name, id(self))

    def _mesh_x_source(self):
        """Identity-stable source the mesh residency layer stages the
        design matrix from.  Where the mesh's data axis spans several
        devices a dense host shard stages DIRECTLY host -> sharded devices
        (no intermediate full single-device copy), as it does for the ADMM
        column grid and under an HBM budget (whose device copy is lazy and
        evictable).  Where the data axis is ONE device the sharded layout
        is the single-device one, which the dataset already holds
        (`GameDataset.device_shard`, kept across fits): the source is that
        copy, `self.x`, the array `score` reads, so staging it moves
        nothing and only the dataset's first fit uploads the shard.  Sparse
        and host-released shards go through `self.x` on every mesh."""
        host = self._dataset.feature_shards[self.config.feature_shard]
        if isinstance(host, np.ndarray) and (
                self._data_div > 1 or self._admm_eligible
                or self.hbm_budget_bytes is not None):
            return host
        return self.x

    @property
    def x(self):
        """Device FeatureMatrix of the shard, materialized lazily (so an
        evicted coordinate re-streams on its next visit).  Dense arrays
        pass through; scipy.sparse shards become PaddedSparse (the
        wide-model product path, ops/features.py); single-device solves
        also carry the column-sorted gradient stream (no scatter).  The
        device copy comes from (and is stored back into) the dataset's
        shared shard cache (`GameDataset.device_shard`) so
        scoring/diagnostics never re-transfer it and a sparse shard is
        packed ONCE a dataset; `build_stats` says what that pack made."""
        if self.streamed:
            raise RuntimeError(f"coordinate {self.name!r} is streamed: its "
                               "feature shard is never fully device-resident")
        if self._x is None:
            ds, shard = self._dataset, self.config.feature_shard
            before = ds.shard_build.get(shard)
            self._x = ds.device_shard(
                shard, with_csc=self.mesh is None or self.mesh.size == 1)
            built = ds.shard_build.get(shard)
            if built is not None:
                # a sparse shard: what its pack made, and the seconds only
                # where it ran for THIS coordinate
                packed_here = built is not before
                self.build_stats = dict(
                    built, pack_s=built["pack_s"] if packed_here else 0.0)
                if packed_here:
                    for key, value in built.items():
                        gauge(f"train.fe_build.{self.name}.{key}").set(value)
        return self._x

    #: per coordinate over a sparse shard, what the pack of its device copy
    #: made (`ops/features.py::pack_sparse`'s counts, for the fit's summary
    #: `GameResult.coordinate_build`; where this coordinate packed it, the
    #: same numbers are the `train.fe_build.<coordinate>.*` gauges) and the
    #: host seconds THIS coordinate spent on it (0 where the dataset's
    #: cache had it).  None for a dense shard, and until a lazy shard is
    #: first materialized
    build_stats: Optional[dict] = None

    def device_block_bytes(self) -> int:
        """Evictable device bytes (the shard; flat [n] labels/weights stay
        resident and are accounted by the estimator's flat-vector term)."""
        if self.streamed:
            return 0
        if self._x is not None:
            leaves = jax.tree_util.tree_leaves(self._x)
            return sum(int(leaf.nbytes) for leaf in leaves)
        return self._resident_shard_bytes(
            self._dataset.feature_shards[self.config.feature_shard])

    def streaming_buffer_bytes(self) -> int:
        """Peak device bytes of the chunk double buffer (2 chunks)."""
        if not self.streamed:
            return 0
        plan = self._stream.plan
        row_bytes = (self.dim + 4) * self._canonical.itemsize
        return 2 * plan.chunk_bytes(row_bytes)

    def stream_snapshot(self) -> Optional[dict]:
        """StreamStats snapshot of the coordinate's chunk stream (None
        when resident): the per-visit deltas land in TrackerSummary.stream
        and solver_diagnostics() so work-per-staged-byte is observable
        per fit."""
        if not self.streamed:
            return None
        return self._stream.stats.snapshot()

    def evict_device_blocks(self) -> None:
        """Residency-manager hook: drop the device shard between visits
        (no-op when streamed — nothing is pinned).  On a mesh, of any
        shape, it drops ONLY this coordinate's staged arrays, which would
        otherwise keep the shard pinned (per-coordinate invalidation; other
        coordinates' entries stay resident)."""
        if self.streamed:
            return
        self._x = None
        self._dataset.release_device_shard(self.config.feature_shard)
        if self.mesh is not None:
            from photon_ml_tpu.parallel.mesh_residency import invalidate
            invalidate(self._mesh_key())

    def initial_model(self) -> FixedEffectModel:
        """reference: Coordinate.initializeModel — zero coefficients.
        `_canonical` equals the device shard dtype without forcing a
        (possibly evicted/streamed) shard to materialize."""
        return FixedEffectModel(
            model_for_task(self.task_type,
                           Coefficients.zeros(self.dim, self._canonical)),
            self.config.feature_shard)

    def update(self, model: FixedEffectModel, offsets: jax.Array,
               schedule=None, outer_iteration: int = 0,
               num_outer_iterations: int = 1
               ) -> Tuple[FixedEffectModel, SolveResult]:
        """Refit with residual offsets (partial scores + base offsets).
        reference: FixedEffectCoordinate.updateModel -> runWithSampling.

        `schedule` (optim.schedule.SolverSchedule) turns this into an
        INEXACT solve: the (iteration cap, tolerance) for this outer
        iteration ride into the compiled program as traced operands."""
        opt = self.config.optimization
        budget = (None if schedule is None else schedule.budget_for(
            outer_iteration, num_outer_iterations, opt.optimizer))
        if self.streamed:
            # ONE [n] readback of the device-resident residual vector per
            # update (vs n*d of streamed feature traffic per oracle pass),
            # then the whole solve is host-stepped over chunk streams
            from photon_ml_tpu.optim.streaming import solve_streamed
            if not getattr(offsets, "is_fully_addressable", True):
                # multi-process residual vector: all-gather to host first
                # (a collective — safe because every process reaches this
                # same point of the lockstep coordinate loop)
                from photon_ml_tpu.parallel import multihost
                offsets = multihost.host_gather(offsets)
            off_host = np.asarray(  # photonlint: disable=PH001 -- the documented ONE [n] readback per streamed update
                offsets, dtype=self._canonical)
            obj = self._stream.replace(offsets=off_host)
            x0 = model.glm.coefficients.means
            if self.norm is not None:
                x0 = self.norm.model_to_transformed_space(x0)
            # coarse-early / polish-late lane selection: a schedule with a
            # stochastic lane runs early outer iterations as per-chunk
            # local epochs (one staging pass does local_epochs passes of
            # work) and leaves the trailing iterations on the strict
            # host-stepped solver (only SolverSchedule carries the lane;
            # the quarantine retry schedule duck-type does not)
            stoch = None
            stoch_plan = getattr(schedule, "stochastic_plan", None)
            if callable(stoch_plan):
                stoch = stoch_plan(outer_iteration, num_outer_iterations)
            res = solve_streamed(obj, x0, opt.optimizer, opt.regularization,
                                 jnp.asarray(opt.regularization_weight,
                                             self._canonical),
                                 budget=budget, stochastic=stoch)
            c = res.x
            if self.norm is not None:
                c = self.norm.model_to_original_space(c)
            return FixedEffectModel(
                model_for_task(self.task_type, Coefficients(c)),
                self.config.feature_shard), res
        weights = self.weights
        if opt.downsampling_rate is not None:
            self._key, sub = jax.random.split(self._key)
            keep, weights = downsampler_for_task(self.task_type)(
                sub, self.labels, self.weights, opt.downsampling_rate)
            weights = weights * keep
        x0 = model.glm.coefficients.means
        if self.norm is not None:
            x0 = self.norm.model_to_transformed_space(x0)
        if self.mesh is not None:
            # mesh-resident path: the objective's static arrays stage ONCE
            # per coordinate through the residency layer (the design matrix
            # from wherever `_mesh_x_source` says it lies); a warm visit
            # moves only offsets and x0
            obj = GLMObjective(self.loss, self._mesh_x_source(), self.labels,
                               weights=weights, offsets=offsets,
                               norm=self.norm)
            if self._admm_eligible:
                # feature-axis consensus-ADMM lane: design columns shard
                # over "feature" (2-D data x feature SPMD), per-iteration
                # cost = one feature-axis vector psum + one data-axis
                # block psum; the schedule maps budgets onto the ADMM
                # iterations and gates the monolithic polish to the
                # trailing outer iterations
                admm_cfg = opt.admm if opt.admm is not None else ADMMConfig()
                admm_budget = budget
                if schedule is not None:
                    admm_budget = schedule.budget_for(
                        outer_iteration, num_outer_iterations, admm_cfg)
                polish = None
                polish_gate = getattr(schedule, "admm_polish", None)
                if admm_cfg.polish and callable(polish_gate):
                    polish = polish_gate(outer_iteration,
                                         num_outer_iterations)
                res = fit_fixed_effect_admm(
                    obj, x0, self.mesh, admm_cfg, opt.optimizer,
                    opt.regularization, opt.regularization_weight,
                    budget=admm_budget, polish_budget=budget,
                    polish=polish, residency_key=self._mesh_key())
            else:
                res = fit_fixed_effect(obj, x0, self.mesh, opt.optimizer,
                                       opt.regularization,
                                       opt.regularization_weight,
                                       shard_features=self.shard_features,
                                       budget=budget,
                                       residency_key=self._mesh_key())
        else:
            obj = GLMObjective(self.loss, self.x, self.labels,
                               weights=weights, offsets=offsets,
                               norm=self.norm)
            if x0 is model.glm.coefficients.means:
                # the solver donates x0 (in-place buffer reuse); the model's
                # live coefficients may still be referenced by best-model /
                # checkpoint snapshots, so donate a copy, never the original
                with annotate("fe/stage"):
                    x0 = jnp.array(x0, copy=True)
            with annotate("fe/dispatch"):
                res = _cached_solver(opt.optimizer, opt.regularization,
                                     donate=True)(
                    obj, x0,
                    jnp.asarray(opt.regularization_weight, self.x.dtype),
                    budget)
        c = res.x
        if self.norm is not None:
            c = self.norm.model_to_original_space(c)
        new_model = FixedEffectModel(
            model_for_task(self.task_type, Coefficients(c)),
            self.config.feature_shard)
        return new_model, res

    def score(self, model: FixedEffectModel) -> jax.Array:
        """Margin contribution on the TRAINING data, canonical order.
        Streamed mode computes it chunk-by-chunk and returns ONE device [n]
        array — the flat residual-score vectors stay resident either way.
        The mesh path scores through the SAME staged sharded design matrix
        the update used (one residency entry per coordinate): rescoring
        moves no data, and scores come back sharded over "data"."""
        if self.streamed:
            return self._stream.scores(model.glm.coefficients.means)
        if self.mesh is not None and self._admm_eligible:
            # score through the SAME staged column grid the ADMM lane
            # trains on — an ADMM coordinate never stages a second
            # (monolithic) design copy just to score
            return score_fixed_effect_admm(model.glm, self._mesh_x_source(),
                                           self.mesh,
                                           residency_key=self._mesh_key())
        if self._data_div > 1:
            from photon_ml_tpu.parallel.fixed_effect import (
                _cached_scorer, staged_fixed_effect_x)
            n, x_dev = staged_fixed_effect_x(self._mesh_key(), self.mesh,
                                             self._mesh_x_source())
            with self.mesh:
                scores = _cached_scorer()(model.glm.coefficients.means,
                                          x_dev, None)
            return scores[:n]
        return fops.matvec(self.x, model.glm.coefficients.means)

    def regularization_term(self, model: FixedEffectModel) -> jax.Array:
        """reference: Coordinate.computeRegularizationTermValue.  For a
        normalized coordinate the solver penalized the NORMALIZED-space
        coefficients, so the term is computed in that space — keeping the
        logged objective consistent with the quantity actually minimized.
        Returned as a DEVICE scalar so the caller folds it into the
        objective with one readback (each float() blocks the host on the
        device)."""
        opt = self.config.optimization
        l1, l2 = opt.regularization.split(opt.regularization_weight)
        c = model.glm.coefficients.means
        if self.norm is not None:
            c = self.norm.model_to_transformed_space(c)
        return _penalty(c, l1, l2)


class _EntityCoordinateBase:
    """Shared setup for entity-keyed coordinates (plain and factored RE):
    build the per-entity dataset, the flat feature view, and the
    canonical-row -> entity-lane map used for scoring.

    Under an HBM budget (hbm_budget_bytes) the per-entity blocks are built
    with host copies kept (keep_host_blocks) and every device view here
    (flat shard, projection) is lazy — the residency manager can then evict
    this coordinate's device blocks after its update+score and the next
    visit re-streams them.  The [n] lane map stays resident (flat-vector
    class, ~d times smaller than any block)."""

    def __init__(self, name: str, dataset: GameDataset, config, task_type: str,
                 mesh=None, seed: int = 7,
                 hbm_budget_bytes: Optional[int] = None):
        self.name = name
        self.config = config
        self.task_type = task_type
        self.loss = TASK_LOSSES[task_type]
        self.mesh = mesh
        self.hbm_budget_bytes = hbm_budget_bytes
        self._dataset = dataset
        self.red: RandomEffectDataset = build_random_effect_dataset(
            dataset, config.data_config(
                seed, keep_host_blocks=hbm_budget_bytes is not None))
        # whether a visit gathers its offsets from the flat scores held in
        # VMEM (the rule: `RandomEffectDataset.vmem_offsets`)
        self.vmem_offsets = self.red.vmem_offsets(
            dataset.num_rows, one_device=mesh is None or mesh.size == 1)
        # what the build did with the rows, under the coordinate's name:
        # gauges for telemetry.snapshot(), the dict for the fit's summary;
        # `vmem_offsets` counts the gathers a visit that run the kernel
        self.build_stats = dict(self.red.build_counts,
                                vmem_offsets=int(self.vmem_offsets))
        for key, value in self.build_stats.items():
            if key != "buckets":
                gauge(f"train.re_build.{name}.{key}").set(value)
        for k, shape in enumerate(self.build_stats["buckets"]):
            for key, value in zip(("entities", "samples", "real_rows"),
                                  shape):
                gauge(f"train.re_build.{name}.bucket{k}.{key}").set(value)
        self._flat_x = None
        self._proj_dev = None
        if hbm_budget_bytes is None:
            self._flat_x = dataset.device_shard(config.feature_shard)
        self.lanes = self.red.flat_train_lanes(dataset)
        self.entity_id_values = np.asarray(
            dataset.entity_vocabs[config.random_effect_type])[self.red.entity_ids]

    @property
    def flat_x(self):
        """Device copy of the flat shard (scoring gathers through it),
        lazily re-streamed after an eviction."""
        if self._flat_x is None:
            self._flat_x = self._dataset.device_shard(
                self.config.feature_shard)
        return self._flat_x

    @property
    def proj_dev(self):
        """Device copy of the per-entity projection, transferred once per
        residency (the model threads the SAME host array through every
        update)."""
        if self._proj_dev is None and self.red.projection is not None:
            self._proj_dev = jnp.asarray(self.red.projection)
        return self._proj_dev

    # --- device residency ----------------------------------------------------
    def device_block_bytes(self) -> int:
        """Evictable device bytes: per-entity blocks + the flat shard view
        + the projection (shared shards are counted by every coordinate
        that uses them — an upper bound, i.e. conservative for
        under-budget claims)."""
        total = self.red.device_bytes()
        host_x = self._dataset.feature_shards[self.config.feature_shard]
        if self._flat_x is not None:
            total += sum(int(leaf.nbytes) for leaf in
                         jax.tree_util.tree_leaves(self._flat_x))
        elif isinstance(host_x, np.ndarray):
            itemsize = jnp.dtype(jax.dtypes.canonicalize_dtype(
                host_x.dtype)).itemsize
            total += int(host_x.shape[0]) * int(host_x.shape[1]) * itemsize
        if self.red.projection is not None:
            total += int(np.asarray(self.red.projection).nbytes)
        return total

    streamed = False  # FE-style chunk streaming does not apply to RE blocks

    def streaming_buffer_bytes(self) -> int:
        return 0

    def _mesh_key(self):
        """Residency key prefix of this coordinate's staged mesh arrays
        (buckets append their lane start; factored coordinates append
        "latent"/"kron" — all invalidate together via prefix match)."""
        return (self.name, id(self))

    def evict_device_blocks(self) -> None:
        """Residency-manager hook: drop this coordinate's device blocks
        (per-entity buckets, flat shard view, projection).  Safe mid-queue:
        XLA keeps buffers alive until in-flight consumers finish; the next
        visit's lazy accessors re-stream from the host copies.  The
        mesh-path invalidation is PER COORDINATE: only THIS coordinate's
        staged padded/sharded blocks drop from the residency layer — the
        old `clear_mesh_block_cache()` call here dropped every
        coordinate's memoized blocks on any eviction."""
        self.red.evict_device_blocks()
        self._flat_x = None
        self._proj_dev = None
        self._dataset.release_device_shard(self.config.feature_shard)
        if self.mesh is not None:
            from photon_ml_tpu.parallel.mesh_residency import invalidate
            invalidate(self._mesh_key())

    def _score_model(self, model) -> jax.Array:
        """All rows (active AND passive) scored against their entity's model
        via static gather — the reference's separate passive-data broadcast
        path (RandomEffectCoordinate.scala:178-210) collapses into this.
        Projection + gather + dot run as ONE fused program (one compile
        and one dispatch per shape, not one per op)."""
        from photon_ml_tpu.parallel.random_effect import (
            score_entities_matmul, score_entities_plain,
            score_entities_scatter)
        if isinstance(model, FactoredRandomEffectModel):
            return score_entities_matmul(model.latent_coefficients,
                                         model.projection, self.flat_x,
                                         self.lanes)
        if model.projection_matrix is not None:
            return score_entities_matmul(model.coefficients,
                                         model.projection_matrix,
                                         self.flat_x, self.lanes)
        if model.projection is not None:
            proj = (self.proj_dev if model.projection is self.red.projection
                    else jnp.asarray(model.projection))
            return score_entities_scatter(model.coefficients, proj,
                                          self.flat_x, self.lanes,
                                          global_dim=model.global_dim)
        return score_entities_plain(model.coefficients, self.flat_x,
                                    self.lanes)


class RandomEffectCoordinate(_EntityCoordinateBase):
    """Per-entity GLMs over one feature shard (reference:
    RandomEffectCoordinate.scala + the projected-space wrapper)."""

    def initial_model(self) -> RandomEffectModel:
        E, dl = self.red.num_entities, self.red.local_dim
        return RandomEffectModel(
            random_effect_type=self.config.random_effect_type,
            feature_shard=self.config.feature_shard,
            task_type=self.task_type,
            coefficients=jnp.zeros((E, dl), self.red.dtype),
            entity_ids=self.entity_id_values,
            projection=self.red.projection,
            global_dim=self.red.global_dim,
            projection_matrix=self.red.projection_matrix)

    def update(self, model: RandomEffectModel, offsets: jax.Array,
               schedule=None, outer_iteration: int = 0,
               num_outer_iterations: int = 1
               ) -> Tuple[RandomEffectModel, SolveResult]:
        """reference: RandomEffectCoordinate.updateModel — the 3-way join +
        per-entity local solves become one gather of every bucket's offsets
        + one batched solve per S-bucket (each size class runs its own
        compiled program; lanes are contiguous so results concatenate
        straight back into [E, d]).

        EVERY bucket's solve is dispatched before any result is touched —
        the concatenate below consumes nothing until all size classes are
        in the device queue, so the accelerator never drains between
        buckets.  Each bucket's x0 slice is donated to its solve for
        in-place buffer reuse.  One `schedule`-derived budget is shared by
        every bucket (unmapped traced operand of the batched solve)."""
        opt = self.config.optimization
        budget = (None if schedule is None else schedule.budget_for(
            outer_iteration, num_outer_iterations, opt.optimizer))
        results = []
        with annotate("re/offsets"):
            bucket_blocks = self.red.blocks_with_offsets(offsets,
                                                         self.vmem_offsets)
        for bucket, blocks in zip(self.red.buckets, bucket_blocks):
            with annotate("re/x0"):
                lo = bucket.lane_start
                x0 = model.coefficients[lo: lo + bucket.num_entities]
                if x0 is model.coefficients:
                    # a full-extent slice is returned as-is by jnp (single
                    # bucket spanning every lane): donating it would consume
                    # the model's live buffer, still referenced by best-model
                    # / checkpoint snapshots — donate a copy instead
                    x0 = jnp.array(x0, copy=True)
            with annotate("re/solve_call"):
                res_b = fit_random_effects(
                    blocks, self.loss, self.mesh, x0=x0,
                    config=opt.optimizer, reg=opt.regularization,
                    reg_weight=opt.regularization_weight,
                    donate_buffers=True, budget=budget,
                    cache_key=(*self._mesh_key(), bucket.lane_start))
            results.append(res_b)
        from photon_ml_tpu.parallel.mesh import concat_rows_safe
        res = (results[0] if len(results) == 1 else jax.tree_util.tree_map(
            lambda *a: concat_rows_safe(self.mesh, a, axis=0), *results))
        new_model = dataclasses.replace(model, coefficients=res.x)
        return new_model, res

    def score(self, model: RandomEffectModel) -> jax.Array:
        return self._score_model(model)

    def regularization_term(self, model: RandomEffectModel) -> jax.Array:
        """Sum over entities (reference: RandomEffectOptimizationProblem
        .getRegularizationTermValue — join + map + reduce, here one einsum);
        device scalar, folded into the objective readback by the caller."""
        opt = self.config.optimization
        l1, l2 = opt.regularization.split(opt.regularization_weight)
        return _penalty(model.coefficients, l1, l2)


class FactoredRandomEffectCoordinate(_EntityCoordinateBase):
    """Matrix-factorized per-entity GLMs: latent factors per entity + a
    shared projection matrix, refit alternately (reference:
    FactoredRandomEffectCoordinate.scala:40-281)."""

    def __init__(self, name: str, dataset: GameDataset,
                 config: FactoredRandomEffectCoordinateConfig, task_type: str,
                 mesh=None, seed: int = 7,
                 hbm_budget_bytes: Optional[int] = None):
        super().__init__(name, dataset, config, task_type, mesh, seed,
                         hbm_budget_bytes=hbm_budget_bytes)
        self.seed = seed
        self._key = jax.random.PRNGKey(seed + 1)
        # what `update` solves on, known from the build alone: the latent
        # half on the S-buckets `train.re_build.*` counts (`cells`), the
        # projection's refit on the shard's flat rows (`rows`), of either of
        # which `real_rows` train; `padded_cells` is what both halves read
        # a pass that trains nothing, `device_bytes` what an update makes
        # anew: the projected blocks, the factors by row, the flat weights
        red, rows = self.red, dataset.num_rows
        cells = red.build_counts["cells"]
        itemsize = jnp.dtype(jax.dtypes.canonicalize_dtype(red.dtype)).itemsize
        k = config.latent_dim
        self.build_stats = dict(self.build_stats, mf_build={
            "entities": red.num_entities, "samples": red.max_samples,
            "cells": cells, "rows": rows, "real_rows": red.num_active,
            "padded_cells": cells + rows - 2 * red.num_active,
            "latent_dim": k,
            "device_bytes": (cells * k + rows * (k + 1)) * itemsize})
        for key, value in self.build_stats["mf_build"].items():
            gauge(f"train.mf_build.{name}.{key}").set(value)

    @property
    def labels(self):
        """The dataset's device copy of the flat labels (the projection's
        refit reads the rows where they lie), in the blocks' dtype."""
        return self._dataset.device_vector("response", self.red.dtype)

    def initial_model(self) -> FactoredRandomEffectModel:
        """Zero latent factors + Gaussian random projection (reference:
        FactoredRandomEffectCoordinate.initializeModel, with
        isKeepingInterceptTerm=false)."""
        E = self.red.num_entities
        k = self.config.latent_dim
        d = self.red.global_dim
        dtype = self.red.dtype
        return FactoredRandomEffectModel(
            random_effect_type=self.config.random_effect_type,
            feature_shard=self.config.feature_shard,
            task_type=self.task_type,
            latent_coefficients=jnp.zeros((E, k), dtype),
            projection=gaussian_projection_matrix(k, d, keep_intercept=False,
                                                  seed=self.seed, dtype=dtype),
            entity_ids=self.entity_id_values,
            global_dim=d)

    def warm_start_latent(self, model: FactoredRandomEffectModel,
                          models) -> Optional[FactoredRandomEffectModel]:
        """Warm latent init from a sibling plain random-effect solution
        (same entity type, same feature shard, same global space): the
        Gaussian random projection is replaced with the top-k principal
        subspace of the sibling's coefficient matrix — the directions
        per-entity effects actually vary in — so the first alternation
        refines a meaningful subspace instead of discovering one from
        noise (what its visits cost on the chip from this start is in
        PERF.md section 5, cell `game-ml20m-mf.fit`; a Gaussian start is
        not measured).

        The latent FACTORS stay zero: the coordinate's initial score is
        unchanged, so the descent residual algebra sees no perturbation —
        in a sequence where the plain RE coordinate is also present, the
        MF coordinate fits the residual, for which zero is the honest
        start.  Returns None when no compatible sibling model exists in
        `models` (the coordinate then cold-starts exactly as before)."""
        sibling = None
        for other in models.values():
            if (isinstance(other, RandomEffectModel)
                    and other.random_effect_type
                    == self.config.random_effect_type
                    and other.feature_shard == self.config.feature_shard
                    and other.global_dim == self.red.global_dim):
                sibling = other
                break
        if sibling is None:
            return None
        w_global = sibling.global_coefficients()        # [E_s, d_global]
        # align the sibling's entity rows to THIS coordinate's lane order
        # (different active-data bounds can bucket the same entities into
        # different orders); entities the sibling never saw stay at zero
        lookup = {v: i for i, v in enumerate(np.asarray(sibling.entity_ids))}
        rows = np.fromiter((lookup.get(v, -1) for v in self.entity_id_values),
                           dtype=np.int64, count=len(self.entity_id_values))
        gathered = jnp.asarray(w_global)[np.maximum(rows, 0)]
        gathered = jnp.where(jnp.asarray(rows >= 0)[:, None], gathered, 0.0)
        from photon_ml_tpu.parallel.factored import (
            principal_subspace_projection)
        p = principal_subspace_projection(
            gathered.astype(model.projection.dtype), model.projection)
        return dataclasses.replace(model, projection=p)

    def update(self, model: FactoredRandomEffectModel, offsets: jax.Array,
               schedule=None, outer_iteration: int = 0,
               num_outer_iterations: int = 1
               ) -> Tuple[FactoredRandomEffectModel, FactoredSolveResult]:
        opt = self.config.optimization
        lat = self.config.latent_optimization
        re_budget = latent_budget = None
        if schedule is not None:
            # one schedule, two base configs: the latent-space and
            # projection-matrix solves each cap/loosen against their own
            # configured (max_iterations, tolerance)
            re_budget = schedule.budget_for(
                outer_iteration, num_outer_iterations, opt.optimizer)
            latent_budget = schedule.budget_for(
                outer_iteration, num_outer_iterations, lat.optimizer)
        # the latent solves run by S-bucket, as a plain random effect's do;
        # the projection's refit reads the shard's flat rows (the ones that
        # train at their block weights, the others at 0), under the
        # descent's own offsets: no single-S view of all entities, no second
        # copy of the features, no gather of the offsets for the refit
        with annotate("re/offsets"):
            blocks = self.red.blocks_with_offsets(offsets, self.vmem_offsets)
        rows = ProjectionRows(
            x=self.flat_x, labels=self.labels, lanes=self.lanes,
            weights=self.red.flat_active_weights(self._dataset),
            offsets=offsets)

        latent_row_weights_fn = None
        if lat.downsampling_rate is not None:
            sampler = downsampler_for_task(self.task_type)

            def latent_row_weights_fn(it: int):
                # fresh draw per inner iteration (reference: runWithSampling
                # called inside each updateLatentProjectionMatrix)
                self._key, sub = jax.random.split(self._key)
                keep, w = sampler(sub, rows.labels, None,
                                  lat.downsampling_rate)
                return keep * w

        res = fit_factored_random_effects(
            blocks, rows, self.loss, self.mesh,
            latent_coefficients=model.latent_coefficients,
            projection=model.projection,
            num_inner_iterations=self.config.num_inner_iterations,
            re_config=opt.optimizer, re_reg=opt.regularization,
            re_reg_weight=opt.regularization_weight,
            latent_config=lat.optimizer, latent_reg=lat.regularization,
            latent_reg_weight=lat.regularization_weight,
            latent_row_weights_fn=latent_row_weights_fn,
            re_budget=re_budget, latent_budget=latent_budget,
            cache_key=self._mesh_key())
        new_model = dataclasses.replace(
            model, latent_coefficients=res.latent_coefficients,
            projection=res.projection)
        return new_model, res

    def score(self, model: FactoredRandomEffectModel) -> jax.Array:
        """c_e . (P x) == (C @ P)[e] . x — one [E,k]x[k,d] matmul then the
        same entity-gather scoring as a plain random effect, fused."""
        return self._score_model(model)

    def regularization_term(self, model: FactoredRandomEffectModel) -> jax.Array:
        """RE term over latent factors + latent-problem term over P
        (reference: FactoredRandomEffectOptimizationProblem
        .getRegularizationTermValue); device scalar, folded into the
        objective readback by the caller."""
        opt, lat = self.config.optimization, self.config.latent_optimization
        l1, l2 = opt.regularization.split(opt.regularization_weight)
        pl1, pl2 = lat.regularization.split(lat.regularization_weight)
        return (_penalty(model.latent_coefficients, l1, l2)
                + _penalty(model.projection, pl1, pl2))


Coordinate = (FixedEffectCoordinate | RandomEffectCoordinate
              | FactoredRandomEffectCoordinate)
