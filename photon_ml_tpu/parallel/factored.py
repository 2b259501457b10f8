"""Factored random effects: per-entity latent factors x shared projection.

Rebuild of the reference's matrix-factorization tower:
  - FactoredRandomEffectCoordinate.updateModel alternation
    (photon-api/.../algorithm/FactoredRandomEffectCoordinate.scala:100-160):
    per inner iteration, (a) refit per-entity coefficients in the latent
    space, (b) refit the shared latent projection matrix as a distributed
    GLM problem over kron(features, coefficients) data
  - FactoredRandomEffectOptimizationProblem
    (photon-api/.../optimization/game/FactoredRandomEffectOptimizationProblem.scala:42-194)
  - ProjectionMatrix.buildGaussianRandomProjectionMatrix
    (photon-api/.../projector/ProjectionMatrix.scala:95-125)

TPU design: step (a) reuses the vmapped entity-sharded solver
(fit_random_effects) on blocks projected through P with one einsum — the
reference's per-entity `projectFeatures` loop is a single [E,S,d]x[k,d]
contraction on the MXU — bucket by bucket where the blocks are S-bucketed.
Step (b) never materializes the kron design matrix
the reference shuffles through Spark: `KroneckerDesign` (ops/features.py)
computes the margin/gradient products directly from X and the gathered
latent factors, and the solve runs through the SAME distributed fixed-effect
path (rows sharded over the mesh, GSPMD psum) as any other GLM.  A
coordinate hands step (b) the shard's rows where they lie (`ProjectionRows`:
weight 0 on a row that does not train), since nothing in one GLM over all
rows is per entity but the factors: at the benchmark's size a single padded
view of all entities beside the buckets did not leave the chip the memory its
scoring program needs (PERF.md section 6, PR 35).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from photon_ml_tpu.ops import GLMObjective
from photon_ml_tpu.ops.features import KroneckerDesign
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.optim import (
    OptimizerConfig, RegularizationContext, SolveResult, solve,
)
from photon_ml_tpu.parallel.fixed_effect import _cached_solver, fit_fixed_effect
from photon_ml_tpu.parallel.mesh import concat_rows_safe
from photon_ml_tpu.parallel.random_effect import EntityBlocks, fit_random_effects
from photon_ml_tpu.telemetry import annotate


def gaussian_projection_matrix(
    latent_dim: int,
    original_dim: int,
    keep_intercept: bool = False,
    seed: int = 7,
    dtype=jnp.float32,
) -> jax.Array:
    """[k(+1), d] Gaussian random projection, rows = projected dims.

    Entries ~ N(0, 1) / k, clipped to [-1, 1] — the reference deliberately
    uses std = k (not the conventional sqrt(k)) to keep entries small
    (ProjectionMatrix.scala:95-125, comment at line ~100).  With
    `keep_intercept`, one extra row selects the intercept column (last, per
    the IndexMap intercept-last convention)."""
    key = jax.random.PRNGKey(seed)
    p = jnp.clip(jax.random.normal(key, (latent_dim, original_dim)) / latent_dim,
                 -1.0, 1.0).astype(dtype)
    if keep_intercept:
        e_last = jnp.zeros((1, original_dim), dtype).at[0, original_dim - 1].set(1.0)
        p = jnp.concatenate([p, e_last], axis=0)
    return p


def project_blocks(blocks: EntityBlocks, projection: jax.Array) -> EntityBlocks:
    """Features -> latent space: one [E,S,d]x[k,d] MXU contraction
    (reference: ProjectionMatrixBroadcast.projectRandomEffectDataSet, which
    instead maps projectFeatures over every per-entity LocalDataSet)."""
    x_lat = jnp.einsum("esd,kd->esk", blocks.x, projection,
                       precision=jax.lax.Precision.HIGHEST)  # float32 as float32
    return dataclasses.replace(blocks, x=x_lat * blocks.mask[:, :, None])


@jax.jit
def principal_subspace_projection(w: jax.Array,
                                  fallback: jax.Array) -> jax.Array:
    """Warm [k, d] latent projection from a sibling solution matrix.

    Rows = the top-k right singular vectors of w (an [E, d] plain
    random-effect coefficient matrix): the directions per-entity effects
    ACTUALLY vary in, instead of the cold Gaussian start whose subspace the
    first alternation must discover from noise (what the coordinate's
    visits cost on the chip from this start: PERF.md section 5, cell
    `game-ml20m-mf.fit`; from a Gaussian start: not measured).  The latent
    factors stay zero, so the coordinate's initial score — and therefore
    the descent state — is unperturbed.  `fallback` (the existing Gaussian
    projection) fills rows beyond w's rank and takes over entirely for a
    degenerate (all-zero) w, where SVD directions are arbitrary."""
    k = fallback.shape[0]
    _, s, vt = jnp.linalg.svd(w, full_matrices=False)
    rows = jnp.minimum(k, vt.shape[0])
    take = jnp.arange(k) < rows
    top = jnp.where(take[:, None], vt[jnp.minimum(jnp.arange(k),
                                                  vt.shape[0] - 1)], fallback)
    # a zero singular value means the "direction" is arbitrary noise — keep
    # the Gaussian row instead (also covers an all-zero sibling solution)
    informative = (s[jnp.minimum(jnp.arange(k), s.shape[0] - 1)]
                   > 1e-7 * jnp.maximum(s[0], 1e-30)) & take
    return jnp.where(informative[:, None], top, fallback).astype(
        fallback.dtype)


@dataclasses.dataclass
class FactoredSolveResult:
    latent_coefficients: jax.Array   # [E, k]
    projection: jax.Array            # [k, d]
    # last inner iteration, [E]-leading; its `lockstep` has a row for every
    # latent run of every inner iteration
    random_effect_result: Optional[SolveResult]
    latent_result: Optional[SolveResult]         # last inner iteration


@dataclasses.dataclass
class ProjectionRows:
    """The projection refit's design as FLAT rows: the rows of a feature
    shard as they lie, each with the lane of its entity and its training
    weight, instead of the rows gathered into padded per-entity blocks.

    The refit is one GLM over all training rows; nothing in it is per
    entity but the factors a row is paired with.  So it needs no block
    layout: a row that does not train (passive, discarded, of an unseen
    entity) carries weight 0 and adds nothing, the offsets are the descent's
    own flat vector (no gather into blocks), and `x` is the shard the
    coordinate already holds for scoring (no second copy of the features)."""

    x: jax.Array                          # [n, d]
    labels: jax.Array                     # [n]
    lanes: jax.Array                      # [n] entity lane, < 0 for none
    weights: jax.Array                    # [n] 0 where the row does not train
    offsets: Optional[jax.Array] = None   # [n]


def refit_latent_projection(
    rows: ProjectionRows,
    latent_coefficients: jax.Array,
    projection: jax.Array,
    loss: PointwiseLoss,
    mesh: Optional[Mesh] = None,
    config: OptimizerConfig = OptimizerConfig(),
    reg: RegularizationContext = RegularizationContext(),
    reg_weight: jax.Array | float = 0.0,
    row_weights: Optional[jax.Array] = None,
    budget=None,
    cache_key=None,
) -> Tuple[jax.Array, SolveResult]:
    """One projection-matrix refit: treat flatten(P) as the coefficient
    vector of a GLM over the implicit kron(c_e, x) design, one row per
    row of `rows` (the factors gathered by lane), warm-started from the
    current P.

    reference: FactoredRandomEffectCoordinate.updateLatentProjectionMatrix
    (scala:~200-250) — there the kron rows are materialized and shuffled;
    here KroneckerDesign keeps the design implicit.  `row_weights` lets the
    caller apply down-sampling (reference: runWithSampling with the optional
    latent sampler).

    On a mesh with `cache_key`, the STATIC half of the Kronecker design
    (x rows, labels, weights — stable across visits) stages through the
    mesh residency layer once; only the latent factors, offsets and P
    itself move per visit.  Without a key the legacy whole-objective staging
    runs.

    The operands are made under `fe/stage` and the jitted call alone under
    `fe/dispatch`, as the fixed effect has them: the refit runs the fixed
    effect's program (`jit_fe_solve`), and a trace pairs each run of it
    with the `fe/dispatch` span its call was made in."""
    k, d = projection.shape
    keyed = mesh is not None and cache_key is not None
    with annotate("fe/stage"):
        lanes = jnp.clip(rows.lanes, 0, latent_coefficients.shape[0] - 1)
        factors = latent_coefficients[lanes]                      # [n, k]
        x, labels, weights, offsets = (rows.x, rows.labels, rows.weights,
                                       rows.offsets)
        p0 = projection.reshape(-1)
        if keyed:
            from photon_ml_tpu.parallel.mesh_residency import (
                default_residency)
            res_reg = default_residency()
            key = (*cache_key, "kron") if isinstance(cache_key, tuple) \
                else (cache_key, "kron")
            x = res_reg.stage_static(key, "x", mesh, x, 0.0)
            labels = res_reg.stage_static(key, "labels", mesh, labels, 0.5)
            weights = res_reg.stage_static(key, "weights", mesh, weights,
                                           0.0)
        if row_weights is not None:
            # a fresh sampling draw every visit: warm by definition
            weights = weights * row_weights
            if keyed:
                weights = res_reg.stage_update(mesh, weights, 0.0, key=key,
                                               field="weights")
        if keyed:
            factors = res_reg.stage_update(mesh, factors, 0.0, key=key,
                                           field="factors")
            offsets = res_reg.stage_update(mesh, offsets, 0.0, key=key,
                                           field="offsets")
            p0 = res_reg.stage_update(mesh, p0, spec="replicated", key=key,
                                      field="p0")
        obj = GLMObjective(loss, KroneckerDesign(x, factors), labels,
                           weights=weights, offsets=offsets)
    if mesh is not None and not keyed:
        # the legacy whole-objective staging, under its own two spans
        res = fit_fixed_effect(obj, p0, mesh, config, reg, reg_weight,
                               budget=budget)
        return res.x.reshape(k, d), res
    lam = jnp.asarray(reg_weight, p0.dtype)
    with (mesh if keyed else contextlib.nullcontext()), \
            annotate("fe/dispatch"):
        res = _cached_solver(config, reg)(obj, p0, lam, budget)
    return res.x.reshape(k, d), res


def fit_factored_random_effects(
    buckets: Sequence[EntityBlocks],
    rows: ProjectionRows,
    loss: PointwiseLoss,
    mesh: Optional[Mesh] = None,
    *,
    latent_coefficients: jax.Array,
    projection: jax.Array,
    num_inner_iterations: int = 1,
    re_config: OptimizerConfig = OptimizerConfig(),
    re_reg: RegularizationContext = RegularizationContext(),
    re_reg_weight: jax.Array | float = 0.0,
    latent_config: OptimizerConfig = OptimizerConfig(),
    latent_reg: RegularizationContext = RegularizationContext(),
    latent_reg_weight: jax.Array | float = 0.0,
    latent_row_weights_fn: Optional[Callable[[int], Optional[jax.Array]]] = None,
    re_budget=None,
    latent_budget=None,
    cache_key=None,
) -> FactoredSolveResult:
    """The alternation loop (reference: FactoredRandomEffectCoordinate
    .updateModel, scala:100-160): numInnerIterations rounds of
    per-entity-latent-solve then projection-matrix refit.

    `buckets` are the S-buckets of a `RandomEffectDataset` in lane order
    (contiguous runs of lanes, each padded to its own S): the latent solves
    run bucket by bucket, as a plain random effect's do, every bucket
    dispatched before any result is read.  `rows` is the same training rows
    as a flat view, for the projection's refit.

    `latent_row_weights_fn(iteration)` supplies optional per-row sampling
    weights for the latent refit (fresh draw per inner iteration, matching
    runWithSampling's behavior).  `re_budget`/`latent_budget` apply one
    dynamic solve budget (optim/schedule.py) to every alternation round's
    latent-space and projection-matrix solves respectively."""
    C, P = latent_coefficients, projection
    re_res = lat_res = None
    as_tuple = cache_key if isinstance(cache_key, tuple) else (cache_key,)
    counted = []    # every latent run's lock-step row, in the order run
    for it in range(num_inner_iterations):
        results, lane = [], 0
        for bucket in buckets:
            with annotate("re/x0"):
                latent_blocks = project_blocks(bucket, P)
                x0 = C[lane: lane + bucket.num_entities]
            with annotate("re/solve_call"):
                results.append(fit_random_effects(
                    latent_blocks, loss, mesh, x0=x0, config=re_config,
                    reg=re_reg, reg_weight=re_reg_weight, budget=re_budget,
                    cache_key=(None if cache_key is None
                               else (*as_tuple, "latent", lane))))
            lane += bucket.num_entities
        re_res = jax.tree_util.tree_map(
            lambda *a: concat_rows_safe(mesh, a, axis=0), *results)
        counted.append(re_res.lockstep)
        C = re_res.x
        rw = latent_row_weights_fn(it) if latent_row_weights_fn else None
        P, lat_res = refit_latent_projection(
            rows, C, P, loss, mesh, latent_config, latent_reg,
            latent_reg_weight, row_weights=rw, budget=latent_budget,
            cache_key=cache_key)
    if len(counted) > 1 and counted[0] is not None:
        re_res = re_res._replace(
            lockstep=concat_rows_safe(mesh, counted, axis=0))
    return FactoredSolveResult(latent_coefficients=C, projection=P,
                               random_effect_result=re_res,
                               latent_result=lat_res)
