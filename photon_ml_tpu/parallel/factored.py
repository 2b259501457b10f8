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
contraction on the MXU.  Step (b) never materializes the kron design matrix
the reference shuffles through Spark: `KroneckerDesign` (ops/features.py)
computes the margin/gradient products directly from X and the gathered
latent factors, and the solve runs through the SAME distributed fixed-effect
path (rows sharded over the mesh, GSPMD psum) as any other GLM.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

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
from photon_ml_tpu.parallel.random_effect import EntityBlocks, fit_random_effects


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
    x_lat = jnp.einsum("esd,kd->esk", blocks.x, projection)
    return dataclasses.replace(blocks, x=x_lat * blocks.mask[:, :, None])


@jax.jit
def principal_subspace_projection(w: jax.Array,
                                  fallback: jax.Array) -> jax.Array:
    """Warm [k, d] latent projection from a sibling solution matrix.

    Rows = the top-k right singular vectors of w (an [E, d] plain
    random-effect coefficient matrix): the directions per-entity effects
    ACTUALLY vary in, instead of the cold Gaussian start whose subspace the
    first alternation must discover from noise (the cold first MF solve is
    the cost ROADMAP S3 chases).  The latent
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
    random_effect_result: Optional[SolveResult]  # last inner iteration, [E]-leading
    latent_result: Optional[SolveResult]         # last inner iteration


def refit_latent_projection(
    blocks: EntityBlocks,
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
    """One projection-matrix refit: flatten the active blocks to rows, treat
    flatten(P) as the coefficient vector of a GLM over the implicit
    kron(c_e, x) design, warm-start from the current P.

    reference: FactoredRandomEffectCoordinate.updateLatentProjectionMatrix
    (scala:~200-250) — there the kron rows are materialized and shuffled;
    here KroneckerDesign keeps the design implicit.  `row_weights` lets the
    caller apply down-sampling (reference: runWithSampling with the optional
    latent sampler).

    On a mesh with `cache_key`, the STATIC half of the Kronecker design
    (x rows, labels, mask — all derived from the blocks, which coordinate
    descent keeps stable across visits) stages through the mesh residency
    layer once; only the latent factors, offsets and P itself move per
    visit.  Without a key the legacy whole-objective staging runs."""
    E, S, d = blocks.x.shape
    k = latent_coefficients.shape[1]
    n = E * S
    factors = jnp.repeat(latent_coefficients, S, axis=0)          # [n, k]
    weights = None if blocks.weights is None else blocks.weights.reshape(n)
    if row_weights is not None:
        weights = row_weights if weights is None else weights * row_weights
    offsets = None if blocks.offsets is None else blocks.offsets.reshape(n)
    p0 = projection.reshape(-1)

    if mesh is not None and cache_key is not None:
        from photon_ml_tpu.parallel.mesh_residency import default_residency
        res_reg = default_residency()
        key = (*cache_key, "kron") if isinstance(cache_key, tuple) \
            else (cache_key, "kron")
        x_dev = res_reg.stage_static(key, "x", mesh, blocks.x, 0.0,
                                     build=lambda: blocks.x.reshape(n, d))
        labels_dev = res_reg.stage_static(
            key, "labels", mesh, blocks.labels, 0.5,
            build=lambda: blocks.labels.reshape(n))
        mask_dev = res_reg.stage_static(
            key, "mask", mesh, blocks.mask, 0.0,
            build=lambda: blocks.mask.reshape(n))
        if weights is None:
            weights_dev = None
        elif row_weights is None:
            weights_dev = res_reg.stage_static(
                key, "weights", mesh, blocks.weights, 0.0,
                build=lambda: blocks.weights.reshape(n))
        else:  # fresh sampling draw every visit: warm by definition
            weights_dev = res_reg.stage_update(mesh, weights, 0.0, key=key,
                                               field="weights")
        factors_dev = res_reg.stage_update(mesh, factors, 0.0, key=key,
                                           field="factors")
        offsets_dev = res_reg.stage_update(mesh, offsets, 0.0, key=key,
                                           field="offsets")
        obj = GLMObjective(loss, KroneckerDesign(x_dev, factors_dev),
                           labels_dev, weights=weights_dev,
                           offsets=offsets_dev, mask=mask_dev)
        p0_dev = res_reg.stage_update(mesh, p0, spec="replicated", key=key,
                                      field="p0")
        with mesh:
            res = _cached_solver(config, reg)(
                obj, p0_dev, jnp.asarray(reg_weight, p0.dtype), budget)
        return res.x.reshape(k, d), res

    design = KroneckerDesign(blocks.x.reshape(n, d), factors)
    obj = GLMObjective(loss, design, blocks.labels.reshape(n),
                       weights=weights, offsets=offsets,
                       mask=blocks.mask.reshape(n))
    if mesh is not None:
        res = fit_fixed_effect(obj, p0, mesh, config, reg, reg_weight,
                               budget=budget)
    else:
        res = _cached_solver(config, reg)(obj, p0,
                                          jnp.asarray(reg_weight, p0.dtype),
                                          budget)
    return res.x.reshape(k, d), res


def fit_factored_random_effects(
    blocks: EntityBlocks,
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

    `latent_row_weights_fn(iteration)` supplies optional per-row sampling
    weights for the latent refit (fresh draw per inner iteration, matching
    runWithSampling's behavior).  `re_budget`/`latent_budget` apply one
    dynamic solve budget (optim/schedule.py) to every alternation round's
    latent-space and projection-matrix solves respectively."""
    C, P = latent_coefficients, projection
    re_res = lat_res = None
    latent_key = None
    if cache_key is not None:
        latent_key = ((*cache_key, "latent") if isinstance(cache_key, tuple)
                      else (cache_key, "latent"))
    for it in range(num_inner_iterations):
        latent_blocks = project_blocks(blocks, P)
        re_res = fit_random_effects(latent_blocks, loss, mesh, x0=C,
                                    config=re_config, reg=re_reg,
                                    reg_weight=re_reg_weight,
                                    budget=re_budget, cache_key=latent_key)
        C = re_res.x
        rw = latent_row_weights_fn(it) if latent_row_weights_fn else None
        P, lat_res = refit_latent_projection(
            blocks, C, P, loss, mesh, latent_config, latent_reg,
            latent_reg_weight, row_weights=rw, budget=latent_budget,
            cache_key=cache_key)
    return FactoredSolveResult(latent_coefficients=C, projection=P,
                               random_effect_result=re_res,
                               latent_result=lat_res)
