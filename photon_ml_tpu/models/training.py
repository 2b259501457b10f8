"""Single-model GLM training: regularization sweep with warm start.

reference: ModelTraining.trainGeneralizedLinearModel
(photon-api/.../ModelTraining.scala:35-196): build loss function + optimization
problem, fold over the sorted regularization weights reusing the previous
solution as the next initial point (warm start, line 160-196), optionally
compute coefficient variances.

TPU design: the solve for the whole sweep is ONE compiled program per lambda
value reuse — the regularization weight is a *traced* scalar, so the sweep
runs k solves through a single XLA executable with zero recompilation (the
reference instead mutates optimizer/objective state per lambda).  Training
runs in normalized space and models are mapped back to the original space on
the way out (reference: GeneralizedLinearOptimizationProblem.createModel).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.glm import GeneralizedLinearModel, model_for_task
from photon_ml_tpu.ops import TASK_LOSSES, GLMObjective
from photon_ml_tpu.ops.features import FeatureMatrix, num_features
from photon_ml_tpu.ops.normalization import NormalizationContext
from photon_ml_tpu.optim import (
    OptimizerConfig, RegularizationContext, SolveResult, solve,
)


@dataclasses.dataclass
class TrainedModel:
    """One sweep entry: (lambda, model-in-original-space, tracker).

    reference: ModelTraining returns (lambda -> GLM) plus per-lambda
    ModelTracker (ModelTraining.scala:160-196)."""

    reg_weight: float
    model: GeneralizedLinearModel
    result: SolveResult
    # host-side wall clock of the whole solve (reference: the per-iteration
    # times in OptimizationStatesTracker.scala:32-102 — iterations run inside
    # one XLA program here, so the host can only observe the full solve)
    wall_s: float = 0.0


def train_glm(
    x: FeatureMatrix,
    labels: jax.Array,
    task_type: str,
    *,
    weights: Optional[jax.Array] = None,
    offsets: Optional[jax.Array] = None,
    optimizer_config: OptimizerConfig = OptimizerConfig(),
    regularization: RegularizationContext = RegularizationContext(),
    regularization_weights: Sequence[float] = (0.0,),
    normalization: Optional[NormalizationContext] = None,
    initial_model: Optional[GeneralizedLinearModel] = None,
    warm_start: bool = True,
    compute_variances: bool = False,
    index_map=None,
) -> list[TrainedModel]:
    """Train one GLM per regularization weight, strongest-first with warm
    starts.  Returns models in ORIGINAL feature space.  `index_map`
    resolves named feature constraints (optimizer_config.constraints) into
    positional bounds (reference: GLMSuite.createConstraintFeatureMap)."""
    if optimizer_config.constraints is not None:
        optimizer_config = optimizer_config.resolved_constraints(index_map)
    loss = TASK_LOSSES[task_type]
    d = num_features(x)
    dtype = labels.dtype if jnp.issubdtype(labels.dtype, jnp.floating) else jnp.float32

    objective = GLMObjective(loss, x, labels, weights=weights, offsets=offsets,
                             norm=normalization)

    # x0 is donated (reused in place for the solution): every start point
    # below is a buffer this function owns — fresh zeros, a copy of the
    # caller's initial model, or a copy at the warm-start handoff
    @functools.partial(jax.jit, donate_argnums=(0,))
    def _solve(x0: jax.Array, lam: jax.Array) -> SolveResult:
        return solve(objective, x0, optimizer_config, regularization, lam)

    @jax.jit
    def _hessian_diag(c_original: jax.Array, l2_w: jax.Array) -> jax.Array:
        # variances in original space without normalization, as the reference;
        # the L2 part of the current lambda contributes to the Hessian diagonal
        # (reference: L2Regularization.scala:164-165 adds l2RegWeight)
        return objective.replace(norm=None).with_l2(l2_w).hessian_diagonal(c_original)

    if initial_model is not None:
        x0 = initial_model.coefficients.means.astype(dtype)
        if normalization is not None:
            x0 = normalization.model_to_transformed_space(x0)
        if x0 is initial_model.coefficients.means:
            # same-dtype astype is a no-op: donating would consume the
            # caller's model coefficients
            x0 = jnp.array(x0, copy=True)
    else:
        x0 = jnp.zeros((d,), dtype)

    out: list[TrainedModel] = []
    # strongest regularization first so warm starts move from the most to the
    # least constrained problem (reference: ModelTraining.scala sorted sweep)
    for lam in sorted(regularization_weights, reverse=True):
        t0 = time.perf_counter()
        # without warm start the SAME x0 seeds every lambda: donate a copy
        # so the shared start point survives the sweep
        res = _solve(x0 if warm_start else jnp.array(x0, copy=True),
                     jnp.asarray(lam, dtype))
        jax.block_until_ready(res)  # dispatch is async: wall_s covers the solve
        wall_s = time.perf_counter() - t0
        c_norm = res.x
        c_orig = (normalization.model_to_original_space(c_norm)
                  if normalization is not None else c_norm)
        if compute_variances:
            _, l2_w = regularization.split(jnp.asarray(lam, dtype))
            coeffs = Coefficients.from_hessian_diagonal(
                c_orig, _hessian_diag(c_orig, l2_w))
        else:
            coeffs = Coefficients(c_orig)
        out.append(TrainedModel(float(lam), model_for_task(task_type, coeffs),
                                res, wall_s=wall_s))
        if warm_start:
            # c_norm is res.x, kept alive inside the returned TrainedModel;
            # the next solve donates its x0, so hand it a copy
            x0 = jnp.array(c_norm, copy=True)
    return out


def best_model_by_validation(
    trained: Sequence[TrainedModel],
    evaluate,  # model -> float, higher-is-better decided by caller
) -> TrainedModel:
    """reference: ModelSelection.selectBestLinearRegressionModel etc.
    (photon-client/.../ModelSelection.scala:95) — generic here; the evaluator
    module provides metric direction."""
    scores = [evaluate(t.model) for t in trained]
    return trained[int(max(range(len(scores)), key=lambda i: scores[i]))]
