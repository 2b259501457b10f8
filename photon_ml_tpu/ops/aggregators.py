"""Fused loss/gradient/Hessian kernels — the compute core.

TPU-native rebuild of the reference's streaming aggregators:
  - ValueAndGradientAggregator (photon-lib/.../function/glm/ValueAndGradientAggregator.scala:33-275)
  - HessianVectorAggregator    (.../HessianVectorAggregator.scala:36)
  - HessianDiagonalAggregator  (.../HessianDiagonalAggregator.scala:33)

Where the reference streams datum-by-datum inside a Spark treeAggregate, we
express each aggregate as a handful of batched XLA ops over [n, d] feature
matrices: one matvec for margins, the pointwise loss, and one rmatvec for
assembly.  XLA fuses the pointwise stages into the reductions; the matvec and
rmatvec land on the MXU.  Cross-device reduction (the treeAggregate
equivalent) is NOT done here — these kernels are per-shard and the parallel
layer wraps them in `shard_map` + `psum` (see photon_ml_tpu/parallel/).

Normalization is handled algebraically without materializing normalized
features, exactly as the reference does (ValueAndGradientAggregator.scala:35-79):
  effective coef e = c*factor;  margin z_i = x_i.e - e.shift + offset_i
  grad = (X^T(w*l') - shift * sum(w*l')) * factor
  Hv   = (X^T(w*l''*dz) - shift * sum(w*l''*dz)) * factor,
         dz_i = x_i.(v*factor) - (v*factor).shift

All functions are pure and jit/vmap/shard_map-safe.  Weights/offsets may be
None (interpreted as 1 / 0) to skip the multiply entirely.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from photon_ml_tpu.ops import features as fops
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.ops.normalization import NormalizationContext


def compute_margins(
    x: fops.FeatureMatrix,
    coefficients: jax.Array,
    offsets: Optional[jax.Array] = None,
    norm: Optional[NormalizationContext] = None,
) -> jax.Array:
    """z_i = x_i . (c*factor) - (c*factor).shift + offset_i.

    reference: LabeledPoint.computeMargin (photon-lib/.../data/LabeledPoint.scala:62)
    plus the aggregator's effectiveCoefficients/totalShift algebra."""
    if norm is not None and not norm.is_identity:
        e = norm.effective_coefficients(coefficients)
        z = fops.matvec(x, e) + norm.margin_shift(e)
    else:
        z = fops.matvec(x, coefficients)
    if offsets is not None:
        z = z + offsets
    return z


def _apply_weights(v: jax.Array, weights: Optional[jax.Array]) -> jax.Array:
    return v if weights is None else v * weights


def value_from_margins(
    loss: PointwiseLoss,
    z: jax.Array,
    labels: jax.Array,
    *,
    weights: Optional[jax.Array] = None,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """sum_i w_i l(z_i, y_i) from margins already in hand: no read of the
    features (reference: ValueAndGradientAggregator valueSum).  `mask` (0/1
    per row) supports padded batches — the TPU replacement for ragged
    per-entity data (rows with mask 0 contribute nothing; the reference has
    no equivalent because Spark handles raggedness)."""
    wl = _apply_weights(loss.loss(z, labels), weights)
    if mask is not None:
        # where() not multiply: a non-finite loss on a padded row must not
        # poison the aggregate (inf * 0 == nan)
        wl = jnp.where(mask != 0, wl, 0.0)
    return jnp.sum(wl)


def gradient_from_margins(
    loss: PointwiseLoss,
    x: fops.FeatureMatrix,
    z: jax.Array,
    labels: jax.Array,
    *,
    weights: Optional[jax.Array] = None,
    norm: Optional[NormalizationContext] = None,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """d/dc of sum_i w_i l(z_i, y_i) at margins z: one read of the features
    (reference: ValueAndGradientAggregator.scala:132-221, the gradient
    assembly)."""
    wdl = _apply_weights(loss.dz(z, labels), weights)
    if mask is not None:
        wdl = jnp.where(mask != 0, wdl, 0.0)
    grad = fops.rmatvec(x, wdl)
    if norm is not None and not norm.is_identity:
        if norm.shifts is not None:
            grad = grad - norm.shifts * jnp.sum(wdl)
        if norm.factors is not None:
            grad = grad * norm.factors
    return grad


def value_and_gradient(
    loss: PointwiseLoss,
    x: fops.FeatureMatrix,
    labels: jax.Array,
    coefficients: jax.Array,
    *,
    weights: Optional[jax.Array] = None,
    offsets: Optional[jax.Array] = None,
    norm: Optional[NormalizationContext] = None,
    mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(sum_i w_i l(z_i, y_i),  d/dc of it) in one fused pass: the margins
    of the point, then the value and the gradient from them."""
    z = compute_margins(x, coefficients, offsets, norm)
    return (value_from_margins(loss, z, labels, weights=weights, mask=mask),
            gradient_from_margins(loss, x, z, labels, weights=weights,
                                  norm=norm, mask=mask))


def value_only(
    loss: PointwiseLoss,
    x: fops.FeatureMatrix,
    labels: jax.Array,
    coefficients: jax.Array,
    *,
    weights: Optional[jax.Array] = None,
    offsets: Optional[jax.Array] = None,
    norm: Optional[NormalizationContext] = None,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """sum_i w_i l(z_i, y_i) (reference: ValueAndGradientAggregator valueSum)."""
    z = compute_margins(x, coefficients, offsets, norm)
    return value_from_margins(loss, z, labels, weights=weights, mask=mask)


def hessian_vector(
    loss: PointwiseLoss,
    x: fops.FeatureMatrix,
    labels: jax.Array,
    coefficients: jax.Array,
    vector: jax.Array,
    *,
    weights: Optional[jax.Array] = None,
    offsets: Optional[jax.Array] = None,
    norm: Optional[NormalizationContext] = None,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Hv = sum_i w_i l''(z_i, y_i) (x'_i . v) x'_i  in normalized space.

    reference: HessianVectorAggregator.scala:41-135 (effectiveMultiplyVector /
    featureVectorProductShift algebra).  This is the oracle TRON's truncated
    CG calls once per CG step (TRON.scala:301)."""
    z = compute_margins(x, coefficients, offsets, norm)
    d2 = loss.d2z(z, labels)
    if norm is not None and not norm.is_identity:
        ev = norm.effective_coefficients(vector)
        dz = fops.matvec(x, ev) + norm.margin_shift(ev)
    else:
        dz = fops.matvec(x, vector)
    wd2dz = _apply_weights(d2 * dz, weights)
    if mask is not None:
        wd2dz = jnp.where(mask != 0, wd2dz, 0.0)
    hv = fops.rmatvec(x, wd2dz)
    if norm is not None and not norm.is_identity:
        if norm.shifts is not None:
            hv = hv - norm.shifts * jnp.sum(wd2dz)
        if norm.factors is not None:
            hv = hv * norm.factors
    return hv


def hessian_diagonal(
    loss: PointwiseLoss,
    x: fops.FeatureMatrix,
    labels: jax.Array,
    coefficients: jax.Array,
    *,
    weights: Optional[jax.Array] = None,
    offsets: Optional[jax.Array] = None,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """diag(H) = sum_i w_i l'' x_i**2 — used for coefficient-variance
    estimation var ~= 1/(diag(H)+eps).

    reference: HessianDiagonalAggregator.scala:33 (which, like this function,
    does NOT support normalization — variances are computed in original space;
    see DistributedOptimizationProblem.computeVariances:80-95)."""
    z = compute_margins(x, coefficients, offsets, None)
    wd2 = _apply_weights(loss.d2z(z, labels), weights)
    if mask is not None:
        wd2 = jnp.where(mask != 0, wd2, 0.0)
    return fops.sq_rmatvec(x, wd2)
