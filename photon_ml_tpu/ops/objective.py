"""Objective functions: data + loss + regularization bundled as a pytree.

Rebuild of the reference's ObjectiveFunction tower —
ObjectiveFunction/DiffFunction/TwiceDiffFunction (photon-lib/.../function/
{ObjectiveFunction,DiffFunction,TwiceDiffFunction}.scala), the stackable
L2Regularization mixins (L2Regularization.scala:25-181), and the GLM loss
functions Distributed/SingleNodeGLMLossFunction (photon-api/.../function/glm/).

The reference needed two parallel class hierarchies (Distributed over
RDD+Broadcast, SingleNode over Iterable) because the data's location changed
the types.  Here there is exactly ONE objective type: a pytree whose feature
block may live on one device, be sharded over a mesh axis (fixed effect), or
carry a leading entity axis consumed by vmap (random effects).  Distribution
is a property of how the caller wraps the solve (shard_map / vmap), not of the
objective — that collapse is the main API simplification of the TPU design.

L1 regularization is intentionally absent here: as in the reference, L1/the L1
part of elastic net is handled inside the OWLQN optimizer via pseudo-gradients
(reference: OWLQN.scala:40-86), not by the objective.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from photon_ml_tpu.ops import aggregators as agg
from photon_ml_tpu.ops import features as fops
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.ops.normalization import NormalizationContext


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class GLMObjective:
    """Weighted GLM loss over a batch, with optional L2 term.

    value(c)            = sum_i w_i l(z_i, y_i) + l2/2 ||c||^2
    value_and_gradient  = fused single pass (reference DiffFunction.calculate)
    hessian_vector(c,v) (reference TwiceDiffFunction.hessianVector)
    hessian_diagonal(c) (reference TwiceDiffFunction.hessianDiagonal)

    `mask` marks valid rows in padded batches (TPU replacement for ragged
    per-entity data).  `l2_weight` is a traced scalar so lambda sweeps can
    jit once and re-run per lambda (the reference instead mutates the
    L2Regularization mixin's weight: L2Regularization.scala l2RegWeight setter).
    """

    loss: PointwiseLoss  # static
    x: fops.FeatureMatrix
    labels: jax.Array
    weights: Optional[jax.Array] = None
    offsets: Optional[jax.Array] = None
    mask: Optional[jax.Array] = None
    norm: Optional[NormalizationContext] = None
    l2_weight: jax.Array | float = 0.0

    def tree_flatten(self):
        children = (self.x, self.labels, self.weights, self.offsets,
                    self.mask, self.norm, self.l2_weight)
        return children, self.loss

    @classmethod
    def tree_unflatten(cls, loss, children):
        return cls(loss, *children)

    # -- DiffFunction surface -------------------------------------------------
    @property
    def dim(self) -> int:
        return fops.num_features(self.x)

    def value(self, c: jax.Array) -> jax.Array:
        return self.value_from_margins(self.margins(c), c)

    def value_and_gradient(self, c: jax.Array) -> Tuple[jax.Array, jax.Array]:
        z = self.margins(c)
        return self.value_from_margins(z, c), self.gradient_from_margins(z, c)

    # -- margin surface -------------------------------------------------------
    # A GLM's margin is affine in the coefficients, z(c + t p) = z(c) + t u
    # with u = direction_margins(p), so a line search along p can evaluate
    # its trial points from z and u without reading the features
    # (optim/lbfgs.py).
    def margins(self, c: jax.Array) -> jax.Array:
        return agg.compute_margins(self.x, c, self.offsets, self.norm)

    def direction_margins(self, p: jax.Array) -> jax.Array:
        return agg.compute_margins(self.x, p, None, self.norm)

    def value_from_margins(self, z: jax.Array, c: jax.Array) -> jax.Array:
        v = agg.value_from_margins(self.loss, z, self.labels,
                                   weights=self.weights, mask=self.mask)
        return v + 0.5 * self.l2_weight * jnp.dot(c, c)

    def gradient_from_margins(self, z: jax.Array, c: jax.Array) -> jax.Array:
        g = agg.gradient_from_margins(self.loss, self.x, z, self.labels,
                                      weights=self.weights, norm=self.norm,
                                      mask=self.mask)
        return g + self.l2_weight * c

    # -- TwiceDiffFunction surface --------------------------------------------
    def hessian_vector(self, c: jax.Array, v: jax.Array) -> jax.Array:
        hv = agg.hessian_vector(self.loss, self.x, self.labels, c, v,
                                weights=self.weights, offsets=self.offsets,
                                norm=self.norm, mask=self.mask)
        return hv + self.l2_weight * v

    def hessian_diagonal(self, c: jax.Array) -> jax.Array:
        """Original-space only: the aggregator has no normalization support
        (reference: HessianDiagonalAggregator.scala), so calling this on a
        normalized objective would silently mix spaces."""
        if self.norm is not None and not self.norm.is_identity:
            raise ValueError(
                "hessian_diagonal is original-space only; use "
                "objective.replace(norm=None) with original-space coefficients")
        hd = agg.hessian_diagonal(self.loss, self.x, self.labels, c,
                                  weights=self.weights, offsets=self.offsets,
                                  mask=self.mask)
        return hd + self.l2_weight

    # -- helpers --------------------------------------------------------------
    def replace(self, **kw) -> "GLMObjective":
        return dataclasses.replace(self, **kw)

    def with_l2(self, l2_weight) -> "GLMObjective":
        """reference: DistributedOptimizationProblem.updateRegularizationWeight."""
        return dataclasses.replace(self, l2_weight=l2_weight)
