"""Numerics helpers.

Rebuild of the reference's math utilities (reference: photon-lib
.../util/MathUtils.scala:22-48 and constants/MathConst.scala) as JAX-traceable
functions.  All functions are dtype-polymorphic: they inherit the dtype of
their inputs so the same code runs float64 (parity checks on CPU) and
float32/bfloat16 (TPU speed configs).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# reference: photon-lib/.../constants/MathConst.scala
EPSILON = 1e-12
POSITIVE_RESPONSE_THRESHOLD = 0.5
DEFAULT_SEED = 7


def ceil_pow2(v):
    """Smallest power of two >= v (v >= 1), elementwise over arrays and
    exact for scalars.  The ONE power-of-two shape rule: the serving
    micro-batcher and the streamed chunks pad to power-of-two sizes, and
    data/batching.py classes entities by it where no `max_buckets` caps the
    bucket count — each trades padding waste for a bounded set of XLA
    program shapes, and sharing the rule keeps them from drifting."""
    if np.isscalar(v) or np.ndim(v) == 0:
        return 1 << max(int(v) - 1, 0).bit_length()
    return 1 << np.ceil(np.log2(np.maximum(v, 1))).astype(np.int64)


def log1p_exp(x: jax.Array) -> jax.Array:
    """Numerically stable log(1 + exp(x)) (softplus).

    reference: photon-lib/.../util/MathUtils.scala:34 (log1pExp).  jax.nn.softplus
    is the XLA-fused stable formulation; we alias it so call sites mirror the
    reference naming.
    """
    return jax.nn.softplus(x)


def is_almost_zero(x: jax.Array, eps: float = EPSILON) -> jax.Array:
    """reference: MathUtils.scala isAlmostZero."""
    return jnp.abs(x) < eps


def safe_div(num: jax.Array, den: jax.Array, eps: float = EPSILON) -> jax.Array:
    """num/den with zero denominators mapped to zero output."""
    den_ok = jnp.abs(den) > eps
    return jnp.where(den_ok, num / jnp.where(den_ok, den, 1.0), 0.0)
