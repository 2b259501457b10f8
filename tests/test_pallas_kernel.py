"""Pallas fused value+gradient kernel == XLA aggregator.

The kernel is a measured experiment (see its module docstring: XLA's own
fusion wins on these shapes, so the product path stays on the XLA
aggregator) — but it must stay CORRECT so the recipe remains trustworthy.
Runs in interpreter mode on the CPU test mesh.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from photon_ml_tpu.ops import LOGISTIC, POISSON, SQUARED, aggregators
from photon_ml_tpu.ops.pallas_kernels import fused_value_and_gradient


@pytest.mark.parametrize("loss", [LOGISTIC, SQUARED, POISSON],
                         ids=lambda l: l.name)
@pytest.mark.parametrize("with_wo", [False, True], ids=["plain", "w+o"])
def test_matches_xla_aggregator(loss, with_wo, rng):
    n, d = 700, 37   # deliberately unaligned: exercises row/column padding
    x = rng.normal(size=(n, d)).astype(np.float32)
    y = (rng.uniform(size=n) > 0.5).astype(np.float32)
    c = (rng.normal(size=d) * 0.1).astype(np.float32)
    w = rng.uniform(0.5, 2.0, size=n).astype(np.float32) if with_wo else None
    o = (rng.normal(size=n) * 0.1).astype(np.float32) if with_wo else None

    v, g = fused_value_and_gradient(
        loss, jnp.asarray(x), jnp.asarray(y), jnp.asarray(c),
        None if w is None else jnp.asarray(w),
        None if o is None else jnp.asarray(o), True)
    v2, g2 = aggregators.value_and_gradient(
        loss, jnp.asarray(x), jnp.asarray(y), jnp.asarray(c),
        weights=None if w is None else jnp.asarray(w),
        offsets=None if o is None else jnp.asarray(o))
    np.testing.assert_allclose(float(v), float(v2), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g2),
                               rtol=1e-4, atol=1e-4)
