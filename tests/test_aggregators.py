"""Aggregator kernels vs autodiff, dense vs sparse, and normalization algebra.

Mirrors reference tests for the aggregators / objective functions
(photon-api/src/test/.../function/glm/SingleNodeObjectiveFunctionTest.scala)
plus the normalization-invariance checks from GameEstimatorTest.scala:125-180.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import sparse as jsparse

from photon_ml_tpu.ops import aggregators as agg
from photon_ml_tpu.ops import losses
from photon_ml_tpu.ops.normalization import build_normalization_context
from photon_ml_tpu.ops.objective import GLMObjective
from tests.synthetic import make_glm_data

LOSS_TASK = [(losses.LOGISTIC, "logistic"), (losses.SQUARED, "linear"),
             (losses.POISSON, "poisson"), (losses.SMOOTHED_HINGE, "hinge")]


def _norm_ctx(x, kind, intercept=None):
    xm = jnp.asarray(x)
    return build_normalization_context(
        kind,
        mean=xm.mean(0),
        variance=xm.var(0, ddof=1),
        max_magnitude=jnp.abs(xm).max(0),
        intercept_index=intercept,
    )


@pytest.mark.parametrize("loss,task", LOSS_TASK, ids=lambda p: getattr(p, "name", str(p)))
def test_value_and_gradient_matches_autodiff(loss, task, rng):
    x, y, w, _ = make_glm_data(rng, n=128, d=7, task=task, weight_range=(0.5, 2.0))
    offsets = rng.normal(size=128) * 0.3
    c = jnp.asarray(rng.normal(size=7))
    x, y, w, offsets = map(jnp.asarray, (x, y, w, offsets))

    def f(c):
        return agg.value_only(loss, x, y, c, weights=w, offsets=offsets)

    v, g = agg.value_and_gradient(loss, x, y, c, weights=w, offsets=offsets)
    np.testing.assert_allclose(v, f(c), rtol=1e-12)
    np.testing.assert_allclose(g, jax.grad(f)(c), rtol=1e-9, atol=1e-10)


@pytest.mark.parametrize("loss,task", [p for p in LOSS_TASK if p[0].twice_differentiable],
                         ids=lambda p: getattr(p, "name", str(p)))
def test_hessian_vector_matches_autodiff(loss, task, rng):
    x, y, w, _ = make_glm_data(rng, n=96, d=6, task=task, weight_range=(0.5, 2.0))
    c = jnp.asarray(rng.normal(size=6) * 0.5)
    v = jnp.asarray(rng.normal(size=6))
    x, y, w = map(jnp.asarray, (x, y, w))

    def f(c):
        return agg.value_only(loss, x, y, c, weights=w)

    got = agg.hessian_vector(loss, x, y, c, v, weights=w)
    want = jax.jvp(jax.grad(f), (c,), (v,))[1]
    np.testing.assert_allclose(got, want, rtol=1e-8, atol=1e-9)


def test_hessian_diagonal_matches_autodiff(rng):
    x, y, w, _ = make_glm_data(rng, n=96, d=6, task="logistic", weight_range=(0.5, 2.0))
    c = jnp.asarray(rng.normal(size=6) * 0.5)
    x, y, w = map(jnp.asarray, (x, y, w))

    def f(c):
        return agg.value_only(losses.LOGISTIC, x, y, c, weights=w)

    got = agg.hessian_diagonal(losses.LOGISTIC, x, y, c, weights=w)
    want = jnp.diag(jax.hessian(f)(c))
    np.testing.assert_allclose(got, want, rtol=1e-8, atol=1e-9)


@pytest.mark.parametrize("kind", ["scale_with_standard_deviation",
                                  "scale_with_max_magnitude", "standardization"])
def test_normalized_kernels_equal_materialized(kind, rng):
    """The shift/factor algebra must equal actually normalizing the features.

    reference: ValueAndGradientAggregator.scala:35-79 contract."""
    x, y, w, _ = make_glm_data(rng, n=128, d=5, task="logistic", weight_range=(0.5, 2.0))
    c = jnp.asarray(rng.normal(size=5))
    norm = _norm_ctx(x, kind, intercept=4)
    xj, yj, wj = map(jnp.asarray, (x, y, w))

    factors = norm.factors if norm.factors is not None else jnp.ones(5)
    shifts = norm.shifts if norm.shifts is not None else jnp.zeros(5)
    x_mat = (xj - shifts) * factors  # materialized normalized features

    v_alg, g_alg = agg.value_and_gradient(losses.LOGISTIC, xj, yj, c, weights=wj, norm=norm)
    v_mat, g_mat = agg.value_and_gradient(losses.LOGISTIC, x_mat, yj, c, weights=wj)
    np.testing.assert_allclose(v_alg, v_mat, rtol=1e-10)
    np.testing.assert_allclose(g_alg, g_mat, rtol=1e-9, atol=1e-10)

    hv_alg = agg.hessian_vector(losses.LOGISTIC, xj, yj, c, g_alg, weights=wj, norm=norm)
    hv_mat = agg.hessian_vector(losses.LOGISTIC, x_mat, yj, c, g_mat, weights=wj)
    np.testing.assert_allclose(hv_alg, hv_mat, rtol=1e-9, atol=1e-10)


def test_sparse_matches_dense(rng):
    x, y, _, _ = make_glm_data(rng, n=64, d=20, task="logistic")
    x[np.abs(x) < 0.8] = 0.0  # sparsify
    c = jnp.asarray(rng.normal(size=20))
    xd = jnp.asarray(x)
    xs = jsparse.BCOO.fromdense(xd)
    y = jnp.asarray(y)

    vd, gd = agg.value_and_gradient(losses.LOGISTIC, xd, y, c)
    vs, gs = agg.value_and_gradient(losses.LOGISTIC, xs, y, c)
    np.testing.assert_allclose(vs, vd, rtol=1e-12)
    np.testing.assert_allclose(gs, gd, rtol=1e-10, atol=1e-12)

    hvd = agg.hessian_vector(losses.LOGISTIC, xd, y, c, gd)
    hvs = agg.hessian_vector(losses.LOGISTIC, xs, y, c, gd)
    np.testing.assert_allclose(hvs, hvd, rtol=1e-10, atol=1e-12)

    hdd = agg.hessian_diagonal(losses.LOGISTIC, xd, y, c)
    hds = agg.hessian_diagonal(losses.LOGISTIC, xs, y, c)
    np.testing.assert_allclose(hds, hdd, rtol=1e-10, atol=1e-12)


def test_mask_equals_subset(rng):
    """Padded rows with mask=0 must contribute nothing (TPU raggedness story)."""
    x, y, w, _ = make_glm_data(rng, n=50, d=4, task="logistic", weight_range=(0.5, 2.0))
    c = jnp.asarray(rng.normal(size=4))
    mask = np.zeros(50); mask[:37] = 1.0
    v_m, g_m = agg.value_and_gradient(losses.LOGISTIC, jnp.asarray(x), jnp.asarray(y), c,
                                      weights=jnp.asarray(w), mask=jnp.asarray(mask))
    v_s, g_s = agg.value_and_gradient(losses.LOGISTIC, jnp.asarray(x[:37]), jnp.asarray(y[:37]),
                                      c, weights=jnp.asarray(w[:37]))
    np.testing.assert_allclose(v_m, v_s, rtol=1e-12)
    np.testing.assert_allclose(g_m, g_s, rtol=1e-12)


def test_objective_l2_and_pytree(rng):
    x, y, w, _ = make_glm_data(rng, n=64, d=5, task="logistic")
    obj = GLMObjective(losses.LOGISTIC, jnp.asarray(x), jnp.asarray(y), l2_weight=0.7)
    c = jnp.asarray(rng.normal(size=5))

    v, g = obj.value_and_gradient(c)
    np.testing.assert_allclose(v, obj.value(c), rtol=1e-12)
    np.testing.assert_allclose(g, jax.grad(obj.value)(c), rtol=1e-9, atol=1e-10)
    hv = obj.hessian_vector(c, g)
    np.testing.assert_allclose(hv, jax.jvp(jax.grad(obj.value), (c,), (g,))[1],
                               rtol=1e-8, atol=1e-9)

    # must survive a jit round-trip as an argument (pytree correctness)
    @jax.jit
    def run(o, c):
        return o.value(c)
    np.testing.assert_allclose(run(obj, c), obj.value(c), rtol=1e-12)
