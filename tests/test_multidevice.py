"""The full product surface on the 8-device mesh, as a pytest.

VERDICT r2 item 8: the dryrun logic (GameEstimator.fit with fixed + random +
factored coordinates over a real Mesh) must live in the test suite with real
assertions — per-update objective decrease, and distributed == single-device
parity.  This is the "Spark local mode exercises all distributed paths"
posture of the reference's sparkTest fixture
(photon-test-utils/.../test/SparkTestUtils.scala:31-77) on the virtual
8-device CPU mesh from conftest.py.
"""
import numpy as np
import pytest

from photon_ml_tpu.data import build_game_dataset
from photon_ml_tpu.game import (
    FactoredRandomEffectCoordinateConfig, FixedEffectCoordinateConfig,
    GameEstimator, GameTrainingConfig, GLMOptimizationConfig,
    RandomEffectCoordinateConfig,
)
from photon_ml_tpu.optim import (
    OptimizerConfig, RegularizationContext, RegularizationType,
)
from photon_ml_tpu.parallel import make_mesh

L2 = RegularizationContext(RegularizationType.L2)


def _glmix_logistic(rng, n=1600, d_global=8, num_users=40, d_user=5):
    xg = rng.normal(size=(n, d_global)); xg[:, -1] = 1.0
    xu = rng.normal(size=(n, d_user)); xu[:, -1] = 1.0
    users = rng.integers(0, num_users, size=n)
    w_g = rng.normal(size=d_global)
    w_u = rng.normal(size=(num_users, d_user))
    z = xg @ w_g + np.einsum("nd,nd->n", xu, w_u[users])
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(float)
    ids = np.asarray([f"u{u:03d}" for u in users])
    return build_game_dataset(y, {"global": xg, "per_user": xu},
                              entity_ids={"userId": ids})


def _full_config(outer=2):
    opt = lambda w: GLMOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=25),
        regularization=L2, regularization_weight=w)
    return GameTrainingConfig(
        task_type="logistic_regression",
        coordinates={
            "fixed": FixedEffectCoordinateConfig("global", opt(0.1)),
            "perUser": RandomEffectCoordinateConfig(
                "userId", "per_user", opt(1.0)),
            "perUserMF": FactoredRandomEffectCoordinateConfig(
                "userId", "per_user", latent_dim=3,
                optimization=opt(1.0), latent_optimization=opt(1.0)),
        },
        updating_sequence=["fixed", "perUser", "perUserMF"],
        num_outer_iterations=outer)


@pytest.fixture(scope="module")
def glmix_splits():
    rng = np.random.default_rng(5)
    ds = _glmix_logistic(rng)
    rows = np.arange(ds.num_rows)
    return ds.subset(rows[:1200]), ds.subset(rows[1200:])


@pytest.fixture(scope="module")
def mesh_8x1_fit(glmix_splits):
    """The full configuration fitted on the 8x1 mesh: what both comparisons
    below hold their other side against."""
    train, val = glmix_splits
    return GameEstimator(_full_config(), mesh=make_mesh()).fit(train, val)


def test_full_surface_on_mesh(glmix_splits):
    """FE + RE + factored coordinates + grouped validation on 8 devices."""
    train, val = glmix_splits
    mesh = make_mesh()
    assert int(np.prod(list(mesh.shape.values()))) == 8
    res = GameEstimator(_full_config()).fit(
        train, val, evaluator_specs=["AUC", "AUC:userId"])
    hist = res.objective_history
    assert len(hist) == 2 * 3
    # every coordinate update must not increase the total objective
    drops = np.diff(np.asarray(hist))
    assert (drops <= 1e-6 * np.abs(np.asarray(hist[:-1]))).all(), hist
    assert res.validation["AUC"] > 0.75
    # grouped per-user AUC exists and is a sane probability-ranking score
    assert 0.4 < res.validation["AUC:userId"] <= 1.0


def test_mesh_matches_single_device(glmix_splits, mesh_8x1_fit):
    """GSPMD sharding must not change the math: same fit on the mesh and on
    one device, objective histories and validation metrics equal to
    tolerance (reference posture: distributed == local, e.g.
    DistributedObjectiveFunctionTest vs SingleNodeObjectiveFunctionTest)."""
    train, val = glmix_splits
    cfg = _full_config()
    res_mesh = mesh_8x1_fit
    res_one = GameEstimator(cfg, mesh=None).fit(train, val)
    np.testing.assert_allclose(res_mesh.objective_history,
                               res_one.objective_history,
                               rtol=1e-6, atol=1e-8)
    assert abs(res_mesh.validation["AUC"] - res_one.validation["AUC"]) < 1e-6


def test_feature_sharded_fixed_effect_on_mesh(glmix_splits, mesh_8x1_fit):
    """--mesh 4x2 regime: coefficients sharded over the feature axis must
    reproduce the data-parallel result (VERDICT r2 item 4: shard_features
    as a product path, auto-enabled by a 2-wide feature axis)."""
    train, val = glmix_splits
    cfg = _full_config()
    res_42 = GameEstimator(cfg, mesh=make_mesh(4, 2)).fit(train, val)
    res_8 = mesh_8x1_fit
    np.testing.assert_allclose(res_42.objective_history,
                               res_8.objective_history,
                               rtol=1e-6, atol=1e-8)
