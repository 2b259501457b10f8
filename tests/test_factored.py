"""Factored random effects + matrix factorization.

Mirrors the reference's FactoredRandomEffectCoordinate / MatrixFactorization
integration tests: a GAME fit with a factored coordinate must beat a
fixed-effect-only model on synthetic low-rank mixed data, the alternation
must decrease the objective, save->load->score must round-trip, and the
KroneckerDesign implicit feature matrix must agree with the materialized
Kronecker product.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data import build_game_dataset
from photon_ml_tpu.game import (
    FactoredRandomEffectCoordinateConfig, FixedEffectCoordinateConfig,
    GameEstimator, GameTrainingConfig, GLMOptimizationConfig,
)
from photon_ml_tpu.models import (
    FactoredRandomEffectModel, MatrixFactorizationModel,
)
from photon_ml_tpu.models.io import load_game_model, save_game_model
from photon_ml_tpu.ops import GLMObjective, LOGISTIC, SQUARED, features as fops
from photon_ml_tpu.optim import RegularizationContext, RegularizationType
from photon_ml_tpu.parallel import (
    ProjectionRows, gaussian_projection_matrix, fit_factored_random_effects,
    project_blocks,
)
from photon_ml_tpu.parallel.random_effect import EntityBlocks

L2 = RegularizationContext(RegularizationType.L2)


def lowrank_data(rng, n=1500, d_global=6, num_users=40, d_user=12, k_true=2):
    """Global effect + per-user deviations that live on a shared rank-k_true
    subspace — the regime factored RE is built for (many entities, few
    samples each, shared structure)."""
    xg = rng.normal(size=(n, d_global)); xg[:, -1] = 1.0
    xu = rng.normal(size=(n, d_user))
    users = rng.integers(0, num_users, size=n)
    w_global = rng.normal(size=d_global)
    basis = rng.normal(size=(k_true, d_user))          # shared latent basis
    c_user = rng.normal(size=(num_users, k_true))      # per-user factors
    w_user = c_user @ basis
    z = xg @ w_global + np.einsum("nd,nd->n", xu, w_user[users])
    y = z + 0.1 * rng.normal(size=n)
    ids = np.asarray([f"u{u:03d}" for u in users])
    return xg, xu, ids, y


def _dataset(rng, **kw):
    xg, xu, ids, y = lowrank_data(rng, **kw)
    return build_game_dataset(y, {"global": xg, "per_user": xu},
                              entity_ids={"userId": ids})


def _factored_config(latent_dim=4, inner=2, iters=2):
    return GameTrainingConfig(
        task_type="linear_regression",
        coordinates={
            "fixed": FixedEffectCoordinateConfig(
                "global", GLMOptimizationConfig(regularization=L2,
                                                regularization_weight=0.1)),
            "perUserMF": FactoredRandomEffectCoordinateConfig(
                random_effect_type="userId", feature_shard="per_user",
                latent_dim=latent_dim, num_inner_iterations=inner,
                optimization=GLMOptimizationConfig(
                    regularization=L2, regularization_weight=1.0),
                latent_optimization=GLMOptimizationConfig(
                    regularization=L2, regularization_weight=0.1)),
        },
        updating_sequence=["fixed", "perUserMF"],
        num_outer_iterations=iters)


# -- KroneckerDesign kernel identities ---------------------------------------

def test_kronecker_design_matches_materialized(rng):
    n, d, k = 40, 5, 3
    x = jnp.asarray(rng.normal(size=(n, d)))
    c = jnp.asarray(rng.normal(size=(n, k)))
    design = fops.KroneckerDesign(x, c)
    dense = fops.densify(design)
    assert dense.shape == (n, k * d)
    v = jnp.asarray(rng.normal(size=(k * d,)))
    u = jnp.asarray(rng.normal(size=(n,)))
    np.testing.assert_allclose(fops.matvec(design, v), dense @ v, rtol=1e-5)
    np.testing.assert_allclose(fops.rmatvec(design, u), dense.T @ u, rtol=1e-5)
    np.testing.assert_allclose(fops.sq_rmatvec(design, u),
                               (dense * dense).T @ u, rtol=1e-5)


def test_kronecker_objective_gradient_finite_difference(rng):
    n, d, k = 30, 4, 2
    x = jnp.asarray(rng.normal(size=(n, d)))
    c = jnp.asarray(rng.normal(size=(n, k)))
    y = jnp.asarray((rng.uniform(size=n) > 0.5).astype(float))
    obj = GLMObjective(LOGISTIC, fops.KroneckerDesign(x, c), y)
    p = jnp.asarray(rng.normal(size=(k * d,)) * 0.1)
    v, g = obj.value_and_gradient(p)
    g_auto = jax.grad(obj.value)(p)
    np.testing.assert_allclose(g, g_auto, rtol=1e-4, atol=1e-6)


def test_gaussian_projection_matrix_properties():
    p = gaussian_projection_matrix(5, 20, keep_intercept=False, seed=3)
    assert p.shape == (5, 20)
    assert float(jnp.max(jnp.abs(p))) <= 1.0
    # std ~ 1/k (reference deliberately uses std=k not sqrt(k))
    assert float(jnp.std(p)) < 2.5 / 5
    pi = gaussian_projection_matrix(5, 20, keep_intercept=True, seed=3)
    assert pi.shape == (6, 20)
    np.testing.assert_array_equal(np.asarray(pi[-1]),
                                  np.eye(20)[-1])  # intercept selector row


# -- alternation solver -------------------------------------------------------

def test_alternation_decreases_objective(rng):
    E, S, d, k = 12, 20, 8, 3
    x = rng.normal(size=(E, S, d))
    basis = rng.normal(size=(k, d))
    c_true = rng.normal(size=(E, k))
    z = np.einsum("esd,ed->es", x, c_true @ basis)
    y = z + 0.05 * rng.normal(size=(E, S))
    blocks = EntityBlocks(x=jnp.asarray(x), labels=jnp.asarray(y),
                          mask=jnp.ones((E, S)))
    # the same cells as flat rows, for the projection's refit
    rows = ProjectionRows(
        x=blocks.x.reshape(E * S, d), labels=blocks.labels.reshape(E * S),
        lanes=jnp.repeat(jnp.arange(E), S), weights=blocks.mask.reshape(-1))
    C0 = jnp.zeros((E, k))
    P0 = gaussian_projection_matrix(k, d, seed=11, dtype=jnp.float64)

    def total_loss(C, P):
        lat = project_blocks(blocks, P)
        z_hat = jnp.einsum("esk,ek->es", lat.x, C)
        return float(jnp.mean((z_hat - blocks.labels) ** 2))

    loss0 = total_loss(C0, P0)
    res1 = fit_factored_random_effects(
        [blocks], rows, SQUARED, latent_coefficients=C0, projection=P0,
        num_inner_iterations=1, re_reg=L2, re_reg_weight=1e-3,
        latent_reg=L2, latent_reg_weight=1e-3)
    loss1 = total_loss(res1.latent_coefficients, res1.projection)
    res3 = fit_factored_random_effects(
        [blocks], rows, SQUARED, latent_coefficients=C0, projection=P0,
        num_inner_iterations=3, re_reg=L2, re_reg_weight=1e-3,
        latent_reg=L2, latent_reg_weight=1e-3)
    loss3 = total_loss(res3.latent_coefficients, res3.projection)
    assert loss1 < loss0 * 0.5
    assert loss3 <= loss1 * 1.001
    # the rank-k structure is recoverable: near the noise floor
    assert loss3 < 0.02


# -- GAME integration ---------------------------------------------------------

def test_game_factored_beats_fixed_only(rng):
    ds = _dataset(rng)
    rows = np.arange(ds.num_rows)
    train, val = ds.subset(rows[:1100]), ds.subset(rows[1100:])

    res = GameEstimator(_factored_config()).fit(train, val)
    rmse_mf = res.validation["RMSE"]

    fe_only = GameTrainingConfig(
        task_type="linear_regression",
        coordinates={"fixed": FixedEffectCoordinateConfig(
            "global", GLMOptimizationConfig(regularization=L2,
                                            regularization_weight=0.1))},
        updating_sequence=["fixed"])
    rmse_fe = GameEstimator(fe_only).fit(train, val).validation["RMSE"]
    assert rmse_mf < rmse_fe * 0.8, (
        "factored RE must clearly beat fixed-only on low-rank mixed data "
        f"(got {rmse_mf:.4f} vs {rmse_fe:.4f})")
    hist = res.objective_history
    assert hist[-1] <= hist[0]
    model = res.model.coordinates["perUserMF"]
    assert isinstance(model, FactoredRandomEffectModel)
    assert model.latent_dim == 4


def test_factored_save_load_score_roundtrip(rng, tmp_path):
    ds = _dataset(rng, n=600, num_users=15)
    res = GameEstimator(_factored_config(iters=1)).fit(ds)
    save_game_model(res.model, str(tmp_path / "m"), config=res.config)
    loaded, cfg = load_game_model(str(tmp_path / "m"))
    np.testing.assert_allclose(np.asarray(loaded.score_dataset(ds)),
                               np.asarray(res.model.score_dataset(ds)),
                               rtol=1e-6)
    assert cfg == res.config  # config JSON round-trip incl. factored kind


def test_factored_unseen_entity_scores_zero(rng):
    ds = _dataset(rng, n=400, num_users=10)
    res = GameEstimator(_factored_config(iters=1)).fit(ds)
    m = res.model.coordinates["perUserMF"]
    val = build_game_dataset(
        np.zeros(2),
        {"global": np.ones((2, 6)), "per_user": np.ones((2, 12))},
        entity_ids={"userId": np.asarray(["zzz", "u000"])})
    s = np.asarray(m.score_dataset(val))
    assert s[0] == 0.0
    assert s[1] != 0.0 or np.allclose(np.asarray(m.latent_coefficients), 0)


# -- Gaussian random-projection projector for plain random effects -----------

def test_random_projection_projector_random_effects(rng):
    """reference: ProjectorType.RandomProjection(dim) — per-entity problems
    solved in a shared k-dim Gaussian-projected space; coefficients map back
    to the original space via P^T c."""
    from photon_ml_tpu.data.batching import (
        RandomEffectDataConfig, build_random_effect_dataset)
    from photon_ml_tpu.game import RandomEffectCoordinateConfig

    ds = _dataset(rng, n=800, num_users=20)
    red = build_random_effect_dataset(
        ds, RandomEffectDataConfig("userId", "per_user",
                                   projector="random_projection:5"))
    assert red.blocks.dim == 6          # k + intercept selector row
    assert red.projection_matrix.shape == (6, 12)
    assert red.projection is None

    cfg = GameTrainingConfig(
        task_type="linear_regression",
        coordinates={
            "fixed": FixedEffectCoordinateConfig(
                "global", GLMOptimizationConfig(regularization=L2,
                                                regularization_weight=0.1)),
            "perUser": RandomEffectCoordinateConfig(
                random_effect_type="userId", feature_shard="per_user",
                projector="random_projection:5",
                optimization=GLMOptimizationConfig(
                    regularization=L2, regularization_weight=1.0)),
        },
        updating_sequence=["fixed", "perUser"], num_outer_iterations=2)
    rows = np.arange(ds.num_rows)
    train, val = ds.subset(rows[:600]), ds.subset(rows[600:])
    res = GameEstimator(cfg).fit(train, val)
    assert np.isfinite(res.validation["RMSE"])
    m = res.model.coordinates["perUser"]
    assert m.projection_matrix is not None
    assert m.global_coefficients().shape == (m.num_entities, 12)


# -- matrix factorization -----------------------------------------------------

def test_matrix_factorization_model_scoring(rng):
    R, C, k = 6, 5, 3
    rf = rng.normal(size=(R, k))
    cf = rng.normal(size=(C, k))
    row_ids = np.asarray([f"r{i}" for i in range(R)])
    col_ids = np.asarray([f"c{j}" for j in range(C)])
    mf = MatrixFactorizationModel("memberId", "itemId",
                                  jnp.asarray(rf), row_ids,
                                  jnp.asarray(cf), col_ids)
    assert mf.num_latent_factors == k
    rows = np.asarray(["r0", "r3", "r5", "nope"])
    cols = np.asarray(["c1", "c4", "nope", "c0"])
    ds = build_game_dataset(
        np.zeros(4), {"dummy": np.ones((4, 1))},
        entity_ids={"memberId": rows, "itemId": cols})
    s = np.asarray(mf.score_dataset(ds))
    np.testing.assert_allclose(s[0], rf[0] @ cf[1], rtol=1e-6)
    np.testing.assert_allclose(s[1], rf[3] @ cf[4], rtol=1e-6)
    assert s[2] == 0.0 and s[3] == 0.0  # either side unseen -> 0


def test_matrix_factorization_from_factored_one_hot(rng):
    """One-hot col-indicator features make factored RE == MF exactly."""
    num_rows_e, num_cols_e, k, n = 8, 6, 3, 300
    r_idx = rng.integers(0, num_rows_e, size=n)
    c_idx = rng.integers(0, num_cols_e, size=n)
    x = np.eye(num_cols_e)[c_idx]                    # one-hot, no intercept
    C = jnp.asarray(rng.normal(size=(num_rows_e, k)))
    P = jnp.asarray(rng.normal(size=(k, num_cols_e)))
    row_ids = np.asarray([f"m{i}" for i in range(num_rows_e)])
    col_ids = np.asarray([f"i{j}" for j in range(num_cols_e)])
    fre = FactoredRandomEffectModel(
        random_effect_type="memberId", feature_shard="items",
        task_type="linear_regression", latent_coefficients=C, projection=P,
        entity_ids=row_ids, global_dim=num_cols_e)
    mf = MatrixFactorizationModel.from_factored(fre, "itemId", col_ids)
    ds = build_game_dataset(
        np.zeros(n), {"items": x},
        entity_ids={"memberId": row_ids[r_idx], "itemId": col_ids[c_idx]})
    np.testing.assert_allclose(np.asarray(mf.score_dataset(ds)),
                               np.asarray(fre.score_dataset(ds)), rtol=1e-5)


def test_mf_save_load_roundtrip(rng, tmp_path):
    from photon_ml_tpu.models.game import GameModel
    mf = MatrixFactorizationModel(
        "memberId", "itemId",
        jnp.asarray(rng.normal(size=(4, 2))), np.asarray(["a", "b", "c", "d"]),
        jnp.asarray(rng.normal(size=(3, 2))), np.asarray(["x", "y", "z"]))
    gm = GameModel({"mf": mf}, task_type="linear_regression")
    save_game_model(gm, str(tmp_path / "mf"))
    loaded, _ = load_game_model(str(tmp_path / "mf"))
    lm = loaded.coordinates["mf"]
    np.testing.assert_allclose(np.asarray(lm.row_factors),
                               np.asarray(mf.row_factors))
    np.testing.assert_allclose(np.asarray(lm.col_factors),
                               np.asarray(mf.col_factors))
    assert list(lm.row_ids) == list(mf.row_ids)
