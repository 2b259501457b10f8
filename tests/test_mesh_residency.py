"""Mesh-resident coordinate data (ISSUE 6): strict f64 mesh-vs-single-device
parity (including the mesh-streamed path), the warm-iteration no-retransfer
contract, per-coordinate invalidation, compile-count stability across mesh
shapes, and fault injection through the mesh.stage site.

The transfer contract: after a coordinate's static arrays are staged
(padded + sharded over the mesh "data" axis) once, a warm outer iteration
stages ZERO cold bytes — only per-visit operands (residual offsets, x0)
move, bounded by coefficients+offsets.  TransferStats makes this
observable; the tests gate on it so the re-transfer regression that
motivated the layer cannot creep back.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data import build_game_dataset
from photon_ml_tpu.game import (
    FactoredRandomEffectCoordinateConfig, FixedEffectCoordinateConfig,
    GameEstimator, GameTrainingConfig, GLMOptimizationConfig,
    RandomEffectCoordinateConfig,
)
from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent
from photon_ml_tpu.optim import (
    OptimizerConfig, RegularizationContext, RegularizationType,
)
from photon_ml_tpu.parallel import make_mesh
from photon_ml_tpu.parallel.mesh_residency import (
    MeshStagingError, TransferStats, default_residency, transfer_snapshot,
)
from photon_ml_tpu.utils import faults
from test_pipeline import _compile_counting

L2 = RegularizationContext(RegularizationType.L2)


def _glmix(rng, n=1600, d_global=10, num_users=64, d_user=4, num_items=0,
           d_item=0):
    xg = rng.normal(size=(n, d_global)); xg[:, -1] = 1.0
    xu = rng.normal(size=(n, d_user)); xu[:, -1] = 1.0
    users = np.arange(n) % num_users
    z = xg @ rng.normal(size=d_global) + np.einsum(
        "nd,nd->n", xu, rng.normal(size=(num_users, d_user))[users])
    shards = {"global": xg, "per_user": xu}
    entity_ids = {"userId": np.asarray([f"u{u:03d}" for u in users])}
    if num_items:
        xi = rng.normal(size=(n, d_item)); xi[:, -1] = 1.0
        items = np.arange(n) % num_items
        z = z + np.einsum("nd,nd->n", xi,
                          rng.normal(size=(num_items, d_item))[items])
        shards["per_item"] = xi
        entity_ids["itemId"] = np.asarray([f"i{i:03d}" for i in items])
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(float)
    ds = build_game_dataset(y, shards, entity_ids=entity_ids)
    rows = np.arange(n)
    cut = int(n * 0.9)
    return ds.subset(rows[:cut]), ds.subset(rows[cut:])


def _opt(w, iters=8, tolerance=None):
    return GLMOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=iters, tolerance=tolerance),
        regularization=L2, regularization_weight=w)


def _config(outer=2, iters=8, with_item=False, with_mf=False, budget=None,
            tolerance=None):
    def opt(w):
        return _opt(w, iters, tolerance)
    coords = {"fixed": FixedEffectCoordinateConfig("global", opt(1.0)),
              "perUser": RandomEffectCoordinateConfig(
                  "userId", "per_user", opt(1.0), projector="identity")}
    seq = ["fixed", "perUser"]
    if with_item:
        coords["perItem"] = RandomEffectCoordinateConfig(
            "itemId", "per_item", opt(1.0), projector="identity")
        seq.append("perItem")
    if with_mf:
        coords["perUserMF"] = FactoredRandomEffectCoordinateConfig(
            "userId", "per_user", latent_dim=2, num_inner_iterations=1,
            optimization=opt(1.0), latent_optimization=opt(0.5))
        seq.append("perUserMF")
    return GameTrainingConfig(
        task_type="logistic_regression", coordinates=coords,
        updating_sequence=seq, num_outer_iterations=outer,
        hbm_budget_bytes=budget)


# -- strict f64 parity (ISSUE 6 satellite) ------------------------------------

def test_mesh_parity_fe_re_factored_strict(rng):
    """Mesh and single-device fits of the FULL surface (FE + RE + factored
    MF) produce numerically identical objective histories in f64 — GSPMD
    sharding + the residency layer's pad/shard must not change the math."""
    train, val = _glmix(rng)
    # Solves stop at 1e-5, not the default 1e-7, so that no lane ends on the
    # floor of f: at 1e-7 a lane's last step gains (1e-7)^2 of f, about one
    # ulp, its Armijo test is decided by rounding, and the 1e-16 by which
    # the FE solve's psum order moves the offsets sends the two fits down
    # different branches of one lane.  That is the solver's doing, on a mesh
    # or off it, not the sharding's: over rng seeds 0..29 the last entry of
    # the history then differs by 1e-11 in 10 fits of 30 before the line
    # search ran on cached margins and in 12 of 30 after (this fixture's
    # seed 7 among the latter), and at 1e-5 in none of 30, by at most 4e-16.
    cfg = _config(with_mf=True, tolerance=1e-5)
    one = GameEstimator(cfg).fit(train, val)
    mesh = GameEstimator(cfg, mesh=make_mesh()).fit(train, val)
    assert len(one.objective_history) == len(mesh.objective_history)
    np.testing.assert_allclose(mesh.objective_history, one.objective_history,
                               rtol=1e-12, atol=0)
    assert mesh.mesh_transfer is not None
    assert mesh.mesh_transfer["cold_bytes"] > 0


def test_mesh_streamed_parity_and_per_device_budget(rng):
    """Mesh x out-of-core (the previously forbidden combination): a config
    whose per-device data exceeds the per-device budget trains on the
    8-device mesh with the FE shard chunk-streamed, matching the RESIDENT
    single-device reference in f64, with tracked per-device peak under the
    budget."""
    train, val = _glmix(rng, n=2400, d_global=96, num_users=80, d_user=4)
    resident = GameEstimator(_config(iters=6)).fit(train, val)

    acct = resident.residency
    fe_b = acct["resident_block_bytes"]["fixed"]
    re_b = sum(b for c, b in acct["resident_block_bytes"].items()
               if c != "fixed")
    flat = acct["flat_vector_bytes"]
    D = 8
    budget = int((flat + -(-re_b // D)) * 2.2)
    assert budget < 2 * fe_b // D, "shape cannot force streaming"
    streamed = GameEstimator(_config(iters=6, budget=budget),
                             mesh=make_mesh()).fit(train, val)

    assert len(streamed.objective_history) == len(resident.objective_history)
    np.testing.assert_allclose(streamed.objective_history,
                               resident.objective_history, rtol=1e-9)
    sacct = streamed.residency
    assert sacct["per_device"] is True and sacct["data_devices"] == 8
    assert sacct["streamed_chunk_bytes"], "FE coordinate did not stream"
    assert sacct["under_budget"] is True
    assert sacct["peak_tracked_bytes"] <= budget
    # the out-of-core claim: per-device data really exceeds the budget
    assert -(-(fe_b + re_b) // D) + flat > budget


# -- warm-iteration transfer contract -----------------------------------------

def test_warm_iterations_stage_zero_cold_bytes(rng):
    """The no-retransfer regression gate: a second descent over the SAME
    coordinates stages zero cold (static) bytes, and every visit's warm
    bytes stay within the coefficients+offsets bound — the dataset is
    d x bigger and cannot hide inside it."""
    train, val = _glmix(rng)
    cfg = _config(outer=2)
    mesh = make_mesh()
    est = GameEstimator(cfg, mesh=mesh)
    coords = est._build_coordinates(train)

    def run():
        return run_coordinate_descent(
            coords, cfg.updating_sequence, cfg.num_outer_iterations, train,
            cfg.task_type, residency=est._residency_manager(coords, train))

    cold_res = run()
    snap1 = transfer_snapshot()
    warm_res = run()
    delta = TransferStats.delta(snap1, transfer_snapshot())
    assert delta["cold_bytes"] == 0, (
        f"warm run re-staged {delta['cold_bytes']} static bytes — the mesh "
        "residency memo broke")
    assert delta["warm_bytes"] > 0  # offsets/x0 legitimately move
    assert warm_res.objective_history == cold_res.objective_history

    # per-visit accounting in the trackers: coefficients+offsets only
    item = 8  # f64
    for key, t in warm_res.trackers.items():
        coord = key.split("/", 1)[1]
        assert t.staged_bytes is not None
        assert t.staged_bytes["cold"] == 0, (key, t.staged_bytes)
        c = coords[coord]
        if hasattr(c, "red"):
            cells = sum((-(-b.num_entities // 8) * 8)
                        * (b.samples_per_entity + b.dim)
                        for b in c.red.buckets)
        else:
            cells = (-(-train.num_rows // 8) * 8) + c.dim
        assert t.staged_bytes["warm"] <= cells * item * 1.5, (
            key, t.staged_bytes, cells * item)


def test_solver_diagnostics_carry_staged_bytes(rng):
    train, val = _glmix(rng, n=800, num_users=32)
    res = GameEstimator(_config(), mesh=make_mesh()).fit(train, val)
    diag = res.descent.solver_diagnostics()
    for coord in ("fixed", "perUser"):
        assert "staged_bytes" in diag[coord]
        assert diag[coord]["staged_bytes"]["warm"] > 0


# -- per-coordinate invalidation (ISSUE 6 satellite) --------------------------

def test_eviction_invalidates_only_the_evicted_coordinate(rng):
    """The eviction sledgehammer fix: evicting one coordinate drops ONLY
    its staged mesh entries; the sibling's stay resident and its next
    update stages zero cold bytes."""
    train, _ = _glmix(rng, n=1200, num_users=32, num_items=16, d_item=4)
    cfg = _config(with_item=True)
    mesh = make_mesh()
    est = GameEstimator(cfg, mesh=mesh)
    coords = est._build_coordinates(train)
    zeros = jnp.zeros(train.num_rows)
    models = {n: coords[n].initial_model() for n in cfg.updating_sequence}
    for n in cfg.updating_sequence:
        models[n], _ = coords[n].update(models[n], zeros)

    reg = default_residency()
    user_prefix = coords["perUser"]._mesh_key()
    item_prefix = coords["perItem"]._mesh_key()
    has_prefix = lambda p: any(k[0][: len(p)] == p for k in reg.keys())
    assert has_prefix(user_prefix) and has_prefix(item_prefix)

    coords["perUser"].evict_device_blocks()
    assert not has_prefix(user_prefix), "evicted entries survived"
    assert has_prefix(item_prefix), (
        "evicting perUser dropped perItem's staged blocks — the global "
        "clear_mesh_block_cache sledgehammer is back")

    # the surviving coordinate's next update re-transfers nothing static
    before = transfer_snapshot()
    models["perItem"], _ = coords["perItem"].update(models["perItem"], zeros)
    delta = TransferStats.delta(before, transfer_snapshot())
    assert delta["cold_bytes"] == 0
    # the evicted one re-streams (cold) on its next visit
    before = transfer_snapshot()
    models["perUser"], _ = coords["perUser"].update(models["perUser"], zeros)
    delta = TransferStats.delta(before, transfer_snapshot())
    assert delta["cold_bytes"] > 0


# -- the design matrix: where a solve reads it from (ISSUE 36) ----------------

@pytest.mark.parametrize("data_devices", [1, 8])
def test_design_matrix_stages_from_where_the_mesh_says(rng, data_devices):
    """Data axis over ONE device: the solve's operand is the dataset's own
    device copy, the buffer `score` reads, and staging it moves no host
    byte.  Data axis over several: the dense HOST shard stages host ->
    sharded devices, every byte from the host, and no full single-device
    copy of it is ever made.  On either mesh an evicted fixed-effect
    coordinate leaves no entry of its own in the registry."""
    import jax
    from photon_ml_tpu.parallel import mesh_residency
    from photon_ml_tpu.parallel.fixed_effect import staged_fixed_effect_x
    train, _ = _glmix(rng, n=800, num_users=32)
    cfg = _config(iters=4)
    mesh = make_mesh(devices=jax.devices()[:data_devices])
    mesh_residency.clear()
    before = transfer_snapshot()
    coords = GameEstimator(cfg, mesh=mesh)._build_coordinates(train)
    fe = coords["fixed"]
    model, _ = fe.update(fe.initial_model(), jnp.zeros(train.num_rows))
    scores = fe.score(model)
    delta = TransferStats.delta(before, transfer_snapshot())
    n, x_dev = staged_fixed_effect_x(fe._mesh_key(), mesh,
                                     fe._mesh_x_source())
    assert TransferStats.delta(before, transfer_snapshot()) == delta, (
        "update, score and a third reader did not share one staged entry")
    host = train.feature_shards["global"]
    flat = n * host.itemsize
    assert scores.shape == (n,) and n == train.num_rows
    # what either mesh staged before this PR, to the byte: the matrix, the
    # labels and the mask cold, the offsets and x0 warm
    assert delta["cold_bytes"] == host.nbytes + 2 * flat
    assert delta["warm_bytes"] == flat + host.shape[1] * host.itemsize
    if data_devices == 1:
        assert fe._mesh_x_source() is fe.x is train.device_shard("global")
        assert x_dev.unsafe_buffer_pointer() == fe.x.unsafe_buffer_pointer()
        assert delta["host_bytes"] == flat       # the mask of ones alone
    else:
        assert fe._mesh_x_source() is host
        assert "global" not in train._device_shards
        assert len(x_dev.sharding.device_set) == data_devices
        assert x_dev.addressable_shards[0].data.shape == (n // data_devices,
                                                          host.shape[1])
        assert delta["host_bytes"] == host.nbytes + flat
    prefix = fe._mesh_key()
    held = lambda: [k for k in default_residency().keys()
                    if k[0][: len(prefix)] == prefix]
    assert held()
    fe.evict_device_blocks()
    assert not held(), "an evicted coordinate left its staged matrix pinned"
    assert "global" not in train._device_shards


def test_one_device_mesh_refits_move_no_design_matrix(rng):
    """What `cli.train --mesh auto` and the benchmark's cells run: whole
    fits of one dataset on a one-device mesh, the registry cleared between
    them.  Every fit reports its transfer delta, no fit's host bytes reach
    the design matrix's (the first fit's one upload is the dataset's, not a
    stage), a warm fit's are [n] vectors and x0, and every fit walks the
    first's objective history bit for bit."""
    import jax
    from photon_ml_tpu.parallel import mesh_residency
    train, val = _glmix(rng, n=800, num_users=32)
    cfg = _config(iters=4)
    mesh = make_mesh(devices=jax.devices()[:1])
    fits = []
    for _ in range(3):
        mesh_residency.clear()
        fits.append(GameEstimator(cfg, mesh=mesh).fit(train, val))
    matrix = train.feature_shards["global"].nbytes
    item = train.feature_shards["global"].itemsize
    for fit in fits:
        assert fit.objective_history == fits[0].objective_history
        assert isinstance(fit.mesh_transfer, dict)
        assert fit.mesh_transfer["cold_bytes"] > matrix
        # the fixed effect's mask of ones is the one host-made [n] vector
        assert fit.mesh_transfer["host_bytes"] <= (
            2 * train.num_rows + 2 * 10) * item < matrix
    assert fits[1].mesh_transfer == fits[2].mesh_transfer


def test_default_residency_singleton_under_thread_race():
    """Regression for the PH013 bare lazy init: racing first calls must
    all get ONE registry (two would split the TransferStats the mesh
    bench gates on).  Resets the module global to exercise the
    double-checked path, restoring it afterwards."""
    import threading

    from photon_ml_tpu.parallel import mesh_residency as mr

    prev = mr._DEFAULT
    try:
        mr._DEFAULT = None
        barrier = threading.Barrier(8)
        got = []

        def racer():
            barrier.wait(timeout=5)
            got.append(mr.default_residency())

        threads = [threading.Thread(target=racer) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=5)
        assert len(got) == 8
        assert all(g is got[0] for g in got)
    finally:
        mr._DEFAULT = prev


def test_clear_mesh_block_cache_alias_retired():
    """ISSUE 14 satellite: the deprecated global-flush alias is GONE —
    invalidation routes through the tiered store's residency registry
    (per-coordinate `invalidate`, or `clear()` on the registry itself)."""
    import photon_ml_tpu.parallel.random_effect as re_mod
    assert not hasattr(re_mod, "clear_mesh_block_cache")
    # the registry's own clear() remains the sanctioned full flush
    from photon_ml_tpu.parallel import mesh_residency
    mesh_residency.clear()
    assert default_residency().num_entries() == 0


# -- compile-count stability across mesh shapes (ISSUE 6 satellite) -----------

@pytest.mark.parametrize("shape", [(8, 1), (4, 2)])
def test_zero_fresh_traces_across_warm_outer_iterations(rng, shape):
    """After one warm-up fit on a mesh shape, a refit traces NOTHING new —
    the staged shardings and budget-operand programs are stable.  Covers
    both the pure data mesh (8x1) and the feature-sharded 4x2 regime."""
    train, val = _glmix(rng, n=800, num_users=32)
    cfg = _config(iters=4)
    mesh = make_mesh(*shape)
    GameEstimator(cfg, mesh=mesh).fit(train, val)   # warm-up compiles all
    with _compile_counting() as counter:
        GameEstimator(cfg, mesh=mesh).fit(train, val)
    assert counter.count == 0, (
        f"{counter.count} fresh XLA traces on a warm {shape} mesh refit")


# -- fault injection through mesh staging (ISSUE 6 satellite) -----------------

def test_mesh_stage_transient_fault_is_retried(rng):
    train, val = _glmix(rng, n=800, num_users=32)
    before = transfer_snapshot()
    plan = faults.FaultPlan([{"site": "mesh.stage", "action": "transient",
                              "hits": [1, 3]}])
    with faults.injected(plan):
        res = GameEstimator(_config(iters=4), mesh=make_mesh()).fit(train,
                                                                    val)
    assert np.isfinite(res.objective_history).all()
    delta = TransferStats.delta(before, transfer_snapshot())
    assert delta["retries"] >= 2
    assert plan.report()["total_fired"] == 2


def test_mesh_stage_fatal_fault_propagates(rng):
    train, val = _glmix(rng, n=800, num_users=32)
    plan = faults.FaultPlan([{"site": "mesh.stage", "action": "fatal",
                              "hits": [1]}])
    with faults.injected(plan):
        with pytest.raises(MeshStagingError):
            GameEstimator(_config(iters=4), mesh=make_mesh()).fit(train, val)


def test_pad_and_shard_rows_fires_mesh_stage_site(rng):
    from photon_ml_tpu.parallel.mesh import pad_and_shard_rows
    mesh = make_mesh()
    x = rng.normal(size=(100, 4))
    plan = faults.FaultPlan([{"site": "mesh.stage", "action": "transient",
                              "hits": [1]}])
    with faults.injected(plan):
        n, (x_dev,) = pad_and_shard_rows(mesh, x)
    assert n == 100 and x_dev.shape[0] == 104  # padded to the 8-multiple
    assert plan.report()["total_fired"] == 1   # absorbed by the retry
