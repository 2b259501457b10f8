"""Feature-axis consensus-ADMM lane (optim/admm.py + the staging,
dispatch and telemetry wiring around it).

Covers the PR's acceptance gates as unit tests:
  - f64 objective parity of the PURE consensus solve (polish off) against
    the monolithic host-stepped solver on 1x1 / 1x2 / 2x2 / 4x2 meshes;
  - zero fresh XLA traces across warm ADMM solves, including rho sweeps,
    tolerance/budget changes and adaptive-rho runs (rho and the budget are
    traced operands, never trace keys);
  - L1 sparsity-pattern agreement with the monolithic OWLQN lane;
  - checkpoint-resume through GameEstimator while the ADMM lane is the
    fixed-effect solver;
  - one feature-axis vector all-reduce (plus one data-axis block
    all-reduce) per compiled iteration, by HLO collective accounting;
  - make_mesh feature-axis construction, shardings and the fail-loud /
    warn-once eligibility rules on FixedEffectCoordinate.
"""
import json
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data import build_game_dataset
from photon_ml_tpu.game import (
    FixedEffectCoordinateConfig, GameEstimator, GameTrainingConfig,
    GLMOptimizationConfig,
)
from photon_ml_tpu.ops.losses import LOGISTIC, SQUARED
from photon_ml_tpu.ops.normalization import NormalizationType
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.optim import (
    ADMMConfig, OptimizerConfig, RegularizationContext, RegularizationType,
    SolverSchedule,
)
from photon_ml_tpu.optim.admm import (
    cached_step_probe, collective_summary, make_init, ADMMOperands,
)
from photon_ml_tpu.parallel import make_mesh
from photon_ml_tpu.parallel.fixed_effect import (
    _fold_x0, _stage_admm_operands, fit_fixed_effect, fit_fixed_effect_admm,
    stage_admm_grid,
)
from photon_ml_tpu.parallel.mesh import (
    DATA_AXIS, FEATURE_AXIS, feature_sharding, grid_sharding,
)

L1 = RegularizationContext(RegularizationType.L1)
L2 = RegularizationContext(RegularizationType.L2)


def _mesh(num_data, num_feature):
    return make_mesh(num_data, num_feature,
                     devices=jax.devices()[:num_data * num_feature])


def _problem(rng, loss, n=240, d=17):
    x = rng.normal(size=(n, d))
    x[:, -1] = 1.0
    w = rng.normal(size=d)
    z = x @ w
    if loss is LOGISTIC:
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(np.float64)
    else:
        y = z + 0.1 * rng.normal(size=n)
    return GLMObjective(loss, x, y)


def _penalized(obj, x, l1_w=0.0, l2_w=0.0):
    x = np.asarray(x)
    return (float(obj.value(jnp.asarray(x)))
            + 0.5 * l2_w * float(x @ x) + l1_w * float(np.abs(x).sum()))


# ---------------------------------------------------------------------------
# f64 parity: pure consensus solve vs the monolithic solver
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(1, 1), (1, 2), (2, 2), (4, 2)])
@pytest.mark.parametrize("loss", [LOGISTIC, SQUARED], ids=["logistic", "sq"])
def test_pure_admm_matches_monolithic(rng, shape, loss):
    """Polish OFF: the consensus iterate itself must land on the monolithic
    optimum to f64 working precision (acceptance gate: rel <= 1e-6)."""
    obj = _problem(rng, loss)
    x0 = np.zeros(obj.dim)
    mesh = _mesh(*shape)
    res = fit_fixed_effect_admm(
        obj, x0, mesh,
        ADMMConfig(max_iterations=800, tolerance=1e-10, polish=False),
        reg=L2, reg_weight=0.3,
        residency_key=("admm-parity", shape, loss.name))
    ref = fit_fixed_effect(
        obj, x0, _mesh(shape[0] * shape[1], 1),
        OptimizerConfig(max_iterations=500, tolerance=1e-12),
        reg=L2, reg_weight=0.3)
    v_admm = _penalized(obj, res.x, l2_w=0.3)
    v_ref = _penalized(obj, ref.x, l2_w=0.3)
    assert abs(v_admm - v_ref) <= 1e-6 * abs(v_ref), (shape, v_admm, v_ref)
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(ref.x),
                               rtol=1e-4, atol=1e-6)


def test_admm_polish_pins_exact_parity(rng):
    """Polish ON (the default): the returned iterate IS a monolithic
    solution warm-started at consensus — bit-for-bit the strict lane's
    fixed point, with both phases' iterations summed."""
    obj = _problem(rng, LOGISTIC)
    x0 = np.zeros(obj.dim)
    mesh = _mesh(2, 2)
    res = fit_fixed_effect_admm(
        obj, x0, mesh, ADMMConfig(max_iterations=60, tolerance=1e-4),
        config=OptimizerConfig(max_iterations=200, tolerance=1e-9),
        reg=L2, reg_weight=0.3, residency_key=("admm-polish",))
    ref = fit_fixed_effect(
        obj, x0, mesh, OptimizerConfig(max_iterations=400, tolerance=1e-9),
        reg=L2, reg_weight=0.3, shard_features=False)
    v = _penalized(obj, res.x, l2_w=0.3)
    v_ref = _penalized(obj, ref.x, l2_w=0.3)
    assert abs(v - v_ref) <= 1e-9 * abs(v_ref)
    assert res.iterations > 0


# ---------------------------------------------------------------------------
# zero fresh traces across warm solves: rho, tolerance and x0 are operands
# ---------------------------------------------------------------------------

class _CompileCounter(logging.Handler):
    def __init__(self):
        super().__init__()
        self.count = 0

    def emit(self, record):
        if record.getMessage().startswith("Compiling "):
            self.count += 1


class _compile_counting:
    def __enter__(self):
        self.handler = _CompileCounter()
        self.logger = logging.getLogger("jax._src.interpreters.pxla")
        self._level = self.logger.level
        self.logger.addHandler(self.handler)
        self.logger.setLevel(logging.WARNING)
        jax.config.update("jax_log_compiles", True)
        return self.handler

    def __exit__(self, *exc):
        jax.config.update("jax_log_compiles", False)
        self.logger.removeHandler(self.handler)
        self.logger.setLevel(self._level)


def test_zero_fresh_traces_across_warm_admm_solves(rng):
    """rho sweeps, tolerance/budget changes, warm starts and in-loop
    adaptive rho all re-dispatch ONE executable — the program's trace keys
    are (loss, has_l1, ceiling, adapt_rho, newton_steps, rho_tau, rho_mu)
    and nothing else."""
    obj = _problem(rng, LOGISTIC)
    mesh = _mesh(2, 2)
    key = ("admm-traces",)

    def run(cfg, x0):
        return fit_fixed_effect_admm(obj, x0, mesh, cfg, reg=L2,
                                     reg_weight=0.3, residency_key=key)

    base = dict(max_iterations=120, polish=False)
    # warm EVERY shape-distinct path: cold staging + program, then a warm
    # start from a device-resident x (the jnp _fold_x0 branch)
    first = run(ADMMConfig(tolerance=1e-8, **base), np.zeros(obj.dim))
    run(ADMMConfig(tolerance=1e-8, **base), first.x)
    with _compile_counting() as counter:
        warm = run(ADMMConfig(tolerance=1e-8, **base), np.zeros(obj.dim))
        run(ADMMConfig(tolerance=1e-6, rho=0.25, **base), warm.x)
        run(ADMMConfig(tolerance=1e-10, rho=4.0, **base), warm.x)
        run(ADMMConfig(tolerance=1e-8, rho=1.0, adapt_rho=True, **base),
            np.zeros(obj.dim))
    assert counter.count == 0


# ---------------------------------------------------------------------------
# L1: per-shard soft-thresholding agrees with the monolithic OWLQN lane
# ---------------------------------------------------------------------------

def test_l1_sparsity_pattern_matches_owlqn(rng):
    n, d = 320, 12
    x = rng.normal(size=(n, d))
    w_true = np.zeros(d)
    w_true[:4] = [3.0, -2.0, 1.5, 2.5]
    y = x @ w_true + 0.05 * rng.normal(size=n)
    obj = GLMObjective(SQUARED, x, y)
    lam = 30.0
    mesh = _mesh(2, 4)
    res = fit_fixed_effect_admm(
        obj, np.zeros(d), mesh,
        ADMMConfig(max_iterations=1500, tolerance=1e-11, polish=False),
        reg=L1, reg_weight=lam, residency_key=("admm-l1",))
    ref = fit_fixed_effect(
        obj, np.zeros(d), mesh,
        OptimizerConfig(max_iterations=600, tolerance=1e-12),
        reg=L1, reg_weight=lam, shard_features=False)
    xa, xr = np.asarray(res.x), np.asarray(ref.x)
    pat_a, pat_r = np.abs(xa) > 1e-6, np.abs(xr) > 1e-6
    # the regulariser must actually bite AND leave signal, else the test
    # proves nothing
    assert pat_r.any() and not pat_r.all()
    np.testing.assert_array_equal(pat_a, pat_r)
    v_a = _penalized(obj, xa, l1_w=lam)
    v_r = _penalized(obj, xr, l1_w=lam)
    assert abs(v_a - v_r) <= 1e-6 * abs(v_r)


# ---------------------------------------------------------------------------
# GameEstimator integration: checkpoint-resume while ADMM drives the FE
# ---------------------------------------------------------------------------

def _fe_dataset(rng, n=640, d=8):
    x = rng.normal(size=(n, d))
    x[:, -1] = 1.0
    w = rng.normal(size=d)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-(x @ w)))).astype(float)
    return build_game_dataset(y, {"global": x})


def _fe_config(outer=2, schedule=None, **fe_kw):
    return GameTrainingConfig(
        task_type="logistic_regression",
        coordinates={"fixed": FixedEffectCoordinateConfig(
            "global",
            GLMOptimizationConfig(
                optimizer=OptimizerConfig(max_iterations=30),
                regularization=L2, regularization_weight=0.1),
            **fe_kw)},
        updating_sequence=["fixed"], num_outer_iterations=outer,
        solver_schedule=schedule)


def test_checkpoint_resume_mid_admm(rng, tmp_path):
    """Killing a feature-sharded fit between outer iterations and resuming
    from the checkpoint must reproduce the straight run — the ADMM lane's
    state fully round-trips through the coordinate checkpoint."""
    ds = _fe_dataset(rng)
    ckpt = tmp_path / "ckpt"
    straight = GameEstimator(_fe_config(2), mesh=make_mesh(4, 2)).fit(ds)
    partial = GameEstimator(_fe_config(1), mesh=make_mesh(4, 2)).fit(
        ds, checkpoint_dir=ckpt)
    resumed = GameEstimator(_fe_config(2), mesh=make_mesh(4, 2)).fit(
        ds, checkpoint_dir=ckpt)
    np.testing.assert_allclose(partial.objective_history,
                               straight.objective_history[:1], rtol=1e-7)
    np.testing.assert_allclose(resumed.objective_history,
                               straight.objective_history, rtol=1e-5)


def test_scheduled_admm_polish_gating():
    """With a SolverSchedule, only the trailing admm_polish_iterations
    outer visits run the monolithic polish."""
    sched = SolverSchedule(admm_polish_iterations=2)
    assert [sched.admm_polish(t, 5) for t in range(5)] == [
        False, False, False, True, True]
    rt = SolverSchedule.from_dict(sched.to_dict())
    assert rt.admm_polish_iterations == 2
    # default stays out of the encoded dict (stable configs don't churn)
    assert "admm_polish_iterations" not in SolverSchedule().to_dict()
    with pytest.raises(ValueError):
        SolverSchedule(admm_polish_iterations=0)


# ---------------------------------------------------------------------------
# collective accounting: ONE feature-axis vector psum per iteration
# ---------------------------------------------------------------------------

def test_one_feature_axis_reduction_per_iteration(rng):
    """Lower the exact while_loop body with the production shardings and
    count all-reduces in the compiled HLO: one [n_local] vector reduction
    over the FEATURE groups, one [F_local, d_F] block reduction over DATA,
    everything else scalar residual bookkeeping."""
    n, d = 256, 64
    obj = GLMObjective(LOGISTIC, rng.normal(size=(n, d)),
                       (rng.uniform(size=n) < 0.5).astype(np.float64))
    mesh = _mesh(2, 4)
    staged, n_, d_, bw = _stage_admm_operands(obj, mesh, ("admm-hlo",))
    ops = ADMMOperands(
        x_grid=staged["x_grid"], q_eig=staged["q_eig"],
        lam_eig=staged["lam_eig"], labels=staged["labels"],
        kappa=staged["mask"], offsets=staged["offsets"],
        l1_weight=jnp.asarray(0.0, jnp.float64),
        l2_weight=jnp.asarray(0.1, jnp.float64))
    with mesh:
        w0 = jax.device_put(jnp.zeros((4, bw)), feature_sharding(mesh, 2))
        carry = make_init(LOGISTIC, False, ops, w0,
                          jnp.asarray(1.0, jnp.float64), 8)
        txt = cached_step_probe(LOGISTIC, False, True, 8).lower(
            ops, carry).compile().as_text()
    summary = collective_summary(txt, mesh)
    n_local = staged["labels"].shape[0] // mesh.shape[DATA_AXIS]
    feature_vectors = [e for e in summary["feature"] if e[0] >= 1]
    assert feature_vectors == [(1, n_local * 8)], summary
    data_blocks = [e for e in summary["data"] if e[0] >= 1]
    assert len(data_blocks) == 1 and data_blocks[0][0] >= 2, summary
    assert not summary["other"], summary
    assert all(e[0] == 0 for e in summary["global"]), summary


_HLO_GROUPS = "replica_groups=[2,4]<=[8], use_global_device_ids=true"


@pytest.mark.parametrize("line,expected", [
    # one op, one array (what jax < 0.9 emitted for the margin psum)
    ("%ar = f64[128]{0} all-reduce(%x), channel_id=1, " + _HLO_GROUPS,
     [(1, 1024)]),
    # the combiner merged the [n_local] vector with a scalar: every
    # element counts, not only the last one
    ("%ar.10 = (f64[128]{0}, f64[]) all-reduce(%x, %y), channel_id=3, "
     + _HLO_GROUPS, [(1, 1024), (0, 8)]),
    # index comments and TPU tiled layouts inside the tuple; async form
    ("%ar.11 = (f32[8,4]{1,0:T(8,128)}, f32[]{:T(128)}, /*index=2*/"
     "bf16[16]{0:T(128)(2,1)}) all-reduce-start(%a, %b, %c), channel_id=5, "
     + _HLO_GROUPS, [(2, 128), (0, 4), (1, 32)]),
    # a tuple-shaped op that is no all-reduce is not counted
    ("%f = (f64[128]{0}, f64[]) fusion(%x, %y), kind=kLoop, calls=%c",
     []),
], ids=["single", "tuple", "tuple-tiled-async", "tuple-not-allreduce"])
def test_collective_summary_reads_combined_all_reduces(line, expected):
    """A hand-written HLO line per result-type form: a combined
    (tuple-shaped) all-reduce is read element by element."""
    summary = collective_summary(line, _mesh(2, 4))
    assert summary["feature"] == expected, summary
    assert not (summary["data"] or summary["global"] or summary["other"])


# ---------------------------------------------------------------------------
# make_mesh feature axis + shardings (satellite: direct unit tests)
# ---------------------------------------------------------------------------

def test_make_mesh_feature_axis():
    mesh = make_mesh(2, 4)
    assert mesh.axis_names == (DATA_AXIS, FEATURE_AXIS)  # data OUTERMOST
    assert mesh.shape[DATA_AXIS] == 2 and mesh.shape[FEATURE_AXIS] == 4
    assert mesh.devices.shape == (2, 4)
    # num_data inferred from the device count
    assert make_mesh(num_feature=4).shape[DATA_AXIS] == 2
    with pytest.raises(ValueError) as ei:
        make_mesh(3, 3)
    msg = str(ei.value)
    assert "data=3 x feature=3" in msg and "8-device" in msg
    assert "outermost" in msg


def test_feature_and_grid_shardings_place_blocks():
    mesh = make_mesh(2, 4)
    w = jax.device_put(np.arange(8.0).reshape(4, 2),
                       feature_sharding(mesh, 2))
    assert w.addressable_shards[0].data.shape == (1, 2)
    g = jax.device_put(np.zeros((8, 4, 2)), grid_sharding(mesh))
    assert g.addressable_shards[0].data.shape == (4, 1, 2)
    # row r of the device grid holds all feature shards of data block r
    for shard in g.addressable_shards:
        row = shard.device.id // 4
        assert shard.index[0] == slice(4 * row, 4 * (row + 1))


def test_grid_staging_pads_and_splits(rng):
    """d not divisible by F zero-pads the tail column block; scoring and
    solving slice it back off."""
    x = rng.normal(size=(50, 10))
    mesh = _mesh(2, 4)
    n, d, bw, x_grid = stage_admm_grid(("admm-pad",), mesh, x)
    assert (n, d, bw) == (50, 10, 3)
    assert x_grid.shape[1:] == (4, 3)
    assert x_grid.shape[0] % mesh.shape[DATA_AXIS] == 0
    host = np.asarray(x_grid)[:50].reshape(50, 12)
    np.testing.assert_array_equal(host[:, :10], x)
    np.testing.assert_array_equal(host[:, 10:], 0.0)
    w0 = _fold_x0(np.arange(10.0), 4, 3)
    assert w0.shape == (4, 3)
    np.testing.assert_array_equal(w0.reshape(-1)[:10], np.arange(10.0))


# ---------------------------------------------------------------------------
# per-device aggregator memory falls with the feature axis
# ---------------------------------------------------------------------------

_MEM_D = 256


@pytest.fixture(scope="module")
def admm_memory_legs():
    """One squared-loss problem at d=256 trained by pure consensus ADMM on
    8x1, 4x2, 2x4 and 1x8 meshes: per feature-axis width, the largest
    per-device shard of the eigenbasis aggregator [F, d_F, d_F] and whether
    the objective fell.  Built once; the cases below only read it."""
    rng = np.random.default_rng(7)
    x = rng.normal(size=(1024, _MEM_D))
    x[:, -1] = 1.0
    y = x @ (rng.normal(size=_MEM_D) * 0.5) + 0.1 * rng.normal(size=1024)
    obj = GLMObjective(SQUARED, x, y)
    v0 = float(obj.value(jnp.zeros(_MEM_D)))
    legs = {}
    for width in (1, 2, 4, 8):
        mesh = _mesh(8 // width, width)
        key = ("admm-mem", width)
        res = fit_fixed_effect_admm(
            obj, np.zeros(_MEM_D), mesh,
            ADMMConfig(max_iterations=25, tolerance=1e-9, polish=False),
            reg=L2, reg_weight=0.3, residency_key=key)
        staged, _, _, _ = _stage_admm_operands(obj, mesh, key)
        legs[width] = {
            "aggregator_bytes": max(
                s.data.nbytes for s in staged["q_eig"].addressable_shards),
            "trained": float(res.value) < v0}
    return legs


@pytest.mark.parametrize("width", [2, 4, 8])
def test_per_device_aggregator_shrinks_with_feature_axis(admm_memory_legs,
                                                         width):
    """The eigenbasis [F, d_F, d_F] is sharded over "feature", so a device
    holds one d_F x d_F block: its share falls with the SQUARE of the axis
    width (a replicated eigenbasis would fall only linearly)."""
    base = admm_memory_legs[1]["aggregator_bytes"]
    assert admm_memory_legs[width]["aggregator_bytes"] <= (
        base / width ** 2 * 1.15)


def test_wide_model_trains_inside_a_budget_the_monolithic_layout_busts(
        admm_memory_legs):
    """A d whose monolithic d^2 aggregator exceeds a per-device budget
    trains on the data x feature mesh with every device inside it."""
    budget = _MEM_D * _MEM_D * 8 // 4
    assert admm_memory_legs[1]["aggregator_bytes"] > budget
    assert admm_memory_legs[8]["aggregator_bytes"] <= budget
    assert admm_memory_legs[8]["trained"]


# ---------------------------------------------------------------------------
# eligibility: fail loud / warn once instead of silently not sharding
# ---------------------------------------------------------------------------

def test_shard_features_without_mesh_raises(rng):
    ds = _fe_dataset(rng, n=160)
    with pytest.raises(ValueError, match="nothing consumes the feature"):
        GameEstimator(_fe_config(1, shard_features=True)).fit(ds)


def test_blocked_lane_warns_and_falls_back(rng, caplog):
    ds = _fe_dataset(rng, n=160)
    cfg = _fe_config(1, shard_features=True,
                     normalization=NormalizationType.STANDARDIZATION)
    with caplog.at_level(logging.WARNING,
                         logger="photon_ml_tpu.game.coordinates"):
        res = GameEstimator(cfg, mesh=make_mesh(4, 2)).fit(ds)
    assert any("ADMM lane is blocked" in r.getMessage()
               and "normalization" in r.getMessage()
               for r in caplog.records)
    assert np.isfinite(res.objective_history).all()


def test_width1_feature_axis_warns(rng, caplog):
    ds = _fe_dataset(rng, n=160)
    with caplog.at_level(logging.WARNING,
                         logger="photon_ml_tpu.game.coordinates"):
        GameEstimator(_fe_config(1, shard_features=True),
                      mesh=make_mesh(8, 1)).fit(ds)
    assert any("width 1" in r.getMessage() for r in caplog.records)


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------

def test_admm_config_json_roundtrip():
    admm = ADMMConfig(max_iterations=123, tolerance=2.5e-9, rho=0.5,
                      adapt_rho=False, rho_tau=3.0, rho_mu=5.0,
                      newton_steps=4, polish=False)
    cfg = GameTrainingConfig(
        task_type="logistic_regression",
        coordinates={"fixed": FixedEffectCoordinateConfig(
            "global", GLMOptimizationConfig(
                optimizer=OptimizerConfig(max_iterations=30),
                regularization=L2, regularization_weight=0.1, admm=admm))},
        updating_sequence=["fixed"], num_outer_iterations=2)
    rt = GameTrainingConfig.from_dict(json.loads(cfg.to_json()))
    assert rt.coordinates["fixed"].optimization.admm == admm
    # absent stays absent (lane defaults, not an encoded block of defaults)
    plain = GameTrainingConfig.from_dict(json.loads(
        _fe_config(1).to_json()))
    assert plain.coordinates["fixed"].optimization.admm is None


def test_admm_config_validation():
    with pytest.raises(ValueError):
        ADMMConfig(rho=0.0)
    with pytest.raises(ValueError):
        ADMMConfig(rho_tau=1.0)
    with pytest.raises(ValueError):
        ADMMConfig(rho_mu=0.5)
    with pytest.raises(ValueError):
        ADMMConfig(newton_steps=0)
    r = ADMMConfig().resolved()
    assert r.max_iterations == 200 and r.tolerance == 1e-8
    assert isinstance(ADMMConfig(rho=np.float64(2)).rho, float)


def test_stage_derived_reanchors_on_new_source(rng):
    """The Gram eigendecomposition is memoized against the staged grid's
    identity: same source -> cached, re-staged source -> re-derived (one
    counted invalidation)."""
    from photon_ml_tpu.parallel.mesh_residency import MeshResidency
    res = MeshResidency()
    mesh = _mesh(2, 2)
    key = ("derived-test",)
    calls = []

    def build(grid):
        def _b():
            calls.append(1)
            return jnp.sum(grid)
        return _b

    _, _, _, g1 = stage_admm_grid(key, mesh, rng.normal(size=(40, 8)),
                                  residency=res)
    res.stage_derived(key, "eig", mesh, g1, build(g1))
    res.stage_derived(key, "eig", mesh, g1, build(g1))
    assert len(calls) == 1
    inv_before = res.stats.invalidations
    _, _, _, g2 = stage_admm_grid(key, mesh, rng.normal(size=(40, 8)),
                                  residency=res)
    assert g2 is not g1
    res.stage_derived(key, "eig", mesh, g2, build(g2))
    assert len(calls) == 2
    assert res.stats.invalidations > inv_before


# ---------------------------------------------------------------------------
# feature-wide meshes: row-sharded concatenate workaround (regression)
# ---------------------------------------------------------------------------

def test_concat_rows_safe_on_feature_mesh(rng):
    """concat_rows_safe must be exact for P("data")-sharded operands on a
    mesh with a >1 feature axis — the layout where a direct jnp.concatenate
    miscompiles under this build's GSPMD (values interleave across shards).
    """
    from photon_ml_tpu.parallel.mesh import concat_rows_safe, data_sharding
    mesh = _mesh(4, 2)
    a, b = rng.normal(size=(36, 5)), rng.normal(size=(24, 5))
    ad = jax.device_put(jnp.asarray(a), data_sharding(mesh, 2))
    bd = jax.device_put(jnp.asarray(b), data_sharding(mesh, 2))
    out = concat_rows_safe(mesh, [ad, bd], axis=0)
    np.testing.assert_array_equal(np.asarray(out), np.concatenate([a, b]))
    # 60 rows tile the 4-wide data axis -> result lands back row-sharded
    assert out.sharding.spec[0] == DATA_AXIS
    # 1-D leaves (per-entity value/iterations) take the same route
    v1 = jax.device_put(jnp.asarray(a[:, 0]), data_sharding(mesh, 1))
    v2 = jax.device_put(jnp.asarray(b[:, 0]), data_sharding(mesh, 1))
    v = concat_rows_safe(mesh, [v1, v2], axis=0)
    np.testing.assert_array_equal(np.asarray(v),
                                  np.concatenate([a[:, 0], b[:, 0]]))
    # mesh-less callers keep the plain concatenate
    w = concat_rows_safe(None, [jnp.asarray(a), jnp.asarray(b)], axis=0)
    np.testing.assert_array_equal(np.asarray(w), np.concatenate([a, b]))


def test_multibucket_re_training_on_feature_mesh(rng):
    """A GAME fit whose random effect spans multiple size buckets must
    reproduce the single-device objective history on a feature-wide mesh
    (regression: the cross-bucket result concatenate at the end of
    RandomEffectCoordinate.update silently corrupted the coefficient table
    on feature>1 meshes, making the objective diverge)."""
    from photon_ml_tpu.game import RandomEffectCoordinateConfig
    # entity counts per size bucket (36 and 24) tile the 4-wide data axis:
    # the per-bucket results then come back still row-sharded, the exact
    # layout whose concatenate miscompiled
    sizes = np.concatenate([np.full(36, 3), np.full(24, 8)])
    users = np.repeat(np.arange(sizes.size), sizes)
    n, d = users.size, 5
    x = rng.normal(size=(n, d))
    x[:, -1] = 1.0
    w = rng.normal(size=d)
    u = 0.5 * rng.normal(size=(sizes.size, d))
    z = np.einsum("nd,nd->n", x, w + u[users])
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(float)
    ds = build_game_dataset(y, {"global": x},
                            entity_ids={"per_user": users})
    cfg = GameTrainingConfig(
        task_type="logistic_regression",
        coordinates={
            "fixed": FixedEffectCoordinateConfig(
                "global", GLMOptimizationConfig(
                    optimizer=OptimizerConfig(max_iterations=30),
                    regularization=L2, regularization_weight=0.1)),
            "perUser": RandomEffectCoordinateConfig(
                random_effect_type="per_user", feature_shard="global",
                optimization=GLMOptimizationConfig(
                    optimizer=OptimizerConfig(max_iterations=30),
                    regularization=L2, regularization_weight=1.0)),
        },
        updating_sequence=["fixed", "perUser"], num_outer_iterations=2)
    one = GameEstimator(cfg, mesh=None).fit(ds)
    meshed = GameEstimator(cfg, mesh=make_mesh(4, 2)).fit(ds)
    h1 = np.asarray(one.objective_history)
    hm = np.asarray(meshed.objective_history)
    np.testing.assert_allclose(hm, h1, rtol=1e-5)
    assert (np.diff(hm) <= 1e-6 * np.abs(hm[:-1])).all()
