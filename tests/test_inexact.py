"""Inexact coordinate descent (ISSUE 4): dynamic inner-solve budgets.

The contract: (iteration cap, tolerance) are OPERANDS of the compiled
solver programs — sweeping a budget schedule across outer iterations
compiles nothing new — and a schedule whose final outer iteration runs at
the full configured tolerance lands the scheduled fit on the strict
full-solve optimum (convex configs), including across a checkpoint/resume
boundary mid-schedule.
"""
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from test_pipeline import _compile_counting, _glmix

from photon_ml_tpu.data import build_game_dataset
from photon_ml_tpu.game import (
    FactoredRandomEffectCoordinateConfig, FixedEffectCoordinateConfig,
    GameEstimator, GameTrainingConfig, GLMOptimizationConfig,
    RandomEffectCoordinateConfig,
)
from photon_ml_tpu.ops import TASK_LOSSES, GLMObjective
from photon_ml_tpu.optim import (
    ConvergenceReason, OptimizerConfig, OptimizerType, RegularizationContext,
    RegularizationType, SolveBudget, SolverSchedule, solve, solve_streamed,
)

LOGISTIC = TASK_LOSSES["logistic_regression"]
L2 = RegularizationContext(RegularizationType.L2)
L1 = RegularizationContext(RegularizationType.L1)


def _logistic_problem(rng, n=300, d=8):
    x = rng.normal(size=(n, d))
    z = x @ rng.normal(size=d)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(float)
    return jnp.asarray(x), jnp.asarray(y)


# -- schedule semantics -------------------------------------------------------

def test_schedule_plan_tightens_and_finishes_full():
    s = SolverSchedule(initial_iterations=4, iteration_growth=2.0,
                      initial_tolerance_factor=1e3, tolerance_decay=0.1)
    plans = [s.plan(t, 5, 100, 1e-7) for t in range(5)]
    caps = [c for c, _ in plans]
    tols = [t for _, t in plans]
    assert caps == [4, 8, 16, 32, 100]          # growth, final = full
    assert tols[-1] == 1e-7                     # final at full tolerance
    assert all(a >= b for a, b in zip(tols, tols[1:]))  # monotone tightening
    assert all(t >= 1e-7 for t in tols)         # floored at configured tol
    # caps clip to the configured ceiling
    assert SolverSchedule(initial_iterations=500).plan(0, 3, 100, 1e-7)[0] == 100


def test_schedule_validation():
    with pytest.raises(ValueError, match="initial_iterations"):
        SolverSchedule(initial_iterations=0)
    with pytest.raises(ValueError, match="iteration_growth"):
        SolverSchedule(iteration_growth=0.5)
    with pytest.raises(ValueError, match="tolerance_decay"):
        SolverSchedule(tolerance_decay=0.0)


def test_schedule_json_round_trip():
    cfg = GameTrainingConfig(
        task_type="logistic_regression",
        coordinates={"fixed": FixedEffectCoordinateConfig(
            "global", solver_schedule=SolverSchedule(initial_iterations=2))},
        updating_sequence=["fixed"],
        solver_schedule=SolverSchedule(tolerance_decay=0.5))
    back = GameTrainingConfig.from_json(cfg.to_json())
    assert back == cfg
    assert back.solver_schedule == SolverSchedule(tolerance_decay=0.5)
    assert back.coordinates["fixed"].solver_schedule == \
        SolverSchedule(initial_iterations=2)


# -- budget semantics in the solvers -----------------------------------------

def test_full_budget_matches_static_solve_bitwise(rng):
    """budget=(max_iterations, configured tolerance) is the identical
    arithmetic to the static no-budget program — histories match bitwise."""
    x, y = _logistic_problem(rng)
    obj = GLMObjective(LOGISTIC, x, y)
    for opt_cfg, reg in (
            (OptimizerConfig(max_iterations=60, tolerance=1e-9), L2),
            (OptimizerConfig(max_iterations=60, tolerance=1e-9), L1),
            (OptimizerConfig(optimizer=OptimizerType.TRON,
                             max_iterations=15, tolerance=1e-9), L2)):
        r = opt_cfg.resolved()
        static = solve(obj, jnp.zeros(x.shape[1]), opt_cfg, reg, 1.0)
        budgeted = solve(obj, jnp.zeros(x.shape[1]), opt_cfg, reg, 1.0,
                         budget=SolveBudget.make(r.max_iterations,
                                                 r.tolerance))
        assert int(static.iterations) == int(budgeted.iterations)
        np.testing.assert_array_equal(np.asarray(static.loss_history),
                                      np.asarray(budgeted.loss_history))
        np.testing.assert_array_equal(np.asarray(static.x),
                                      np.asarray(budgeted.x))


def test_budget_caps_iterations(rng):
    x, y = _logistic_problem(rng)
    obj = GLMObjective(LOGISTIC, x, y)
    cfg = OptimizerConfig(max_iterations=100, tolerance=1e-12)
    res = solve(obj, jnp.zeros(x.shape[1]), cfg, L2, 1.0,
                budget=SolveBudget.make(3, 1e-12))
    assert int(res.iterations) == 3
    assert int(res.reason) == ConvergenceReason.MAX_ITERATIONS
    # loose tolerance stops early with a convergence reason
    loose = solve(obj, jnp.zeros(x.shape[1]), cfg, L2, 1.0,
                  budget=SolveBudget.make(100, 1e-2))
    assert int(loose.iterations) < int(
        solve(obj, jnp.zeros(x.shape[1]), cfg, L2, 1.0).iterations)
    # history buffers stay sized by the static ceiling whatever the cap
    assert res.loss_history.shape == loose.loss_history.shape == (101,)


def test_streamed_budget_matches_resident(rng):
    """solve_streamed honors the budget and stays on the resident solver's
    trajectory for the shared iterations (f64, single-chunk parity)."""
    from photon_ml_tpu.data.streaming import ChunkPlan
    from photon_ml_tpu.ops.chunked import ChunkedGLMObjective
    x, y = _logistic_problem(rng, n=256, d=6)
    xn, yn = np.asarray(x), np.asarray(y)
    plan = ChunkPlan.build(xn.shape[0], chunk_rows=64)
    cobj = ChunkedGLMObjective(LOGISTIC, xn, yn, plan)
    cfg = OptimizerConfig(max_iterations=50, tolerance=1e-12)
    budget = SolveBudget.make(4, 1e-12)
    ss = solve_streamed(cobj, jnp.zeros(6), cfg, L2, 1.0, budget=budget)
    rr = solve(GLMObjective(LOGISTIC, x, y), jnp.zeros(6), cfg, L2, 1.0,
               budget=budget)
    assert int(ss.iterations) == int(rr.iterations) == 4
    np.testing.assert_allclose(np.asarray(ss.x), np.asarray(rr.x),
                               rtol=1e-10)


# -- compile-count regression (ISSUE 4 satellite) ----------------------------

def test_budget_sweep_zero_recompiles_resident(rng):
    """Sweeping (cap, tolerance) across outer iterations must hit ONE
    compiled program per solver: LBFGS, OWLQN, TRON, and the vmapped
    batched RE solver."""
    from photon_ml_tpu.parallel.fixed_effect import _cached_solver
    from photon_ml_tpu.parallel.random_effect import EntityBlocks, \
        fit_random_effects
    x, y = _logistic_problem(rng, n=200, d=6)
    obj = GLMObjective(LOGISTIC, x, y)
    lam = jnp.asarray(1.0)
    solvers = [
        (_cached_solver(OptimizerConfig(max_iterations=50), L2), obj),
        (_cached_solver(OptimizerConfig(max_iterations=50), L1), obj),
        (_cached_solver(OptimizerConfig(optimizer=OptimizerType.TRON,
                                        max_iterations=15), L2), obj),
    ]
    # warm every program with ONE budget (compiles happen here)
    for run, o in solvers:
        float(run(o, jnp.zeros(6), lam, SolveBudget.make(5, 1e-3)).value)
    E, S, d = 12, 16, 4
    blocks = EntityBlocks(x=jnp.asarray(rng.normal(size=(E, S, d))),
                          labels=jnp.asarray(
                              (rng.uniform(size=(E, S)) < 0.5).astype(float)),
                          mask=jnp.ones((E, S)))
    re_cfg = OptimizerConfig(max_iterations=40)
    float(fit_random_effects(blocks, LOGISTIC, config=re_cfg, reg=L2,
                             reg_weight=1.0,
                             budget=SolveBudget.make(5, 1e-3)).value[0])

    with _compile_counting() as counter:
        for cap, tol in ((2, 1e-1), (7, 1e-5), (50, 1e-9), (13, 1e-7)):
            b = SolveBudget.make(cap, tol)
            for run, o in solvers:
                float(run(o, jnp.zeros(6), lam, b).value)
            float(fit_random_effects(blocks, LOGISTIC, config=re_cfg, reg=L2,
                                     reg_weight=1.0, budget=b).value[0])
    assert counter.count == 0, (
        f"{counter.count} fresh XLA compiles across a budget sweep — the "
        "cap or tolerance leaked into a trace constant")


def test_budget_sweep_zero_recompiles_streamed(rng):
    """The host-stepped streamed solvers must also compile nothing new
    across a budget sweep (their jitted helpers are keyed on shapes)."""
    from photon_ml_tpu.data.streaming import ChunkPlan
    from photon_ml_tpu.ops.chunked import ChunkedGLMObjective
    x, y = _logistic_problem(rng, n=256, d=6)
    xn, yn = np.asarray(x), np.asarray(y)
    plan = ChunkPlan.build(xn.shape[0], chunk_rows=64)
    cobj = ChunkedGLMObjective(LOGISTIC, xn, yn, plan)
    lcfg = OptimizerConfig(max_iterations=30)
    tcfg = OptimizerConfig(optimizer=OptimizerType.TRON, max_iterations=10)
    # warmup traces every [d]-keyed helper + chunk kernel
    solve_streamed(cobj, jnp.zeros(6), lcfg, L2, 1.0,
                   budget=SolveBudget.make(5, 1e-3))
    solve_streamed(cobj, jnp.zeros(6), tcfg, L2, 1.0,
                   budget=SolveBudget.make(3, 1e-3))
    with _compile_counting() as counter:
        for cap, tol in ((2, 1e-1), (9, 1e-6), (30, 1e-9)):
            solve_streamed(cobj, jnp.zeros(6), lcfg, L2, 1.0,
                           budget=SolveBudget.make(cap, tol))
            solve_streamed(cobj, jnp.zeros(6), tcfg, L2, 1.0,
                           budget=SolveBudget.make(min(cap, 10), tol))
    assert counter.count == 0, (
        f"{counter.count} fresh XLA compiles across a streamed budget sweep")


def test_scheduled_game_fit_zero_recompiles_across_outer_iterations(rng):
    """End-to-end: a scheduled GAME fit changes budgets every outer
    iteration; after a 1-outer warmup fit (which runs the full budget),
    a longer scheduled fit must trace nothing new."""
    train, val = _glmix(rng)
    sched = SolverSchedule(initial_iterations=3)

    def config(iters):
        return GameTrainingConfig(
            task_type="logistic_regression",
            coordinates={
                "fixed": FixedEffectCoordinateConfig(
                    "global", GLMOptimizationConfig(
                        regularization=L2, regularization_weight=0.1)),
                "perUser": RandomEffectCoordinateConfig(
                    "userId", "per_user", GLMOptimizationConfig(
                        regularization=L2, regularization_weight=1.0)),
            },
            updating_sequence=["fixed", "perUser"],
            num_outer_iterations=iters, solver_schedule=sched)

    GameEstimator(config(1)).fit(train, val)   # warmup (compiles uncounted)
    with _compile_counting() as counter:
        GameEstimator(config(4)).fit(train, val)
    assert counter.count == 0, (
        f"{counter.count} fresh XLA compiles in a scheduled fit after "
        "warmup — a budget value leaked into a static cache key")


# -- strict-vs-scheduled parity (ISSUE 4 satellite) --------------------------

def _convex_config(iters, sched=None):
    return GameTrainingConfig(
        task_type="logistic_regression",
        coordinates={
            "fixed": FixedEffectCoordinateConfig(
                "global", GLMOptimizationConfig(
                    regularization=L2, regularization_weight=0.1)),
            "perUser": RandomEffectCoordinateConfig(
                "userId", "per_user", GLMOptimizationConfig(
                    regularization=L2, regularization_weight=1.0)),
        },
        updating_sequence=["fixed", "perUser"],
        num_outer_iterations=iters, solver_schedule=sched)


def test_strict_vs_scheduled_final_parity_f64(rng):
    """Convex config in float64: the scheduled fit's FINAL objective (full
    tolerance on the last outer iteration) matches the strict full-solve
    fit within the 1e-4 gate — and far tighter in practice — while using
    fewer inner iterations."""
    train, val = _glmix(rng)
    strict = GameEstimator(_convex_config(5)).fit(train, val)
    sched = GameEstimator(_convex_config(
        5, SolverSchedule(initial_iterations=3))).fit(train, val)
    a, b = strict.objective_history[-1], sched.objective_history[-1]
    # the existing 1e-4 bench parity gate; measured ~5e-6 here — the
    # residual is outer-loop fixed-point convergence, not solver error
    assert abs(a - b) / abs(a) <= 1e-4
    assert sched.descent.total_iterations() < strict.descent.total_iterations()
    # the budget trajectory is recorded: capped early, full (None cap =
    # clipped to max_iterations is still an int) on the final iteration
    diag = sched.descent.solver_diagnostics()
    caps = diag["fixed"]["iteration_caps"]
    assert caps[0] == 3 and caps[-1] == 100
    assert diag["fixed"]["reasons"]  # ConvergenceReason counts surfaced


@pytest.mark.parametrize("per_user_reg", ["l2", "l1"])
def test_solver_diagnostics_count_data_passes_and_trials(rng, per_user_reg):
    """What the device ran, per visit: `data_passes` full value+gradient
    passes and `ls_trials` the line search's trial values.  An entity
    coordinate runs one lock step a bucket, one after the other: both are
    sums over its runs, each run as long as its slowest lane and its search
    as long as any lane's, ended lanes included.  On cached margins (L2) a
    run's passes follow its trips however the search backtracks; under L1
    every lock-step trial is a pass."""
    train, val = _glmix(rng)
    cfg = _convex_config(2)
    if per_user_reg == "l1":
        per_user = cfg.coordinates["perUser"]
        cfg = dataclasses.replace(cfg, coordinates=dict(
            cfg.coordinates, perUser=dataclasses.replace(
                per_user, optimization=dataclasses.replace(
                    per_user.optimization, regularization=L1))))
    descent = GameEstimator(cfg).fit(train, val).descent
    diag = descent.solver_diagnostics()
    for name in ("fixed", "perUser"):
        assert (len(diag[name]["data_passes"]) == len(diag[name]["ls_trials"])
                == diag[name]["solves"] == 2)
    assert len(diag["perUser"]["lockstep"]) == 2
    assert "lockstep" not in diag["fixed"]
    for key, t in descent.trackers.items():
        if key.endswith("/fixed"):      # one lane, on margins
            assert t.data_passes == t.iterations + 2
            assert t.ls_trials >= t.iterations
            assert t.lockstep is None
            continue
        runs = t.lockstep
        assert t.data_passes == sum(runs["data_passes"])
        assert t.ls_trials == sum(runs["lockstep_trials"])
        assert sum(runs["lane_iterations"]) == t.iterations
        assert sum(runs["lanes"]) == 25
        for trips, ran, needed, passes in zip(
                runs["trips"], runs["lockstep_trials"],
                runs["running_trials"], runs["data_passes"]):
            assert trips <= needed <= ran
            if per_user_reg == "l2":
                assert passes == trips + 2
            else:
                assert passes == ran + 1


def test_scheduled_resume_reproduces_trajectory(rng, tmp_path):
    """A scheduled fit interrupted mid-schedule (after outer iteration 0's
    checkpoint) and resumed reproduces the uninterrupted trajectory —
    budgets depend only on (outer iteration, total), which resume
    preserves."""
    from photon_ml_tpu.game.coordinate_descent import (
        read_checkpoint, run_coordinate_descent)

    train, val = _glmix(rng)
    cfg = _convex_config(3, SolverSchedule(initial_iterations=3))
    est = GameEstimator(cfg)
    coords = est._build_coordinates(train)
    schedules = {n: cfg.solver_schedule for n in cfg.updating_sequence}

    straight = run_coordinate_descent(
        coords, cfg.updating_sequence, 3, train, cfg.task_type,
        solver_schedules=schedules)

    class _Interrupt(Exception):
        pass

    class _Bomb:
        """Delegating wrapper that raises at a chosen outer iteration."""

        def __init__(self, inner, at):
            self._inner, self._at = inner, at

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def update(self, model, offsets, **kw):
            if kw.get("outer_iteration") == self._at:
                raise _Interrupt()
            return self._inner.update(model, offsets, **kw)

    ckpt = str(tmp_path / "ckpt")
    bombed = {n: _Bomb(c, 1) for n, c in coords.items()}
    with pytest.raises(_Interrupt):
        run_coordinate_descent(
            bombed, cfg.updating_sequence, 3, train, cfg.task_type,
            checkpoint_dir=ckpt, solver_schedules=schedules)
    state = read_checkpoint(ckpt)
    assert state is not None and state.completed_iterations == 1
    resumed = run_coordinate_descent(
        coords, cfg.updating_sequence, 3, train, cfg.task_type,
        checkpoint_dir=ckpt, resume=state, solver_schedules=schedules)
    assert len(resumed.objective_history) == len(straight.objective_history)
    np.testing.assert_allclose(resumed.objective_history,
                               straight.objective_history, rtol=0, atol=1e-9)


# -- warm latent init --------------------------------------------------------

def _mf_dataset(rng, n=1500, d_user=6, num_users=40):
    xg = rng.normal(size=(n, 4)); xg[:, -1] = 1.0
    xu = rng.normal(size=(n, d_user)); xu[:, -1] = 1.0
    users = rng.integers(0, num_users, size=n)
    # user effects confined to a 2-dim subspace: the principal-subspace
    # warm start has something real to find
    basis = rng.normal(size=(2, d_user))
    w_u = rng.normal(size=(num_users, 2)) @ basis
    z = xg @ rng.normal(size=4) + np.einsum("nd,nd->n", xu, w_u[users])
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(float)
    ids = np.asarray([f"u{u:03d}" for u in users])
    return build_game_dataset(y, {"global": xg, "per_user": xu},
                              entity_ids={"userId": ids})


def _mf_config(iters, latent_dim=2, with_re=True):
    opt = GLMOptimizationConfig(regularization=L2, regularization_weight=1.0)
    coords = {"fixed": FixedEffectCoordinateConfig(
        "global", GLMOptimizationConfig(regularization=L2,
                                        regularization_weight=0.1))}
    seq = ["fixed"]
    if with_re:
        coords["perUser"] = RandomEffectCoordinateConfig(
            "userId", "per_user", opt)
        seq.append("perUser")
    coords["perUserMF"] = FactoredRandomEffectCoordinateConfig(
        "userId", "per_user", latent_dim=latent_dim, optimization=opt,
        latent_optimization=opt)
    seq.append("perUserMF")
    return GameTrainingConfig(task_type="logistic_regression",
                              coordinates=coords, updating_sequence=seq,
                              num_outer_iterations=iters)


def test_warm_latent_init_uses_sibling_subspace(rng):
    ds = _mf_dataset(rng)
    cfg = _mf_config(1)
    coords = GameEstimator(cfg)._build_coordinates(ds)
    re_coord, mf = coords["perUser"], coords["perUserMF"]
    re_model, _ = re_coord.update(re_coord.initial_model(),
                                  jnp.zeros(ds.num_rows))
    cold = mf.initial_model()
    warm = mf.warm_start_latent(cold, {"perUser": re_model})
    assert warm is not None
    # latent factors stay zero: the initial score (and the descent state)
    # is unperturbed
    np.testing.assert_array_equal(np.asarray(warm.latent_coefficients), 0.0)
    # the warm projection's row space captures the sibling solution better
    # than the Gaussian start: smaller out-of-subspace residual
    w = np.asarray(re_model.global_coefficients())

    def resid(p):
        p = np.asarray(p, np.float64)
        proj = p.T @ np.linalg.solve(p @ p.T, p)
        return float(np.linalg.norm(w - w @ proj))

    # the warm projection IS the optimal rank-k subspace of the sibling
    # solution (row permutation cannot change singular subspaces), and
    # strictly better than the Gaussian cold start
    s = np.linalg.svd(w, compute_uv=False)
    optimal = float(np.sqrt((s[2:] ** 2).sum()))
    np.testing.assert_allclose(resid(warm.projection), optimal, rtol=1e-3)
    assert resid(warm.projection) < resid(cold.projection)
    # no compatible sibling -> None (cold start preserved)
    assert mf.warm_start_latent(cold, {"fixed": object()}) is None


def test_warm_latent_init_applies_only_to_cold_first_visit(rng, tmp_path):
    """E2E: the descent warm-inits a cold factored coordinate at its first
    visit; a PROVIDED initial model is never overridden (resume safety)."""
    ds = _mf_dataset(rng)
    cfg = _mf_config(2)
    est = GameEstimator(cfg)
    fit = est.fit(ds)
    assert np.isfinite(fit.objective_history).all()
    # provided initial models (the resume path) keep their projection
    coords = GameEstimator(cfg)._build_coordinates(ds)
    provided = coords["perUserMF"].initial_model()
    from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent
    res = run_coordinate_descent(
        coords, cfg.updating_sequence, 1, ds, cfg.task_type,
        initial_models={"perUserMF": provided})
    assert np.isfinite(res.objective_history).all()

# -- the schedule reaches every coordinate, the factored one included ---------

@pytest.fixture(scope="module")
def mf_strict_and_scheduled():
    """One FE + RE + factored-MF dataset fitted twice over five outer
    iterations, strict and under a schedule that starts at four inner
    iterations: (strict, scheduled) descents.  Fitted once; the cases
    below only read the diagnostics."""
    ds = _mf_dataset(np.random.default_rng(7))
    sched = SolverSchedule(initial_iterations=4, iteration_growth=2.0,
                           initial_tolerance_factor=1e3, tolerance_decay=0.1)
    strict = GameEstimator(_mf_config(5)).fit(ds).descent
    scheduled = GameEstimator(dataclasses.replace(
        _mf_config(5), solver_schedule=sched)).fit(ds).descent
    return strict, scheduled


@pytest.mark.parametrize("coordinate", ["fixed", "perUser", "perUserMF"])
def test_schedule_caps_early_visits_of_every_coordinate(
        mf_strict_and_scheduled, coordinate):
    """Under a schedule the first visit of each coordinate (the factored
    one too) runs at the schedule's small cap; a strict fit records no cap
    on any visit."""
    strict, scheduled = mf_strict_and_scheduled
    caps = scheduled.solver_diagnostics()[coordinate]["iteration_caps"]
    assert caps[0] is not None and caps[0] <= 4
    assert all(c is None for c in
               strict.solver_diagnostics()[coordinate]["iteration_caps"])


def test_scheduled_fit_with_a_factored_coordinate_runs_fewer_iterations(
        mf_strict_and_scheduled):
    strict, scheduled = mf_strict_and_scheduled
    assert scheduled.total_iterations() < strict.total_iterations()
    assert np.isfinite(scheduled.objective_history).all()
