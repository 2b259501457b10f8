"""The per-entity solve's own count of its lock step (ISSUE 37): what the
vmapped L-BFGS ran, beside what its running lanes needed, reduced over the
lanes inside `jit_re_bucket_solve`, read at the flush and put on the
profiler's clock as `photon/re/lockstep` events."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import lockstep_reduce
from photon_ml_tpu.data import build_game_dataset
from photon_ml_tpu.game import (FixedEffectCoordinateConfig, GameEstimator,
                                GameTrainingConfig, GLMOptimizationConfig,
                                RandomEffectCoordinateConfig)
from photon_ml_tpu.game.coordinate_descent import _summarize_tracker
from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
from photon_ml_tpu.ops import LOGISTIC, GLMObjective
from photon_ml_tpu.optim import (OptimizerConfig, RegularizationContext,
                                 RegularizationType, lbfgs, solve)
from photon_ml_tpu.optim.types import LOCKSTEP, ConvergenceReason
from photon_ml_tpu.parallel.random_effect import (EntityBlocks,
                                                  fit_random_effects)
from tests.synthetic import make_glm_data

L2 = RegularizationContext(RegularizationType.L2)
L1 = RegularizationContext(RegularizationType.L1)
_MAX_LS = 30


def _counts(res, run=0):
    return dict(zip(LOCKSTEP, (int(v) for v in np.asarray(res.lockstep)[run])))


def _blocks(rng, lanes, S=40, d=4, stretch=None):
    xs, ys = [], []
    for _ in range(lanes):
        x, y, _, _ = make_glm_data(rng, n=S, d=d, task="logistic")
        xs.append(x), ys.append(y)
    if stretch is not None:     # a first step that overshoots: backtracks
        lane, scale = stretch
        xs[lane] = xs[lane] * scale
    return EntityBlocks(jnp.asarray(np.stack(xs)), jnp.asarray(np.stack(ys)),
                        jnp.ones((lanes, S)))


def _alone(blocks, i, reg, lam):
    """Lane i solved alone, as the batched program solves it."""
    obj = GLMObjective(LOGISTIC, blocks.x[i], blocks.labels[i],
                       mask=blocks.mask[i])
    return jax.jit(lambda: solve(obj, jnp.zeros(blocks.dim),
                                 OptimizerConfig(), reg, lam))()


def test_a_bucket_of_identical_lanes_counts_one_lane(rng):
    """Every lane is the same solve: the lock step ran what one lane
    needed, every trip of every lane was a running lane's."""
    one = _blocks(rng, 1, stretch=(0, np.array([1e3, 1.0, 1e-2, 1.0])))
    E = 5
    same = EntityBlocks(*(jnp.repeat(a, E, axis=0)
                          for a in (one.x, one.labels, one.mask)))
    res = fit_random_effects(same, LOGISTIC, reg=L2, reg_weight=0.1)
    alone = _alone(one, 0, L2, 0.1)
    c = _counts(res)
    its, trials = int(alone.iterations), int(alone.ls_trials)
    assert trials > its      # the search backtracked
    assert c == dict(entities=E, samples=40, lanes=E, trips=its,
                     lane_iterations=E * its, lockstep_trials=trials,
                     running_trials=trials, data_passes=its + 2)
    assert lockstep_reduce.lane_occupancy([c]) == 100.0
    assert lockstep_reduce.ended_trial_share([c]) == 0.0


def _quadratic(center, sign):
    """sum (x - c)^2 with its gradient times `sign`: at sign -1 the
    direction climbs, every trial fails Armijo and the lane ends at its
    first trip, where its frozen state fails the same way on every later
    trip of the lock step."""
    def value_and_grad(x):
        r = x - center
        return jnp.sum(r * r) + 0.1 * jnp.sum(jnp.abs(r) ** 3), sign * (
            2.0 * r + 0.3 * r * jnp.abs(r))
    return value_and_grad


def _solve(center, sign, lane_axis=None, cap=None):
    return lbfgs(_quadratic(center, sign), jnp.zeros(center.shape[-1]),
                 max_iterations=50, tolerance=1e-9, lane_axis=lane_axis,
                 iteration_cap=cap)


def _per_trip_trials(center, sign, iterations):
    """A lane's trials at each of its iterations, replayed alone: the
    difference of `ls_trials` between caps k - 1 and k."""
    run = jax.jit(lambda cap: _solve(center, sign, cap=cap).ls_trials)
    totals = [int(run(k)) for k in range(iterations + 1)]
    return np.diff(totals)


def test_a_lane_that_ends_at_its_first_trip_beside_one_that_runs_on():
    """Trips, lane iterations and the running lanes' trials are a per-lane
    replay's, exactly; the lock step ran more, because the ended lane's
    search kept failing on its frozen state on every trip."""
    centers = jnp.asarray([[2.0, -1.0, 0.5], [3.0, 1.5, -2.0]])
    signs = jnp.asarray([-1.0, 1.0])
    lanes = jax.jit(jax.vmap(lambda c, s: _solve(c, s, lane_axis="lanes"),
                             axis_name="lanes"))(centers, signs)
    alone = [jax.jit(_solve)(centers[i], signs[i]) for i in range(2)]
    its = [int(a.iterations) for a in alone]
    assert its[0] == 1 and its[1] > 3
    assert int(alone[0].reason) == ConvergenceReason.LINE_SEARCH_FAILED
    np.testing.assert_array_equal(np.asarray(lanes.iterations), its)
    per_trip = [_per_trip_trials(centers[i], signs[i], its[i])
                for i in range(2)]
    assert per_trip[0].tolist() == [1 + _MAX_LS]
    trips = max(its)
    running = sum(max(t[k] for t, n in zip(per_trip, its) if n > k)
                  for k in range(trips))
    ran, needed, passes = (int(np.max(a)) for a in lanes.lockstep)
    assert needed == running
    assert ran == trips * (1 + _MAX_LS) > needed
    assert passes == ran + 1      # every trial a fused value+gradient
    c = dict(lanes=2, trips=trips, lane_iterations=sum(its),
             lockstep_trials=int(ran), running_trials=int(needed))
    assert lockstep_reduce.lane_occupancy([c]) == pytest.approx(
        100 * sum(its) / (2 * trips))
    assert lockstep_reduce.ended_trial_share([c]) > 50


@pytest.mark.parametrize("reg", ["l2", "l1"])
def test_the_counters_change_no_output_of_the_solve(rng, reg):
    """The counting lock step returns what one without the lane axis
    returns, bit for bit, and every lane what it returns alone."""
    ctx = L2 if reg == "l2" else L1
    blocks = _blocks(rng, 9, stretch=(4, np.array([1e3, 1.0, 1e-2, 1.0])))
    res = fit_random_effects(blocks, LOGISTIC, reg=ctx, reg_weight=0.1)

    def plain(x, y, m):
        return solve(GLMObjective(LOGISTIC, x, y, mask=m),
                     jnp.zeros(blocks.dim), OptimizerConfig(), ctx, 0.1)

    uncounted = jax.jit(jax.vmap(plain))(blocks.x, blocks.labels,
                                         blocks.mask)
    assert uncounted.lockstep is None
    for field in uncounted._fields:
        a, b = getattr(res, field), getattr(uncounted, field)
        if b is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=field)
    for i in (0, 4, 8):
        one = _alone(blocks, i, ctx, 0.1)
        assert int(one.iterations) == int(res.iterations[i])
        assert int(one.ls_trials) == int(res.ls_trials[i])
        assert int(one.reason) == int(res.reason[i])
        np.testing.assert_allclose(res.x[i], one.x, rtol=1e-9, atol=1e-12)
    c = _counts(res)
    assert c["trips"] == int(np.max(res.iterations))
    assert c["lane_iterations"] == int(np.sum(res.iterations))
    assert c["lockstep_trials"] >= c["running_trials"] >= int(
        np.max(res.ls_trials))


def _skewed_dataset(rng, n=1200, users=40):
    """Users with 2 to 120 rows, so the coordinate runs several buckets."""
    counts = np.maximum(2, (120 * rng.power(0.3, size=users)).astype(int))
    ids = np.repeat([f"u{u:03d}" for u in range(users)], counts)[:n]
    m = len(ids)
    x = rng.normal(size=(m, 4)); x[:, -1] = 1.0
    xg = rng.normal(size=(m, 3)); xg[:, -1] = 1.0
    y = (rng.uniform(size=m) < 1 / (1 + np.exp(-x[:, 0]))).astype(float)
    return build_game_dataset(y, {"global": xg, "per_user": x},
                              entity_ids={"userId": ids})


def test_data_passes_of_a_multi_bucket_coordinate_sum_each_buckets_most(rng):
    ds = _skewed_dataset(rng)
    cfg = RandomEffectCoordinateConfig(
        "userId", "per_user", GLMOptimizationConfig(
            regularization=L2, regularization_weight=1.0))
    coord = RandomEffectCoordinate("perUser", ds, cfg, "logistic_regression")
    buckets = coord.red.buckets
    assert len(buckets) >= 2
    _, res = coord.update(coord.initial_model(), jnp.zeros(ds.num_rows))
    fg = np.asarray(res.fg_count)
    most = [int(fg[b.lane_start: b.lane_start + b.num_entities].max())
            for b in buckets]
    rows = np.asarray(res.lockstep)
    assert rows.shape == (len(buckets), len(LOCKSTEP))
    runs = dict(zip(LOCKSTEP, rows.T.tolist()))
    assert runs["data_passes"] == most
    assert runs["entities"] == [b.num_entities for b in buckets]
    summary = _summarize_tracker(res, 0.0)
    assert summary.lockstep == runs
    assert summary.data_passes == sum(most) > int(fg.max())
    assert summary.ls_trials == sum(runs["lockstep_trials"])
    # what the flush fetched is what the tracker holds
    assert _summarize_tracker(res, 0.0, lockstep=rows).lockstep == runs


def test_each_run_is_marked_on_the_profilers_clock_inside_its_fit(rng,
                                                                  tmp_path):
    """A traced fit: one `photon/re/lockstep` event a run, carrying the
    tracker's counts, inside the fit's mark; nothing outside it."""
    ds = _skewed_dataset(rng)
    cfg = GameTrainingConfig(
        task_type="logistic_regression",
        coordinates={
            "fixed": FixedEffectCoordinateConfig("global",
                                                 GLMOptimizationConfig(
                                                     regularization=L2)),
            "perUser": RandomEffectCoordinateConfig(
                "userId", "per_user", GLMOptimizationConfig(
                    regularization=L2, regularization_weight=1.0))},
        updating_sequence=["fixed", "perUser"], num_outer_iterations=2)
    estimator = GameEstimator(cfg)
    estimator.fit(ds)       # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(lockstep_reduce.FIT_MARK):
        descent = estimator.fit(ds).descent
    jax.profiler.stop_trace()
    estimator.fit(ds)       # after tracing stopped: no event
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    (runs,) = lockstep_reduce.per_fit(lockstep_reduce.read_events(path))
    trackers = [descent.trackers[f"{it}/perUser"] for it in (0, 1)]
    assert len(runs) == sum(len(t.lockstep["trips"]) for t in trackers)
    for it, t in enumerate(trackers):
        mine = [r for r in runs if r["visit"] == it]
        assert {r["coordinate"] for r in mine} == {"perUser"}
        for key, column in t.lockstep.items():
            assert [r[key] for r in sorted(mine, key=lambda r: r["run"])] \
                == column
    assert lockstep_reduce.trips(runs) == sum(sum(t.lockstep["trips"])
                                              for t in trackers)
