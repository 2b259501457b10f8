"""Stochastic single-pass solver lane (ISSUE 15): per-chunk local
coordinate descent with hierarchical merge, SolverSchedule lane selection,
Prefetcher pinning accounting, the solve.local fault site, and the
compile-count regressions (chunk counts + 8x1/4x2 meshes).

The contract under test:

  * fixed-point parity — stochastic-early + strict-LBFGS-polish converges
    to the SAME minimizer as strict streamed LBFGS (f64, <= 1e-6; the
    lane is a warm-start generator, the polish pins the fixed point);
  * seeded determinism — a given (plan, seed, chunking) replays
    bit-for-bit across runs;
  * staging amortization — a pinned chunk runs K local epochs for ONE
    staging pass, so examples_per_staged_byte rises by ~K;
  * zero fresh traces across chunk counts of one chunk shape and across
    8x1 / 4x2 meshes (every kernel keyed on the chunk shape only).
"""
import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu.data.game_data import build_game_dataset
from photon_ml_tpu.data.streaming import ChunkPlan, Prefetcher, StreamStats
from photon_ml_tpu.game import (
    FixedEffectCoordinateConfig, GameEstimator, GameTrainingConfig,
    GLMOptimizationConfig, RandomEffectCoordinateConfig,
)
from photon_ml_tpu.ops.chunked import ChunkedGLMObjective, LocalSolveError
from photon_ml_tpu.ops.losses import LOGISTIC, POISSON, SQUARED
from photon_ml_tpu.optim import (
    OptimizerConfig, RegularizationContext, RegularizationType,
    SolverSchedule, StochasticPlan, solve_stochastic, solve_streamed,
)
from photon_ml_tpu.utils import faults

L2 = RegularizationContext(RegularizationType.L2)


def _problem(rng, n=6000, d=12, loss="logistic"):
    x = rng.normal(size=(n, d))
    x[:, -1] = 1.0
    w = rng.normal(size=d) * 0.5
    z = x @ w
    if loss == "logistic":
        y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(float)
    elif loss == "squared":
        y = z + rng.normal(size=n) * 0.1
    else:  # poisson
        y = rng.poisson(np.exp(np.clip(z, None, 3.0))).astype(float)
    return x, y


def _chunked(x, y, loss=LOGISTIC, chunk_rows=1024, l2=1.0, **kw):
    plan = ChunkPlan.build(len(y), chunk_rows=chunk_rows,
                           row_multiple=kw.pop("row_multiple", 1))
    return ChunkedGLMObjective(loss, x, y, plan, l2_weight=l2, **kw)


# --------------------------------------------------------------------------
# schedule / plan plumbing
# --------------------------------------------------------------------------

def test_stochastic_plan_validation():
    with pytest.raises(ValueError, match="local_epochs"):
        StochasticPlan(local_epochs=0)
    with pytest.raises(ValueError, match="merge"):
        StochasticPlan(merge="parallel")
    with pytest.raises(ValueError, match="stochastic_polish_iterations"):
        SolverSchedule(stochastic_passes=1, stochastic_polish_iterations=0)
    with pytest.raises(ValueError, match="stochastic_merge"):
        SolverSchedule(stochastic_passes=1, stochastic_merge="nope")


def test_schedule_lane_selection_and_polish():
    """Early outer iterations get the lane; the final
    stochastic_polish_iterations are strict; disabled = always strict."""
    sched = SolverSchedule(stochastic_passes=2, stochastic_local_epochs=3,
                           stochastic_polish_iterations=2)
    plans = [sched.stochastic_plan(it, 5) for it in range(5)]
    assert all(p is not None for p in plans[:3])
    assert plans[0].passes == 2 and plans[0].local_epochs == 3
    assert plans[3] is None and plans[4] is None
    # a 1-iteration fit is ALL polish
    assert sched.stochastic_plan(0, 1) is None
    assert SolverSchedule().stochastic_plan(0, 5) is None


def test_schedule_json_round_trip_and_fingerprint_stability():
    on = SolverSchedule(stochastic_passes=3, stochastic_local_epochs=8,
                        stochastic_merge="average", stochastic_seed=5,
                        stochastic_polish_iterations=2)
    assert SolverSchedule.from_dict(on.to_dict()) == on
    # strict-only schedules encode EXACTLY as before this PR, so existing
    # checkpoint fingerprints stay valid
    off = SolverSchedule()
    assert set(off.to_dict()) == {"initial_iterations", "iteration_growth",
                                  "initial_tolerance_factor",
                                  "tolerance_decay"}
    assert SolverSchedule.from_dict(off.to_dict()) == off


# --------------------------------------------------------------------------
# fixed-point parity + determinism (the core numerical contract)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("loss,lname", [(LOGISTIC, "logistic"),
                                        (SQUARED, "squared"),
                                        (POISSON, "poisson")])
def test_fixed_point_parity_stochastic_plus_polish(rng, loss, lname):
    """stochastic-early + strict-polish lands on the SAME f64 fixed point
    as strict streamed LBFGS, <= 1e-6 (measured: machine precision)."""
    x, y = _problem(rng, loss=lname)
    d = x.shape[1]
    cfg = OptimizerConfig(max_iterations=300, tolerance=1e-10)

    strict = solve_streamed(_chunked(x, y, loss=loss), jnp.zeros(d),
                            cfg, L2, 1.0)

    obj = _chunked(x, y, loss=loss)
    coarse = solve_streamed(obj, jnp.zeros(d), cfg, L2, 1.0,
                            stochastic=StochasticPlan(passes=2,
                                                      local_epochs=4))
    polished = solve_streamed(obj, coarse.x, cfg, L2, 1.0)
    rel = abs(float(polished.value) - float(strict.value)) / max(
        abs(float(strict.value)), 1e-12)
    assert rel <= 1e-6, (lname, rel)
    np.testing.assert_allclose(np.asarray(polished.x), np.asarray(strict.x),
                               rtol=1e-5, atol=1e-7)
    if loss.d2z_bound is not None:
        # majorized steps descend monotonically, so the warm start does
        # real work: the polish needs no more iterations than a cold
        # strict solve (Poisson's clipped steps carry no such guarantee)
        assert int(polished.iterations) <= int(strict.iterations)


def test_out_of_core_lane_amortises_staging_under_the_budget(rng):
    """The lane's reason to exist, at the solver: with the data over the
    HBM budget and the plan sized from that budget, stochastic-early +
    polish processes >= 1.5x the examples per staged byte of strict
    streamed LBFGS, both sides never hold more than the budget, and the
    pinned chunks really ran several local epochs per staging."""
    x, y = _problem(rng, n=16384, d=16)
    d = x.shape[1]
    data_bytes = x.nbytes + 3 * y.nbytes       # x + labels + mask + weights
    budget = data_bytes // 4
    cfg = OptimizerConfig(max_iterations=80, tolerance=1e-9)

    def objective():
        plan = ChunkPlan.build(len(y), hbm_budget_bytes=budget,
                               bytes_per_row=(d + 3) * x.dtype.itemsize)
        return ChunkedGLMObjective(LOGISTIC, x, y, plan)

    strict_obj = objective()
    strict = solve_streamed(strict_obj, jnp.zeros(d), cfg, L2, 1.0)
    stoch_obj = objective()
    coarse = solve_streamed(stoch_obj, jnp.zeros(d), cfg, L2, 1.0,
                            stochastic=StochasticPlan(passes=2,
                                                      local_epochs=6))
    polished = solve_streamed(stoch_obj, coarse.x, cfg, L2, 1.0)
    s_strict = strict_obj.stats.snapshot()
    s_stoch = stoch_obj.stats.snapshot()
    assert data_bytes > budget
    assert max(s_strict["peak_resident_bytes"],
               s_stoch["peak_resident_bytes"]) <= budget
    assert s_stoch["local_epochs"] > s_stoch["chunks_staged"]
    assert (s_stoch["examples_per_staged_byte"]
            >= 1.5 * s_strict["examples_per_staged_byte"])
    rel = abs(float(polished.value) - float(strict.value)) / abs(
        float(strict.value))
    assert rel <= 1e-6, rel


def test_seeded_determinism_across_runs(rng):
    x, y = _problem(rng)
    d = x.shape[1]
    plan = StochasticPlan(passes=3, local_epochs=4, seed=11)
    runs = [solve_stochastic(_chunked(x, y), jnp.zeros(d), plan)
            for _ in range(2)]
    assert np.array_equal(np.asarray(runs[0].loss_history),
                          np.asarray(runs[1].loss_history), equal_nan=True)
    assert bool(jnp.all(runs[0].x == runs[1].x))
    # a different seed visits coordinates in different orders
    other = solve_stochastic(
        _chunked(x, y), jnp.zeros(d),
        StochasticPlan(passes=3, local_epochs=4, seed=12))
    assert not bool(jnp.all(other.x == runs[0].x))


def test_average_merge_descends_and_is_order_free(rng):
    """The CoCoA-safe averaging merge still makes progress (entry
    objective strictly decreases over passes)."""
    x, y = _problem(rng)
    d = x.shape[1]
    res = solve_stochastic(
        _chunked(x, y), jnp.zeros(d),
        StochasticPlan(passes=3, local_epochs=4, merge="average"))
    hist = np.asarray(res.loss_history)[:3]
    assert np.all(np.isfinite(hist))
    assert hist[1] < hist[0] and hist[2] < hist[1]


def test_lane_respects_normalization(rng):
    """A normalized streamed coordinate runs the lane in normalized
    space via the margin-invariant column algebra — the polished fit
    matches the strict one."""
    from photon_ml_tpu.ops.normalization import (
        NormalizationType, build_normalization_context)
    x, y = _problem(rng, n=4000, d=8)
    d = x.shape[1]
    mean = x.mean(axis=0)
    var = x.var(axis=0)
    norm = build_normalization_context(
        NormalizationType.STANDARDIZATION, mean=jnp.asarray(mean),
        variance=jnp.asarray(var), intercept_index=d - 1)
    cfg = OptimizerConfig(max_iterations=300, tolerance=1e-10)
    strict = solve_streamed(_chunked(x, y, norm=norm), jnp.zeros(d),
                            cfg, L2, 1.0)
    obj = _chunked(x, y, norm=norm)
    coarse = solve_streamed(obj, jnp.zeros(d), cfg, L2, 1.0,
                            stochastic=StochasticPlan(passes=2,
                                                      local_epochs=4))
    polished = solve_streamed(obj, coarse.x, cfg, L2, 1.0)
    rel = abs(float(polished.value) - float(strict.value)) / max(
        abs(float(strict.value)), 1e-12)
    assert rel <= 1e-6


def test_l1_and_box_fall_through_to_strict_lane(rng):
    """OWLQN / box-constrained solves ignore the stochastic plan (their
    prox/projection structure is the host-stepped solver's job)."""
    x, y = _problem(rng, n=3000, d=6)
    d = x.shape[1]
    en = RegularizationContext(RegularizationType.ELASTIC_NET,
                               elastic_net_alpha=0.5)
    plan = StochasticPlan(passes=2, local_epochs=2)
    res = solve_streamed(_chunked(x, y), jnp.zeros(d),
                         OptimizerConfig(max_iterations=50),
                         en, 0.5, stochastic=plan)
    ref = solve_streamed(_chunked(x, y), jnp.zeros(d),
                         OptimizerConfig(max_iterations=50), en, 0.5)
    assert np.array_equal(np.asarray(res.loss_history),
                          np.asarray(ref.loss_history), equal_nan=True)
    box = OptimizerConfig(max_iterations=50, box_lower=(-0.1,) * d,
                          box_upper=(0.1,) * d)
    res_box = solve_streamed(_chunked(x, y), jnp.zeros(d), box, L2, 1.0,
                             stochastic=plan)
    assert float(jnp.max(jnp.abs(res_box.x))) <= 0.1 + 1e-12


# --------------------------------------------------------------------------
# Prefetcher pinning + StreamStats accounting
# --------------------------------------------------------------------------

def test_prefetcher_pinning_accounting():
    """pin_epochs stages each chunk ONCE and books rows*epochs of work:
    examples_per_staged_byte scales with the pin count."""
    plan = ChunkPlan.build(1000, chunk_rows=256)
    fetch = lambda spec: {"x": np.zeros((spec.padded_rows, 4))}
    stats = StreamStats()
    pf = Prefetcher(plan, fetch, stats=stats)
    chunks = sum(1 for _ in pf.stream(pin_epochs=5))
    snap = stats.snapshot()
    assert chunks == plan.num_chunks
    assert snap["chunks_staged"] == plan.num_chunks     # staged ONCE each
    assert snap["local_epochs"] == 5 * plan.num_chunks
    assert snap["examples_processed"] == 5 * 1000
    assert snap["peak_resident_chunks"] <= 2            # double buffer held
    base = snap["total_bytes"]
    assert snap["examples_per_staged_byte"] == 5 * 1000 / base

    # a plain pass books one epoch per chunk
    for _ in pf.stream():
        pass
    snap2 = stats.snapshot()
    assert snap2["local_epochs"] == snap["local_epochs"] + plan.num_chunks
    assert snap2["examples_processed"] == snap["examples_processed"] + 1000
    with pytest.raises(ValueError, match="pin_epochs"):
        next(iter(pf.stream(pin_epochs=0)))


# --------------------------------------------------------------------------
# solve.local fault site
# --------------------------------------------------------------------------

def test_solve_local_transient_retried_bit_exact(rng):
    x, y = _problem(rng, n=3000, d=6)
    d = x.shape[1]
    plan = StochasticPlan(passes=2, local_epochs=2)
    ref = solve_stochastic(_chunked(x, y), jnp.zeros(d), plan)
    spec = faults.FaultSpec(site="solve.local", hits=(1, 3),
                            action="transient")
    with faults.injected(faults.FaultPlan([spec], seed=3)):
        hit = solve_stochastic(_chunked(x, y), jnp.zeros(d), plan)
    assert spec.fired >= 1
    assert bool(jnp.all(hit.x == ref.x))
    assert np.array_equal(np.asarray(hit.loss_history),
                          np.asarray(ref.loss_history), equal_nan=True)


def test_solve_local_fatal_names_the_chunk(rng):
    x, y = _problem(rng, n=3000, d=6)
    d = x.shape[1]
    spec = faults.FaultSpec(site="solve.local", hits=(2,), action="fatal")
    with faults.injected(faults.FaultPlan([spec], seed=3)):
        with pytest.raises(LocalSolveError, match="chunk 1") as err:
            solve_stochastic(_chunked(x, y), jnp.zeros(d),
                             StochasticPlan(passes=1, local_epochs=2))
    assert err.value.chunk_index == 1


# --------------------------------------------------------------------------
# GAME integration: lane engages early, polish final, diagnostics land
# --------------------------------------------------------------------------

def _glmix(rng, n=4000, d_global=12, num_users=80, d_user=4):
    xg = rng.normal(size=(n, d_global)); xg[:, -1] = 1.0
    xu = rng.normal(size=(n, d_user)); xu[:, -1] = 1.0
    users = rng.integers(0, num_users, size=n)
    z = xg @ rng.normal(size=d_global)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(float)
    ds = build_game_dataset(y, {"global": xg, "per_user": xu},
                            entity_ids={"userId": np.asarray(
                                [f"u{u:03d}" for u in users])})
    rows = np.arange(n)
    return ds.subset(rows[: int(n * 0.9)]), ds.subset(rows[int(n * 0.9):])


def _game_config(outer=3, schedule=None, chunk_rows=1024):
    return GameTrainingConfig(
        task_type="logistic_regression",
        coordinates={
            "fixed": FixedEffectCoordinateConfig(
                "global", GLMOptimizationConfig(
                    regularization=L2, regularization_weight=0.1),
                memory_mode="streamed", chunk_rows=chunk_rows),
            "perUser": RandomEffectCoordinateConfig(
                "userId", "per_user", GLMOptimizationConfig(
                    regularization=L2, regularization_weight=1.0)),
        },
        updating_sequence=["fixed", "perUser"],
        num_outer_iterations=outer, solver_schedule=schedule)


def test_game_fit_stochastic_schedule_engages_and_converges(rng):
    """A streamed-FE GAME fit with a stochastic schedule: the lane's
    local epochs show up in the diagnostics (examples_per_staged_byte
    above the strict fit's), the run is deterministic, and the final
    objective tracks the strict fit's closely.  (The <= 1e-6 fixed-point
    parity contract is the SOLVER-level gate above — at fit level both
    runs are still contracting toward the joint optimum at the outer-CD
    rate, so only a coarse gate is honest at small iteration counts.)"""
    train, val = _glmix(rng)
    sched = SolverSchedule(stochastic_passes=2, stochastic_local_epochs=6)
    stoch = GameEstimator(_game_config(6, sched)).fit(train, val)
    strict = GameEstimator(_game_config(6)).fit(train, val)
    rel = abs(stoch.objective_history[-1] - strict.objective_history[-1]) \
        / abs(strict.objective_history[-1])
    assert rel <= 1e-2, rel
    # the coarse iterations made real progress: the stochastic fit ends
    # below the strict fit's first full outer iteration
    assert stoch.objective_history[-1] < strict.objective_history[1]

    d_stoch = stoch.descent.solver_diagnostics()["fixed"]["stream"]
    d_strict = strict.descent.solver_diagnostics()["fixed"]["stream"]
    assert d_stoch["local_epochs"] > d_stoch["chunks_staged"]
    assert d_stoch["examples_per_staged_byte"] \
        > 1.3 * d_strict["examples_per_staged_byte"]
    # residency accounting mirrors the same snapshot per streamed coord
    assert "fixed" in stoch.residency["stream"]
    assert stoch.residency["stream"]["fixed"]["local_epochs"] > 0

    again = GameEstimator(_game_config(6, sched)).fit(train, val)
    assert again.objective_history == stoch.objective_history


# --------------------------------------------------------------------------
# compile-count regressions
# --------------------------------------------------------------------------

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


def test_zero_new_traces_across_chunk_counts(rng):
    """The local-epoch program is keyed on the chunk SHAPE (and the
    static epoch count) — never the chunk index, chunk count, pass index,
    or seed — so a dataset with more chunks of the same shape traces
    nothing new."""
    d, C = 8, 512
    plan = StochasticPlan(passes=2, local_epochs=3)

    def make(n, seed):
        r = np.random.default_rng(seed)
        x = r.normal(size=(n, d)); x[:, -1] = 1.0
        y = (r.uniform(size=n) < 0.5).astype(float)
        return _chunked(x, y, chunk_rows=C)

    warm = make(2 * C, 0)
    solve_stochastic(warm, jnp.zeros(d), plan)
    with _compile_counting() as counter:
        solve_stochastic(warm, jnp.zeros(d), plan)            # warm passes
        solve_stochastic(make(4 * C, 1), jnp.zeros(d),        # more chunks
                         StochasticPlan(passes=1, local_epochs=3, seed=9))
    assert counter.count == 0, (
        f"{counter.count} fresh XLA compiles across chunk counts of one "
        "chunk shape — a program keyed on chunk count/index/seed crept in")


@pytest.mark.parametrize("mesh_shape", [(8, 1), (4, 2)])
def test_zero_new_traces_warm_mesh(rng, mesh_shape):
    """Each mesh shape compiles the kernel once; warm passes (and more
    chunks of the same shape) trace nothing new — on 8x1 AND 4x2."""
    from photon_ml_tpu.parallel import make_mesh
    nd, nf = mesh_shape
    mesh = make_mesh(nd, nf)
    d, C = 8, 512
    plan = StochasticPlan(passes=1, local_epochs=2)

    def make(n, seed):
        r = np.random.default_rng(seed)
        x = r.normal(size=(n, d)); x[:, -1] = 1.0
        y = (r.uniform(size=n) < 0.5).astype(float)
        return _chunked(x, y, chunk_rows=C, row_multiple=nd, mesh=mesh)

    warm = make(2 * C, 0)
    res = solve_stochastic(warm, jnp.zeros(d), plan)
    # second warm round: the carried iterate comes back with the mesh's
    # output sharding (vs the unsharded x0), which is its own cache key
    res = solve_stochastic(warm, res.x, plan)
    with _compile_counting() as counter:
        solve_stochastic(warm, res.x, plan)
        solve_stochastic(make(3 * C, 1), jnp.zeros(d), plan)
    assert counter.count == 0, (
        f"{counter.count} fresh XLA compiles on warm {nd}x{nf} mesh passes")


def test_mesh_history_parity_vs_single_device(rng):
    """The SAME plan + seed on one device and on an 8x1 data mesh gives
    the same per-pass objective history (float-summation-order residual
    only) and the same final coefficients."""
    from photon_ml_tpu.parallel import make_mesh
    x, y = _problem(rng, n=4096, d=10)
    d = x.shape[1]
    plan = StochasticPlan(passes=2, local_epochs=3)
    single = solve_stochastic(_chunked(x, y, chunk_rows=1024,
                                       row_multiple=8),
                              jnp.zeros(d), plan)
    mesh = solve_stochastic(_chunked(x, y, chunk_rows=1024, row_multiple=8,
                                     mesh=make_mesh(8, 1)),
                            jnp.zeros(d), plan)
    h1 = np.asarray(single.loss_history)
    h2 = np.asarray(mesh.loss_history)
    mask = np.isfinite(h1)
    np.testing.assert_allclose(h2[mask], h1[mask], rtol=1e-9)
    np.testing.assert_allclose(np.asarray(mesh.x), np.asarray(single.x),
                               rtol=1e-9, atol=1e-12)
