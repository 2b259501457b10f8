"""Out-of-core GAME training (ISSUE 3): chunk planning, double-buffered
prefetch, ChunkedGLMObjective oracle parity, host-stepped solver parity,
HBM-budgeted fits (streamed FE + eviction rotation), peak-memory
accounting, and the compile-count regression across chunk counts.

Parity contract: the streamed oracle computes each chunk with the SAME
fused aggregators the resident path runs on that (padded, masked) row
range, accumulated in chunk order — so it matches a chunk-wise resident
evaluation bit-for-bit, and a full streamed fit matches the resident fit
to ~1e-6 relative objective (float summation order is the only residual;
in this suite's float64 it is typically exact).
"""
import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu.data import build_game_dataset
from photon_ml_tpu.data.batching import (
    RandomEffectDataConfig, build_random_effect_dataset,
)
from photon_ml_tpu.data.streaming import (
    ChunkPlan, Prefetcher, StreamStats, MIN_CHUNK_ROWS,
)
from photon_ml_tpu.game import (
    FixedEffectCoordinateConfig, GameEstimator, GameTrainingConfig,
    GLMOptimizationConfig, RandomEffectCoordinateConfig,
)
from photon_ml_tpu.models.io import save_game_model
from photon_ml_tpu.ops import ChunkedGLMObjective, GLMObjective, TASK_LOSSES
from photon_ml_tpu.optim import (
    OptimizerConfig, OptimizerType, RegularizationContext, RegularizationType,
    solve, solve_streamed,
)

L2 = RegularizationContext(RegularizationType.L2)
LOGISTIC = TASK_LOSSES["logistic_regression"]


# --------------------------------------------------------------------------
# ChunkPlan
# --------------------------------------------------------------------------

def test_chunk_plan_pow2_and_coverage():
    plan = ChunkPlan.build(10_000, chunk_rows=1000)   # rounds up to 1024
    assert plan.chunk_rows == 1024
    assert sum(c.rows for c in plan.chunks) == 10_000
    assert plan.chunks[0].start == 0 and plan.chunks[-1].stop == 10_000
    for c in plan.chunks:
        assert c.padded_rows & (c.padded_rows - 1) == 0   # pow2
        assert c.padded_rows >= c.rows
    # one program per chunk SHAPE: full shape + at most one tail shape
    assert len(plan.chunk_shapes) <= 2


def test_chunk_plan_budget_sizing():
    # two chunks must fit in the budget
    plan = ChunkPlan.build(1_000_000, hbm_budget_bytes=8 << 20,
                           bytes_per_row=1024)
    assert 2 * plan.chunk_rows * 1024 <= 8 << 20
    # a budget larger than the data degenerates to one chunk == resident
    small = ChunkPlan.build(500, hbm_budget_bytes=1 << 30, bytes_per_row=8)
    assert small.num_chunks == 1
    assert small.chunks[0].padded_rows == 512


def test_chunk_plan_floor():
    plan = ChunkPlan.build(100_000, hbm_budget_bytes=10, bytes_per_row=1024)
    assert plan.chunk_rows == MIN_CHUNK_ROWS  # dispatch-overhead floor


# --------------------------------------------------------------------------
# Prefetcher: double buffer bound + ordering + error propagation
# --------------------------------------------------------------------------

def test_prefetcher_bounded_double_buffer():
    plan = ChunkPlan.build(4096, chunk_rows=256)
    stats = StreamStats()
    fetched = []
    pf = Prefetcher(plan, lambda spec: {"v": np.full(spec.padded_rows,
                                                     spec.index, np.float64)},
                    depth=2, stats=stats)
    for _ in range(3):  # several passes over the same plan
        order = [spec.index for spec, _ in pf.stream()]
        assert order == list(range(plan.num_chunks))
    snap = stats.snapshot()
    assert snap["passes"] == 3
    assert snap["chunks_staged"] == 3 * plan.num_chunks
    # the double-buffer invariant: never more than `depth` chunks resident
    assert snap["peak_resident_chunks"] <= 2
    assert snap["total_bytes"] == 3 * plan.num_chunks * 256 * 8
    del fetched


def test_prefetcher_error_propagates():
    plan = ChunkPlan.build(2048, chunk_rows=256)

    def bad_fetch(spec):
        if spec.index == 3:
            raise OSError("host read failed")
        return {"v": np.zeros(spec.padded_rows)}

    with pytest.raises(RuntimeError, match="chunk staging failed"):
        list(Prefetcher(plan, bad_fetch).stream())


# --------------------------------------------------------------------------
# ChunkedGLMObjective: oracle parity
# --------------------------------------------------------------------------

def _problem(rng, n=3000, d=10):
    x = rng.normal(size=(n, d))
    x[:, -1] = 1.0
    w = rng.normal(size=d)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-x @ w))).astype(float)
    weights = rng.uniform(0.5, 1.5, size=n)
    offsets = rng.normal(size=n) * 0.1
    return x, y, weights, offsets


def test_chunked_oracle_bitwise_given_same_chunking(rng):
    """The streamed oracle == a chunk-wise resident evaluation (same padded
    chunks, same masks) BIT-FOR-BIT, for value, gradient, and Hv."""
    x, y, w, off = _problem(rng)
    plan = ChunkPlan.build(len(y), chunk_rows=1024)
    assert plan.num_chunks == 3
    cobj = ChunkedGLMObjective(LOGISTIC, x, y, plan, weights=w, offsets=off,
                               l2_weight=0.3)
    c = jnp.asarray(rng.normal(size=x.shape[1]))
    v_c, g_c = cobj.value_and_gradient(c)
    hv_c = cobj.hessian_vector(c, 0.5 * c)

    # manual chunk-wise resident evaluation through GLMObjective on the
    # SAME padded+masked row ranges, accumulated in the same order
    acc_v = jnp.zeros(())
    acc_g = jnp.zeros_like(c)
    acc_hv = jnp.zeros_like(c)
    for spec in plan.chunks:
        sl = slice(spec.start, spec.stop)
        pad = spec.padded_rows - spec.rows
        pd = lambda a, fill: np.concatenate(
            [a[sl], np.full((pad,) + a.shape[1:], fill, a.dtype)])
        mask = np.concatenate([np.ones(spec.rows), np.zeros(pad)])
        o = GLMObjective(LOGISTIC, jnp.asarray(pd(x, 0.0)),
                         jnp.asarray(pd(y, 0.5)),
                         weights=jnp.asarray(pd(w, 0.0)),
                         offsets=jnp.asarray(pd(off, 0.0)),
                         mask=jnp.asarray(mask))
        v_i, g_i = o.value_and_gradient(c)
        acc_v = acc_v + v_i
        acc_g = acc_g + g_i
        acc_hv = acc_hv + o.hessian_vector(c, 0.5 * c)
    acc_v = acc_v + 0.5 * 0.3 * jnp.dot(c, c)
    acc_g = acc_g + 0.3 * c
    acc_hv = acc_hv + 0.3 * (0.5 * c)

    assert float(v_c) == float(acc_v)
    np.testing.assert_array_equal(np.asarray(g_c), np.asarray(acc_g))
    np.testing.assert_array_equal(np.asarray(hv_c), np.asarray(acc_hv))


def test_chunked_oracle_close_to_resident_single_sum(rng):
    """vs the resident single-sum oracle only float summation order
    differs (~1e-12 relative in f64)."""
    x, y, w, off = _problem(rng)
    plan = ChunkPlan.build(len(y), chunk_rows=512)
    cobj = ChunkedGLMObjective(LOGISTIC, x, y, plan, weights=w, offsets=off,
                               l2_weight=0.3)
    robj = GLMObjective(LOGISTIC, jnp.asarray(x), jnp.asarray(y),
                        weights=jnp.asarray(w), offsets=jnp.asarray(off),
                        l2_weight=0.3)
    c = jnp.asarray(rng.normal(size=x.shape[1]))
    v_c, g_c = cobj.value_and_gradient(c)
    v_r, g_r = robj.value_and_gradient(c)
    np.testing.assert_allclose(float(v_c), float(v_r), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(g_c), np.asarray(g_r), rtol=1e-9,
                               atol=1e-12)
    # streamed scoring == resident matvec
    np.testing.assert_allclose(np.asarray(cobj.scores(c)),
                               np.asarray(jnp.asarray(x) @ c), rtol=1e-12)


def test_chunked_rejects_sparse(rng):
    import scipy.sparse as sp
    x = sp.random(100, 20, density=0.1, format="csr", random_state=0)
    with pytest.raises(TypeError, match="dense host feature block"):
        ChunkedGLMObjective(LOGISTIC, x, np.zeros(100),
                            ChunkPlan.build(100, chunk_rows=256))


# --------------------------------------------------------------------------
# host-stepped solvers: parity with the resident lax.while_loop solvers
# --------------------------------------------------------------------------

@pytest.mark.parametrize("opt,reg,weight,count_slack", [
    (OptimizerConfig(max_iterations=100, tolerance=1e-9), L2, 1.0, 0),
    (OptimizerConfig(optimizer=OptimizerType.TRON, max_iterations=30,
                     tolerance=1e-9), L2, 1.0, 1),
    (OptimizerConfig(max_iterations=150, tolerance=1e-10),
     RegularizationContext(RegularizationType.ELASTIC_NET,
                           elastic_net_alpha=0.5), 0.1, 1),
])
def test_solve_streamed_matches_resident(rng, opt, reg, weight, count_slack):
    x, y, _, _ = _problem(rng)
    d = x.shape[1]
    plan = ChunkPlan.build(len(y), chunk_rows=1024)
    cobj = ChunkedGLMObjective(LOGISTIC, x, y, plan)
    robj = GLMObjective(LOGISTIC, jnp.asarray(x), jnp.asarray(y))
    rs = solve(robj, jnp.zeros(d), opt, reg, weight)
    ss = solve_streamed(cobj, jnp.zeros(d), opt, reg, weight)
    # identical iteration trajectory in f64 (same algorithm, same
    # constants; the streamed oracle differs only by summation order).
    # `count_slack`: the TRON and OWLQN cases run at tolerance 1e-9/1e-10
    # until the objective sits at its float64 resolution (the two value
    # histories differ by exactly one ulp, 2.3e-13 on 1.2e3, from the
    # fifth iteration on).  There a trust-region accept/reject and a
    # line-search backtrack are decided by the sign of a sub-ulp
    # difference, which chunked vs single-sum order flips (under jax
    # 0.9.0's XLA:CPU reduction order it does): TRON stops after 15 vs 14
    # iterations (hv 20 vs 19), OWLQN takes 15 vs 14 evaluations.  The
    # counts may differ by that one step; values and coefficients may not.
    assert abs(int(ss.iterations) - int(rs.iterations)) <= count_slack
    np.testing.assert_allclose(float(ss.value), float(rs.value), rtol=1e-9)
    np.testing.assert_allclose(np.asarray(ss.x), np.asarray(rs.x),
                               rtol=1e-6, atol=1e-9)
    if rs.ls_trials is not None:
        # the same trial points on both sides; the streamed solver pays a
        # fused pass for each, the resident one evaluates them on cached
        # margins where there is neither L1 nor a box (its fg_count is
        # then iterations + 2)
        assert abs(int(ss.fg_count) - (1 + int(rs.ls_trials))) <= count_slack
    if rs.hv_count is not None:
        assert abs(int(ss.hv_count) - int(rs.hv_count)) <= count_slack


def test_solve_streamed_box_constraints(rng):
    x, y, _, _ = _problem(rng, n=2000, d=6)
    d = x.shape[1]
    cfg = OptimizerConfig(max_iterations=100, tolerance=1e-9,
                          box_lower=(-0.2,) * d, box_upper=(0.2,) * d)
    plan = ChunkPlan.build(len(y), chunk_rows=1024)
    ss = solve_streamed(ChunkedGLMObjective(LOGISTIC, x, y, plan),
                        jnp.zeros(d), cfg, L2, 1.0)
    rs = solve(GLMObjective(LOGISTIC, jnp.asarray(x), jnp.asarray(y)),
               jnp.zeros(d), cfg, L2, 1.0)
    assert float(jnp.max(jnp.abs(ss.x))) <= 0.2 + 1e-12
    np.testing.assert_allclose(float(ss.value), float(rs.value), rtol=1e-9)


# --------------------------------------------------------------------------
# full GAME fit: streamed vs resident parity, determinism, peak memory
# --------------------------------------------------------------------------

def _glmix(rng, n=4000, d_global=12, num_users=80, d_user=4):
    xg = rng.normal(size=(n, d_global)); xg[:, -1] = 1.0
    xu = rng.normal(size=(n, d_user)); xu[:, -1] = 1.0
    users = rng.integers(0, num_users, size=n)
    z = xg @ rng.normal(size=d_global) + np.einsum(
        "nd,nd->n", xu, rng.normal(size=(num_users, d_user))[users])
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(float)
    ds = build_game_dataset(y, {"global": xg, "per_user": xu},
                            entity_ids={"userId": np.asarray(
                                [f"u{u:03d}" for u in users])})
    rows = np.arange(n)
    return ds.subset(rows[: int(n * 0.9)]), ds.subset(rows[int(n * 0.9):])


def _config(iters=3, budget=None, chunk_rows=None, memory_mode="auto"):
    return GameTrainingConfig(
        task_type="logistic_regression",
        coordinates={
            "fixed": FixedEffectCoordinateConfig(
                "global", GLMOptimizationConfig(
                    regularization=L2, regularization_weight=0.1),
                memory_mode=memory_mode, chunk_rows=chunk_rows),
            "perUser": RandomEffectCoordinateConfig(
                "userId", "per_user", GLMOptimizationConfig(
                    regularization=L2, regularization_weight=1.0)),
        },
        updating_sequence=["fixed", "perUser"],
        num_outer_iterations=iters,
        hbm_budget_bytes=budget)


def _fe_shard_bytes(train):
    x = train.feature_shards["global"]
    itemsize = np.dtype(jax.dtypes.canonicalize_dtype(x.dtype)).itemsize
    return x.shape[0] * x.shape[1] * itemsize


def test_streamed_fit_parity_and_determinism(rng, tmp_path):
    """Strict parity gate (ISSUE 3): streamed objective history matches
    resident to ~1e-6 relative (exact here in f64), final models within
    gate, and the same chunking gives an IDENTICAL history on a re-run."""
    train, val = _glmix(rng)
    resident = GameEstimator(_config()).fit(train, val)
    # budget below the FE shard -> auto-streams; below total -> rotation
    budget = int(_fe_shard_bytes(train) * 0.6)
    streamed = GameEstimator(_config(budget=budget)).fit(train, val)
    assert len(streamed.objective_history) == len(resident.objective_history)
    np.testing.assert_allclose(streamed.objective_history,
                               resident.objective_history, rtol=1e-6)
    # streamed mode actually engaged
    acct = streamed.residency
    assert acct["streamed_chunk_bytes"], "FE coordinate did not stream"
    # final models within gate (every persisted array)
    save_game_model(resident.descent.model, str(tmp_path / "r"))
    save_game_model(streamed.descent.model, str(tmp_path / "s"))
    import glob
    files_r = sorted(glob.glob(str(tmp_path / "r" / "**" / "*.npz"),
                               recursive=True))
    for fr in files_r:
        fs = fr.replace(str(tmp_path / "r"), str(tmp_path / "s"))
        with np.load(fr, allow_pickle=True) as zr, \
                np.load(fs, allow_pickle=True) as zs:
            for k in zr.files:
                if zr[k].dtype == object:
                    assert np.array_equal(zr[k], zs[k]), (fr, k)
                else:
                    np.testing.assert_allclose(zr[k], zs[k], rtol=1e-6,
                                               atol=1e-8, err_msg=f"{fr}:{k}")

    # same chunking => identical objective history (bit-for-bit determinism)
    streamed2 = GameEstimator(_config(budget=budget)).fit(train, val)
    assert streamed.objective_history == streamed2.objective_history


def test_streamed_fit_peak_memory_under_budget(rng):
    """The acceptance accounting: the streamed fit trains a config whose
    coordinate data EXCEEDS the budget, while tracked peak residency stays
    UNDER it — and the prefetcher held at most 2 chunks at once."""
    train, val = _glmix(rng, n=6000, num_users=120)
    resident = GameEstimator(_config(iters=2)).fit(train, val)
    r_acct = resident.residency
    data_bytes = (r_acct["resident_block_total"]
                  + r_acct["flat_vector_bytes"])
    floor = (max(r_acct["resident_block_bytes"].values())
             + r_acct["flat_vector_bytes"])
    budget = max(int(data_bytes * 0.6), int(floor * 1.05))
    assert budget < data_bytes, "test shape cannot demonstrate out-of-core"

    from photon_ml_tpu.game.coordinate_descent import run_coordinate_descent
    est = GameEstimator(_config(iters=2, budget=budget))
    coords = est._build_coordinates(train)
    fe = coords["fixed"]
    assert fe.streamed
    manager = est._residency_manager(coords, train)
    run_coordinate_descent(coords, est.config.updating_sequence, 2, train,
                           est.config.task_type, residency=manager)
    acct = manager.accounting()
    assert acct["budget_bytes"] == budget
    # impossible before this PR: total coordinate data > budget...
    assert data_bytes > budget
    # ...while the fit never held more than the budget resident
    assert acct["under_budget"], acct
    assert acct["peak_tracked_bytes"] <= budget
    # the double buffer held <= 2 chunks at any moment
    snap = fe._stream.stats.snapshot()
    assert snap["passes"] > 0
    assert snap["peak_resident_chunks"] <= 2
    # two chunks of the plan fit the coordinate's budget share
    assert fe.streaming_buffer_bytes() <= budget


def test_memory_mode_forced_and_validated(rng):
    train, val = _glmix(rng, n=2000, num_users=40)
    # explicit streamed without any budget
    forced = GameEstimator(_config(iters=1, memory_mode="streamed",
                                   chunk_rows=512)).fit(train, val)
    assert forced.residency["streamed_chunk_bytes"]
    # explicit resident under a tiny budget: no streaming, rotation only
    budget = int(_fe_shard_bytes(train) * 0.8)
    res = GameEstimator(_config(iters=1, budget=budget,
                                memory_mode="resident")).fit(train, val)
    assert not res.residency["streamed_chunk_bytes"]
    with pytest.raises(ValueError, match="memory_mode"):
        FixedEffectCoordinateConfig("global", memory_mode="sometimes")


def test_config_round_trip_memory_fields():
    cfg = _config(budget=123_456, chunk_rows=2048, memory_mode="streamed")
    back = GameTrainingConfig.from_json(cfg.to_json())
    assert back.hbm_budget_bytes == 123_456
    fe = back.coordinates["fixed"]
    assert fe.memory_mode == "streamed" and fe.chunk_rows == 2048
    # "auto" encodes as absent so pre-existing checkpoint fingerprints
    # (estimator strips None-valued keys) stay valid
    d = _config().to_dict()
    assert d["coordinates"]["fixed"]["memory_mode"] is None


# --------------------------------------------------------------------------
# compile-count regression: zero fresh traces across chunk COUNTS
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
    """ISSUE 3 satellite (mirroring tests/test_pipeline.py's warm-fit
    tracker): every compiled program in the chunked solve path is keyed on
    the CHUNK shape, never the row count — so a dataset 1.5x larger with
    the same chunk shape must not trace a single new program, for LBFGS
    and TRON, oracle and scoring."""
    d, C = 8, 512

    def make(n, seed):
        x = rng.normal(size=(n, d)); x[:, -1] = 1.0
        y = (rng.uniform(size=n) < 0.5).astype(float)
        return ChunkedGLMObjective(LOGISTIC, x, y,
                                   ChunkPlan.build(n, chunk_rows=C))

    lbfgs_cfg = OptimizerConfig(max_iterations=8, tolerance=1e-9)
    tron_cfg = OptimizerConfig(optimizer=OptimizerType.TRON,
                               max_iterations=5, tolerance=1e-9)
    warm = make(2 * C, 0)        # 2 chunks: warm every program
    for cfg in (lbfgs_cfg, tron_cfg):
        solve_streamed(warm, jnp.zeros(d), cfg, L2, 1.0)
    warm.scores(jnp.zeros(d))

    bigger = make(3 * C, 1)      # 3 chunks, SAME chunk shape
    with _compile_counting() as counter:
        for cfg in (lbfgs_cfg, tron_cfg):
            solve_streamed(bigger, jnp.zeros(d), cfg, L2, 1.0)
        bigger.scores(jnp.zeros(d))
    assert counter.count == 0, (
        f"{counter.count} fresh XLA compiles across differing chunk counts "
        "of the same chunk shape — a program keyed on the row count crept "
        "into the streamed solve path")


# --------------------------------------------------------------------------
# eviction / re-stream + release_host_shards
# --------------------------------------------------------------------------

def test_entity_bucket_evict_and_restream(rng):
    train, _ = _glmix(rng, n=1500, num_users=50)
    cfg = RandomEffectDataConfig("userId", "per_user", keep_host_blocks=True)
    red = build_random_effect_dataset(train, cfg)
    for b in red.buckets:
        b.blocks  # materialize every bucket's device copy
    first = np.asarray(red.buckets[0].blocks.x)
    assert red.device_bytes() > 0
    assert all(b.is_resident for b in red.buckets)
    red.evict_device_blocks()
    assert not any(b.is_resident for b in red.buckets)
    # re-stream gives back the same values
    np.testing.assert_array_equal(np.asarray(red.buckets[0].blocks.x), first)
    # without host copies, evict is a safe no-op
    red2 = build_random_effect_dataset(
        train, RandomEffectDataConfig("userId", "per_user"))
    assert all(b.host_blocks is None for b in red2.buckets)
    red2.evict_device_blocks()
    assert all(b.is_resident for b in red2.buckets)


def test_coordinate_evict_restream_same_result(rng):
    """An evicted coordinate's next update/score re-streams from host and
    produces bit-identical results."""
    train, val = _glmix(rng, n=1500, num_users=50)
    budget = int(_fe_shard_bytes(train) * 10)  # roomy: accounting only
    est = GameEstimator(_config(iters=1, budget=budget))
    coords = est._build_coordinates(train)
    re = coords["perUser"]
    model = re.initial_model()
    offsets = jnp.zeros(train.num_rows)
    m1, _ = re.update(model, offsets)
    s1 = np.asarray(re.score(m1))
    re.evict_device_blocks()
    m2, _ = re.update(model, offsets)
    s2 = np.asarray(re.score(m2))
    np.testing.assert_array_equal(np.asarray(m1.coefficients),
                                  np.asarray(m2.coefficients))
    np.testing.assert_array_equal(s1, s2)


def test_release_host_shards(rng):
    from photon_ml_tpu.data.game_data import ReleasedHostShard
    train, _ = _glmix(rng, n=500, num_users=10)
    with pytest.raises(ValueError, match="no device copy"):
        train.release_host_shard("global")
    dev = train.device_shard("global", release_host=True)
    assert isinstance(train.feature_shards["global"], ReleasedHostShard)
    # metadata (shard_dim) survives; the cached device copy is returned
    assert train.shard_dim("global") == 12
    assert train.device_shard("global") is dev
    # array reads fail loudly, and a dropped device copy is unrecoverable
    with pytest.raises(ValueError, match="released"):
        np.asarray(train.feature_shards["global"])
    train.release_device_shard("global")
    with pytest.raises(ValueError, match="released"):
        train.device_shard("global")


def test_parse_byte_size():
    from photon_ml_tpu.cli.train import parse_byte_size
    assert parse_byte_size("8GB") == 8_000_000_000
    assert parse_byte_size("512mb") == 512_000_000
    assert parse_byte_size("1.5g") == 1_500_000_000
    assert parse_byte_size("4096") == 4096
    assert parse_byte_size(None) is None
    with pytest.raises(SystemExit):
        parse_byte_size("eight gigs")
    with pytest.raises(SystemExit):
        parse_byte_size("-1GB")
