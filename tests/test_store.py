"""Tiered entity store (ISSUE 14): one residency layer for training,
mesh staging, and serving.

Covers the tier-lifecycle satellite: deterministic evict -> spill ->
fetch -> promote round-trips bit-exact in f64; delta-apply-to-warm-row +
rollback restores exact pre-delta bytes; the concurrent
score/fetch/promote stress test runs with the locktrace tracker ARMED
and validated against the static lock graph; and the compile-count
regression (steady-state misses and promotions = zero fresh XLA traces)
on both the serving and training paths.
"""
import logging
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import photon_ml_tpu
from photon_ml_tpu.data.game_data import build_game_dataset
from photon_ml_tpu.game.config import (FixedEffectCoordinateConfig,
                                       GameTrainingConfig,
                                       GLMOptimizationConfig,
                                       RandomEffectCoordinateConfig)
from photon_ml_tpu.game.estimator import GameEstimator
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.game import (FixedEffectModel, GameModel,
                                       RandomEffectModel)
from photon_ml_tpu.models.glm import model_for_task
from photon_ml_tpu.online.delta import CoordinateDelta, ModelDelta
from photon_ml_tpu.optim import RegularizationContext, RegularizationType
from photon_ml_tpu.serving import ScoringService, ServingConfig
from photon_ml_tpu.serving.registry import ModelRegistry
from photon_ml_tpu.serving.scorer import CompiledScorer
from photon_ml_tpu.store import (BlockStore, ColdStore, StoreConfig,
                                 StoreError, TieredEntityStore)
from photon_ml_tpu.utils import faults, locktrace

TASK = "logistic_regression"
D_G, D_U, N_ENT = 6, 4, 300
L2 = RegularizationContext(RegularizationType.L2)

PACKAGE_DIR = os.path.dirname(os.path.abspath(photon_ml_tpu.__file__))
_STATIC_EDGES = None


def lock_order_edges_cached():
    global _STATIC_EDGES
    if _STATIC_EDGES is None:
        from photon_ml_tpu.analysis.concurrency import lock_order_edges
        _STATIC_EDGES = lock_order_edges([PACKAGE_DIR])
    return _STATIC_EDGES


def _small_store(tmp_path, rng, *, E=512, d=6, hot=64, warm=2, seg=100,
                 name="perUser"):
    table = rng.normal(size=(E, d))          # f64 under the test config
    st = TieredEntityStore.create(
        str(tmp_path / name), table,
        StoreConfig(hot_rows=hot, warm_segments=warm, seg_rows=seg,
                    overlay_rows=64, flush_rows=32,
                    scatter_chunk=64, lfu_sample=128), name=name)
    st.warmup()
    return st, table.copy()


def _served(slots, stage, table, staged_values):
    """Values the scoring gather would see: each row from exactly one of
    the main hot table / the per-batch staging window."""
    t = np.asarray(table)
    o = np.asarray(staged_values)
    if not len(o):
        o = np.zeros((1, t.shape[1]), t.dtype)
    assert not ((slots >= 0) & (stage >= 0)).any(), "row in BOTH lanes"
    return np.where((slots >= 0)[:, None], t[np.maximum(slots, 0)],
                    o[np.maximum(stage, 0)])


def _make_model(rng, E=N_ENT):
    fe = FixedEffectModel(
        model_for_task(TASK, Coefficients(
            jnp.asarray(rng.normal(size=D_G)))), "global")
    re = RandomEffectModel(
        random_effect_type="userId", feature_shard="per_user",
        task_type=TASK,
        coefficients=jnp.asarray(rng.normal(size=(E, D_U))),
        entity_ids=np.asarray([f"u{i}" for i in range(E)], dtype=object),
        projection=None, global_dim=D_U)
    return GameModel({"fixed": fe, "perUser": re}, TASK)


def _requests(rng, n, E=N_ENT, unseen=0.05):
    feats = {"global": rng.normal(size=(n, D_G)),
             "per_user": rng.normal(size=(n, D_U))}
    ids = np.asarray(
        [f"u{rng.integers(0, int(E * (1 + unseen)))}" for _ in range(n)],
        dtype=object)
    return feats, {"userId": ids}


# -- tier lifecycle ----------------------------------------------------------

def test_tier_lifecycle_round_trip_bit_exact_f64(rng, tmp_path):
    """Deterministic evict -> spill -> fetch -> promote cycles against a
    host numpy reference: every value served from any tier is bit-exact
    in f64, and after flush the cold directory alone reproduces the
    table."""
    st, ref = _small_store(tmp_path, rng)
    E = len(ref)
    for it in range(30):
        rows = rng.integers(0, E, size=40)
        slots, stage, table, overlay = st.lookup_slots(rows)
        assert np.array_equal(_served(slots, stage, table, overlay),
                              ref[rows]), it
        if it % 3 == 0:
            # deltas land in whatever tier the rows live in
            upd = np.unique(rng.integers(0, E, size=8))
            vals = rng.normal(size=(len(upd), st.dim))
            st.update_rows(upd, vals, promote=(it % 6 == 0))
            ref[upd] = vals
        assert np.array_equal(st.gather_rows(rows), ref[rows])
    snap = st.stats.snapshot()
    # every tier transition actually happened
    assert snap["hot_hits"] > 0 and snap["warm_hits"] > 0
    assert snap["cold_misses"] > 0 and snap["promotions"] > 0
    assert snap["spills"] > 0 and snap["evictions"] > 0
    assert np.array_equal(st.full_table(), ref)
    st.flush()
    reopened = TieredEntityStore.open(str(tmp_path / "perUser"))
    assert np.array_equal(reopened.full_table(), ref)


def test_cold_segment_tamper_refused(rng, tmp_path):
    st, ref = _small_store(tmp_path, rng, name="t")
    st.flush()
    cold = ColdStore.open(str(tmp_path / "t"))
    seg_path = os.path.join(str(tmp_path / "t"), "seg-00002.bin")
    raw = bytearray(open(seg_path, "rb").read())
    raw[13] ^= 0xFF
    with open(seg_path, "wb") as f:
        f.write(bytes(raw))
    with pytest.raises(Exception) as ei:
        cold.read_segment(2)
    assert "sha256" in str(ei.value)
    # through the store: surfaces as a FATAL store.fetch (named block)
    fresh = TieredEntityStore.open(str(tmp_path / "t"), name="t")
    with pytest.raises(StoreError) as ei:
        fresh.gather_rows(np.asarray([250]))   # row in segment 2
    assert "t/seg-2" in str(ei.value)


def test_unique_rows_required_shapes_checked(rng, tmp_path):
    st, _ = _small_store(tmp_path, rng, name="v")
    with pytest.raises(ValueError):
        st.update_rows(np.asarray([0, 1]), np.zeros((3, st.dim)))
    with pytest.raises(ValueError):
        st.update_rows(np.asarray([0, 10**9]), np.zeros((2, st.dim)))


# -- serving tenant ----------------------------------------------------------

def test_tiered_scorer_parity_with_resident(rng, tmp_path):
    """A store-backed scorer (hot tier ~1/5 of the table) returns
    bit-identical scores to the fully resident scorer, including unseen
    ids (fixed-effect-only fallback)."""
    model = _make_model(rng)
    resident = CompiledScorer(model, max_batch=64, min_bucket=8)
    resident.warmup()
    tiered = CompiledScorer(
        model, max_batch=64, min_bucket=8,
        store=StoreConfig(hot_rows=64, warm_segments=2, seg_rows=64,
                          overlay_rows=64, flush_rows=64,
                          scatter_chunk=64, lfu_sample=64),
        store_dir=str(tmp_path / "store"))
    tiered.warmup()
    assert tiered.tiered and not resident.tiered
    for it in range(10):
        feats, ids = _requests(rng, 48)
        a = resident.score(feats, ids)
        b = tiered.score(feats, ids)
        assert np.array_equal(a.scores, b.scores), it
        assert a.entity_lookups == b.entity_lookups
        assert a.entity_hits == b.entity_hits
    totals = tiered.store_totals()
    assert totals["promotions"] > 0 and totals["hot_hits"] > 0
    # the logical table hash matches the resident scorer's device hash
    assert tiered.table_hashes() == resident.table_hashes()


def test_skewed_traffic_is_served_from_the_hot_tier(rng, tmp_path):
    """The residency claim at a small size: a hot tier a fifth of the
    table serves traffic whose head (97% of lookups) fits it at a hot hit
    rate of 90% or better once the misses have been promoted."""
    hot, head = 64, 40
    tiered = CompiledScorer(
        _make_model(rng), max_batch=64, min_bucket=8,
        store=StoreConfig(hot_rows=hot, warm_segments=2, seg_rows=64,
                          overlay_rows=64, flush_rows=64,
                          scatter_chunk=64, lfu_sample=64),
        store_dir=str(tmp_path / "store"))
    tiered.warmup()
    st = tiered.entity_store("perUser")

    def traffic(n=48):
        rows = rng.integers(0, head, size=n)
        tail = rng.random(n) >= 0.97
        rows[tail] = rng.integers(0, N_ENT, size=int(tail.sum()))
        return ({"global": rng.normal(size=(n, D_G)),
                 "per_user": rng.normal(size=(n, D_U))},
                {"userId": np.asarray([f"u{r}" for r in rows],
                                      dtype=object)})

    for _ in range(10):                 # the head gets promoted
        tiered.score(*traffic())
    st.promote_pending()
    before = st.stats.snapshot()
    for _ in range(20):
        tiered.score(*traffic())
    after = st.stats.snapshot()
    hits, warm, cold = (after[k] - before[k]
                        for k in ("hot_hits", "warm_hits", "cold_misses"))
    assert hot < N_ENT
    assert hits / (hits + warm + cold) >= 0.90
    assert after["promotions"] > 0


def test_delta_to_warm_row_rollback_restores_exact_bytes(rng, tmp_path):
    """ISSUE 14 satellite: a delta landing on rows living in the hot,
    warm AND cold tiers, followed by rollback, restores the exact
    pre-delta bytes everywhere (full_table comparison is byte-exact)."""
    model = _make_model(rng)
    tiered = CompiledScorer(
        model, max_batch=64, min_bucket=8,
        store=StoreConfig(hot_rows=64, warm_segments=2, seg_rows=64,
                          overlay_rows=64, flush_rows=64,
                          scatter_chunk=64, lfu_sample=64),
        store_dir=str(tmp_path / "store"))
    tiered.warmup()
    registry = ModelRegistry(lambda d, v: tiered)
    registry.install(tiered, "v1")
    # make rows 0..40 hot; rows ~200+ stay warm/cold
    feats, _ids = _requests(rng, 41)
    tiered.score(feats, {"userId": np.asarray(
        [f"u{i}" for i in range(41)], dtype=object)})
    st = tiered.entity_store("perUser")
    pre = st.full_table().copy()
    rows = np.asarray([0, 40, 200, 299])     # hot, hot, beyond-hot
    prior = np.asarray(tiered.gather_rows("perUser", rows))
    assert np.array_equal(prior, pre[rows])
    vals = rng.normal(size=(4, D_U))
    registry.apply_delta(ModelDelta(base_version="v1", seq=1, coordinates={
        "perUser": CoordinateDelta(rows=rows, values=vals, prior=prior)}))
    post = st.full_table()
    assert np.array_equal(post[rows], vals)
    # feedback-for-cold-entities: the delta PROMOTED the cold rows hot
    slots, _stage, table, _ovl = st.lookup_slots(rows)
    assert (slots >= 0).all()
    assert np.array_equal(np.asarray(table)[slots], vals)
    registry.rollback()
    assert np.array_equal(st.full_table(), pre), \
        "rollback did not restore exact pre-delta bytes across tiers"


def test_store_metrics_on_both_surfaces_and_healthz(rng, tmp_path):
    svc = ScoringService(
        model=_make_model(rng),
        config=ServingConfig(max_batch=64, min_bucket=4,
                             store_budget_rows=64,
                             store_dir=str(tmp_path / "store"),
                             store_warm_segments=2, store_seg_rows=64))
    try:
        for _ in range(6):
            feats, ids = _requests(rng, 32)
            svc.score(feats, ids)
        snap = svc.metrics_snapshot()
        store = snap["store"]
        assert store["warm_hits"] + store["cold_misses"] > 0
        lookups = (store["hot_hits"] + store["warm_hits"]
                   + store["cold_misses"])
        assert lookups > 0 and store["hit_rate"] is not None
        prom = svc.prometheus_metrics()
        for name in ("store_hot_hits", "store_warm_hits",
                     "store_cold_misses", "store_promotions",
                     "store_spills"):
            assert name in prom, name
        hz = svc.healthz()
        assert "store" in hz and hz["store"]["hit_rate"] is not None
        assert "spills" in hz["store"]
    finally:
        svc.close()


def test_store_disabled_surfaces_stay_zero(rng):
    svc = ScoringService(model=_make_model(rng),
                         config=ServingConfig(max_batch=64, min_bucket=4))
    try:
        feats, ids = _requests(rng, 8)
        svc.score(feats, ids)
        snap = svc.metrics_snapshot()
        assert snap["store"]["hit_rate"] is None
        assert snap["store"]["promotions"] == 0
        assert "store" not in svc.healthz()
    finally:
        svc.close()


# -- fault sites -------------------------------------------------------------

def test_store_fetch_transient_absorbed_bit_exact(rng, tmp_path):
    st, ref = _small_store(tmp_path, rng, name="f")
    plan = faults.FaultPlan([{"site": "store.fetch", "action": "transient",
                              "hits": [1, 2]}])
    with faults.injected(plan):
        rows = np.arange(120, 160)
        slots, stage, table, overlay = st.lookup_slots(rows)
    assert np.array_equal(_served(slots, stage, table, overlay),
                          ref[rows])
    assert plan.report()["total_fired"] == 2
    assert st.stats.snapshot()["retries"] >= 2


def test_store_promote_transient_absorbed_bit_exact(rng, tmp_path):
    st, ref = _small_store(tmp_path, rng, name="p")
    plan = faults.FaultPlan([{"site": "store.promote",
                              "action": "transient", "hits": [1]}])
    with faults.injected(plan):
        rows = np.arange(40)
        slots, stage, table, overlay = st.lookup_slots(rows)
    assert np.array_equal(_served(slots, stage, table, overlay),
                          ref[rows])
    assert plan.report()["total_fired"] == 1


def test_store_spill_transient_absorbed_fatal_names_block(rng, tmp_path):
    st, ref = _small_store(tmp_path, rng, name="s", warm=1)
    # touch 3 segments, dirty them, force warm evictions -> spills
    st.update_rows(np.asarray([5]), rng.normal(size=(1, st.dim)))
    plan = faults.FaultPlan([{"site": "store.spill", "action": "transient",
                              "hits": [1]}])
    with faults.injected(plan):
        st.update_rows(np.asarray([150]), rng.normal(size=(1, st.dim)))
        st.update_rows(np.asarray([250]), rng.normal(size=(1, st.dim)))
        st.flush()
    assert plan.report()["total_fired"] == 1
    # fatal spill names the entity block and loses nothing (write-back
    # buffer still holds the bytes)
    st2, ref2 = _small_store(tmp_path, rng, name="s2", warm=1)
    vals = rng.normal(size=(1, st2.dim))
    st2.update_rows(np.asarray([10]), vals)
    ref2[10] = vals
    plan = faults.FaultPlan([{"site": "store.spill", "action": "fatal",
                              "hits": [1]}])
    with faults.injected(plan):
        with pytest.raises(StoreError) as ei:
            st2.update_rows(np.asarray([150]),
                            rng.normal(size=(1, st2.dim)))
            st2.flush()
    assert "s2/seg-" in str(ei.value)
    assert np.array_equal(st2.gather_rows(np.asarray([10])), ref2[[10]])


# -- concurrency -------------------------------------------------------------

def test_concurrent_score_fetch_promote_stress_locktrace_armed(rng,
                                                               tmp_path):
    """ISSUE 14 satellite: concurrent scoring (misses promoting through
    the tiers), deltas landing hot AND warm, rollback, and metric renders
    under the ARMED lock tracker — every observed acquisition order must
    be consistent with the static lock-order graph."""
    with locktrace.enabled() as tracker:
        svc = ScoringService(
            model=_make_model(rng),
            config=ServingConfig(max_batch=64, min_bucket=4,
                                 store_budget_rows=64,
                                 store_dir=str(tmp_path / "store"),
                                 store_warm_segments=2,
                                 store_seg_rows=64))
        stop = threading.Event()
        errors = []

        def scorer_loop(seed):
            r = np.random.default_rng(seed)
            while not stop.is_set():
                feats, ids = _requests(r, 24)
                try:
                    svc.score(feats, ids)
                except Exception as e:  # pragma: no cover
                    errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=scorer_loop, args=(s,),
                                    daemon=True) for s in (11, 13)]
        for t in threads:
            t.start()
        try:
            st = svc.registry.scorer.entity_store("perUser")
            pre = st.full_table().copy()
            for seq in range(1, 4):
                rows = np.unique(rng.integers(0, N_ENT, size=12))
                prior = np.asarray(
                    svc.registry.scorer.gather_rows("perUser", rows))
                vals = rng.normal(size=(len(rows), D_U))
                svc.registry.apply_delta(ModelDelta(
                    base_version=svc.model_version, seq=seq, coordinates={
                        "perUser": CoordinateDelta(rows=rows, values=vals,
                                                   prior=prior)}))
                svc.metrics_snapshot()
            svc.prometheus_metrics()
            svc.rollback()  # delta-aware: reverts ALL pending, newest-first
            assert np.array_equal(st.full_table(), pre)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=5)
            svc.close()
            locktrace.shutdown()
    assert errors == []
    tracker.assert_consistent(lock_order_edges_cached())
    # the store lock must actually have been exercised under load (the
    # test proves nothing if no store acquisition was ever observed)
    assert tracker.acquisitions().get("TieredEntityStore._lock", 0) > 0


# -- compile-count regression ------------------------------------------------

class _CompileCounter(logging.Handler):
    def __init__(self):
        super().__init__()
        self.count = 0
        self.messages = []

    def emit(self, record):
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.count += 1
            self.messages.append(msg[:120])


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


def test_zero_fresh_traces_steady_state_serving(rng, tmp_path):
    """Steady-state misses, promotions, spills AND delta swaps through a
    warmed tiered scorer trace nothing new."""
    model = _make_model(rng)
    tiered = CompiledScorer(
        model, max_batch=64, min_bucket=8,
        store=StoreConfig(hot_rows=64, warm_segments=2, seg_rows=64,
                          overlay_rows=64, flush_rows=64,
                          scatter_chunk=64, lfu_sample=64),
        store_dir=str(tmp_path / "store"))
    tiered.warmup()
    registry = ModelRegistry(lambda d, v: tiered)
    registry.install(tiered, "v1")

    def one_round(seed, seq):
        r = np.random.default_rng(seed)
        feats, ids = _requests(r, 48)
        tiered.score(feats, ids)
        rows = np.unique(r.integers(0, N_ENT, size=8))
        prior = np.asarray(tiered.gather_rows("perUser", rows))
        registry.apply_delta(ModelDelta(
            base_version="v1", seq=seq, coordinates={
                "perUser": CoordinateDelta(
                    rows=rows, values=r.normal(size=(len(rows), D_U)),
                    prior=prior)}))

    one_round(0, 1)     # device_put paths
    with _compile_counting() as counter:
        for s in range(1, 6):
            one_round(s, s + 1)
    assert counter.count == 0, counter.messages
    totals = tiered.store_totals()
    assert totals["promotions"] > 0


# -- training tenant ---------------------------------------------------------

def _glmix(rng, n=3000, d_global=12, num_users=60, d_user=4):
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


def _config(iters=3, budget=None):
    return GameTrainingConfig(
        task_type=TASK,
        coordinates={
            "fixed": FixedEffectCoordinateConfig(
                "global", GLMOptimizationConfig(
                    regularization=L2, regularization_weight=0.1)),
            "perUser": RandomEffectCoordinateConfig(
                "userId", "per_user", GLMOptimizationConfig(
                    regularization=L2, regularization_weight=1.0)),
        },
        updating_sequence=["fixed", "perUser"],
        num_outer_iterations=iters,
        hbm_budget_bytes=budget)


def _rotation_budget(acct):
    """A budget under the all-resident total (rotation on) whose half still
    holds the FE shard (the fixed effect streams when its shard is over half
    the budget, game/coordinates.py: no auto-stream here)."""
    data_bytes = acct["resident_block_total"] + acct["flat_vector_bytes"]
    budget = max(int(data_bytes * 0.8),
                 2 * acct["resident_block_bytes"]["fixed"] + 2)
    assert budget < data_bytes
    return budget


def test_budgeted_fit_through_store_matches_resident_f64(rng):
    """The training tenant: a budgeted fit whose residency rotation runs
    through the store's block handles reproduces the all-resident f64
    objective history <= 1e-10 (bit-exact here: eviction + re-stage moves
    the same host bytes)."""
    train, val = _glmix(rng)
    resident = GameEstimator(_config()).fit(train, val)
    budget = _rotation_budget(resident.residency)
    budgeted = GameEstimator(_config(budget=budget)).fit(train, val)
    b_acct = budgeted.residency
    assert b_acct["evict_inactive"] is True
    assert b_acct["evictions"] > 0
    store = b_acct["store"]
    assert store["evictions"] > 0 and store["fetches"] > 0
    assert any(b["evictions"] > 0 for b in store["blocks"].values())
    np.testing.assert_allclose(budgeted.objective_history,
                               resident.objective_history,
                               rtol=1e-10, atol=0)


def test_training_rotation_store_fetch_site_fires(rng):
    train, val = _glmix(rng, n=1500, num_users=30)
    resident = GameEstimator(_config(iters=2)).fit(train, val)
    budget = _rotation_budget(resident.residency)
    plan = faults.FaultPlan([{"site": "store.fetch", "action": "transient",
                              "hits": [1]},
                             {"site": "store.fetch", "action": "fatal",
                              "hits": [4], "match": {"tier": "device"}}])
    with faults.injected(plan):
        with pytest.raises(StoreError) as ei:
            GameEstimator(_config(iters=4, budget=budget)).fit(train, val)
    assert "block" in str(ei.value)
    assert plan.report()["total_fired"] == 2


def test_zero_fresh_traces_warm_budgeted_refit(rng):
    """Training-path compile regression: a second budgeted fit (same
    shapes) whose rotation keeps evicting/re-fetching through the store
    traces NOTHING new — steady-state fetch/evict is pure data movement."""
    train, val = _glmix(rng, n=1500, num_users=30)
    resident = GameEstimator(_config(iters=2)).fit(train, val)
    budget = _rotation_budget(resident.residency)
    GameEstimator(_config(iters=2, budget=budget)).fit(train, val)
    with _compile_counting() as counter:
        res = GameEstimator(_config(iters=2, budget=budget)).fit(train, val)
    assert res.residency["evictions"] > 0
    assert counter.count == 0, counter.messages


# -- block store unit --------------------------------------------------------

def test_blockstore_touch_evict_accounting():
    evicted = []
    bs = BlockStore()
    bs.register("fixed", evict=lambda: evicted.append("fixed"),
                block_bytes=100)
    bs.register("stream", evict=lambda: evicted.append("stream"),
                streamed=True)
    assert bs.touch("fixed") is True          # initial cold fetch
    assert bs.touch("fixed") is False         # already resident
    bs.evict("fixed")
    assert evicted == ["fixed"]
    bs.evict("fixed")                         # idempotent
    assert evicted == ["fixed"]
    assert bs.touch("fixed") is True          # re-fetch after eviction
    assert bs.touch("stream") is False        # streamed: never managed
    bs.evict("stream")
    assert evicted == ["fixed"]
    snap = bs.snapshot()
    assert snap["fetches"] == 2 and snap["evictions"] == 1
    assert snap["blocks"]["fixed"]["fetches"] == 2
