"""Online learning tier tests (photon_ml_tpu/online/).

Covers the ISSUE 9 acceptance scenarios: online-updated entity
coefficients match an offline refit of the same entities (f64, through the
training-side block build AND an independent scipy oracle), feedback
buffer backpressure/dedup/coalescing, delta durability (atomic writes via
utils/durable.py), delta-aware rollback interleaved with full-model swaps
under concurrent scoring, the compile-count regression (a warm serve loop
absorbing a delta stream traces NOTHING new), and the containment
discipline on the `online.solve`/`online.publish` fault sites (transient
retry, non-finite freeze — never a poisoned live table).
"""
import logging
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy.optimize import minimize

import photon_ml_tpu

from photon_ml_tpu.data.game_data import build_game_dataset
from photon_ml_tpu.game.anchored import (anchored_objective_np, entity_rows,
                                         offline_anchored_refit,
                                         solve_anchored,
                                         sub_dataset_for_entities)
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.game import (FixedEffectModel, GameModel,
                                       RandomEffectModel)
from photon_ml_tpu.models.glm import model_for_task
from photon_ml_tpu.models.io import load_model_delta, save_model_delta
from photon_ml_tpu.online import (FeedbackBuffer, ModelDelta, Observation,
                                  OnlineUpdateConfig)
from photon_ml_tpu.online.delta import CoordinateDelta
from photon_ml_tpu.ops import TASK_LOSSES
from photon_ml_tpu.optim import OptimizerConfig
from photon_ml_tpu.parallel.random_effect import EntityBlocks
from photon_ml_tpu.serving import (Overloaded, ScoringService, ServingConfig,
                                   StaleDeltaError)
from photon_ml_tpu.utils import faults, locktrace

D_G, D_U, N_ENT = 6, 4, 30
TASK = "logistic_regression"


def _make_model(rng, coef_scale=1.0):
    fe = FixedEffectModel(
        model_for_task(TASK, Coefficients(
            jnp.asarray(coef_scale * rng.normal(size=D_G)))), "global")
    re = RandomEffectModel(
        random_effect_type="userId", feature_shard="per_user",
        task_type=TASK,
        coefficients=jnp.asarray(coef_scale * rng.normal(size=(N_ENT, D_U))),
        entity_ids=np.asarray([f"u{i}" for i in range(N_ENT)], dtype=object),
        projection=None, global_dim=D_U)
    return GameModel({"fixed": fe, "perUser": re}, TASK)


def _service(rng, *, updates=None, start_updater=False, **svc_kw):
    svc_kw.setdefault("config", ServingConfig(max_batch=64, min_bucket=4))
    return ScoringService(model=_make_model(rng), updates=updates,
                          start_updater=start_updater, **svc_kw)


def _feedback(rng, n, ids=None):
    feats = {"global": rng.normal(size=(n, D_G)),
             "per_user": rng.normal(size=(n, D_U))}
    if ids is None:
        ids = np.asarray([f"u{rng.integers(0, N_ENT)}" for _ in range(n)],
                         dtype=object)
    labels = (rng.uniform(size=n) < 0.5).astype(float)
    return feats, {"userId": ids}, labels


def _obs(rng, entity="u0", event_id=None, t=0.0):
    return Observation(
        features={"global": rng.normal(size=D_G),
                  "per_user": rng.normal(size=D_U)},
        ids={"userId": entity}, label=1.0, weight=1.0, offset=0.0,
        enqueued_at=t, event_id=event_id)


# -- the anchored solve math ------------------------------------------------

def test_anchored_solve_matches_scipy_oracle(rng):
    """solve_anchored lands on the scipy L-BFGS-B optimum of the anchored
    objective (independent implementation: host numpy, no shared code)."""
    E, S = 3, 12
    x = rng.normal(size=(E, S, D_U))
    labels = (rng.uniform(size=(E, S)) < 0.5).astype(float)
    mask = np.ones((E, S))
    mask[1, 8:] = 0.0
    offsets = rng.normal(size=(E, S)) * 0.3
    prior = rng.normal(size=(E, D_U))
    blocks = EntityBlocks(
        x=jnp.asarray(x), labels=jnp.asarray(labels), mask=jnp.asarray(mask),
        weights=jnp.asarray(mask), offsets=jnp.asarray(offsets * mask))
    lam = 0.8
    new_rows, res = solve_anchored(
        blocks, jnp.asarray(prior), TASK_LOSSES[TASK],
        OptimizerConfig(max_iterations=200, tolerance=1e-12), lam)
    new_rows = np.asarray(new_rows)
    for e in range(E):
        keep = mask[e] > 0
        f = lambda c: anchored_objective_np(
            x[e][keep], labels[e][keep], None, offsets[e][keep], c,
            prior[e], TASK, lam)
        ref = minimize(f, prior[e], method="L-BFGS-B", tol=1e-14).x
        assert np.max(np.abs(new_rows[e] - ref)) <= 1e-5 * max(
            1.0, np.max(np.abs(ref)))


def test_entity_sub_dataset_extraction(rng):
    feats, ids, labels = _feedback(rng, 40)
    ds = build_game_dataset(labels, feats, entity_ids=ids)
    wanted = ["u1", "u3"]
    rows = entity_rows(ds, "userId", wanted)
    got = set(np.asarray(ids["userId"])[rows].tolist())
    assert got <= set(wanted)
    n_expected = int(np.isin(ids["userId"].astype(str),
                             np.asarray(wanted, str)).sum())
    assert len(rows) == n_expected
    sub = sub_dataset_for_entities(ds, "userId", wanted)
    assert sub.num_rows == n_expected


# -- parity with an offline refit -------------------------------------------

def test_online_update_parity_with_offline_refit(rng):
    """The tentpole guarantee: the online path (FeedbackBuffer blocks,
    micro-batched padded lanes, warm-started delta-space solve) and an
    offline refit of the same entities through build_random_effect_dataset
    land on the same coefficients in f64 (<= 1e-6 rel)."""
    anchor = 0.6
    svc = _service(rng, updates=OnlineUpdateConfig(
        micro_batch=4, anchor_weight=anchor, max_iterations=200,
        tolerance=1e-12))
    try:
        scorer = svc.registry.scorer
        touched = ["u0", "u1", "u2", "u5", "u7", "u9", "u11"]
        n = 35
        feats, ids, labels = _feedback(
            rng, n, ids=np.asarray([touched[i % len(touched)]
                                    for i in range(n)], dtype=object))
        table0 = np.asarray(scorer.re_table("perUser"))
        prior = {u: table0[scorer.entity_row("perUser", u)].copy()
                 for u in touched}
        margins = scorer.score(feats, ids).scores
        svc.feedback(feats, ids, labels)
        out = svc.updater.flush()
        assert out["entities"] == len(touched)
        assert out["deltas"] >= 2     # micro_batch 4 < 7 touched entities
        table1 = np.asarray(scorer.re_table("perUser"))
        ds = build_game_dataset(labels, feats, offsets=margins,
                                entity_ids=ids)
        offline = offline_anchored_refit(
            ds, "userId", "per_user", prior, TASK_LOSSES[TASK],
            OptimizerConfig(max_iterations=200, tolerance=1e-12),
            anchor_weight=anchor)
        for u in touched:
            row = table1[scorer.entity_row("perUser", u)]
            denom = max(float(np.max(np.abs(offline[u]))), 1e-12)
            assert np.max(np.abs(row - offline[u])) / denom <= 1e-6, u
            # the update MOVED the row (fresh labels carry signal)
            assert not np.array_equal(row, prior[u])
        # untouched entities' rows are bit-identical
        untouched = [i for i in range(N_ENT)
                     if f"u{i}" not in set(touched)]
        assert np.array_equal(table1[untouched], table0[untouched])
    finally:
        svc.close()


# -- feedback buffer --------------------------------------------------------

def test_buffer_backpressure_overloaded(rng):
    buf = FeedbackBuffer(max_rows=8, entity_window=8)
    entries = [("perUser", f"u{i}", i, _obs(rng, f"u{i}")) for i in range(8)]
    buf.offer_batch(entries)
    with pytest.raises(Overloaded):
        buf.offer_batch([("perUser", "u9", 9, _obs(rng, "u9"))])
    assert buf.stats()["shed"] == 1
    # rejection is all-or-nothing: nothing from the failed batch landed
    assert buf.pending_rows == 8
    # draining frees capacity again
    buf.drain("perUser", 8)
    out = buf.offer_batch([("perUser", "u9", 9, _obs(rng, "u9"))])
    assert out["accepted"] == 1


def test_buffer_event_dedup_and_entity_window(rng):
    buf = FeedbackBuffer(max_rows=100, entity_window=3, dedup_window=10)
    a = _obs(rng, "u0", event_id="ev-1")
    out = buf.offer_batch([("perUser", "u0", 0, a)])
    assert out["accepted"] == 1
    # a client retry with the same event id is dropped
    out = buf.offer_batch([("perUser", "u0", 0, _obs(rng, "u0",
                                                     event_id="ev-1"))])
    assert out["accepted"] == 0 and out["deduped"] == 1
    # one event fanning out to two lanes is NOT a duplicate
    b = _obs(rng, "u1", event_id="ev-2")
    out = buf.offer_batch([("perUser", "u1", 1, b),
                           ("perItem", "i1", 0, b)])
    assert out["accepted"] == 2 and out["deduped"] == 0
    # per-entity window: only the newest 3 observations survive
    obs = [_obs(rng, "u0", t=float(i)) for i in range(6)]
    buf.offer_batch([("perUser", "u0", 0, o) for o in obs])
    drained = buf.drain("perUser", 10)
    u0 = next(ef for ef in drained if ef.entity_id == "u0")
    assert len(u0.observations) == 3
    assert [o.enqueued_at for o in u0.observations] == [3.0, 4.0, 5.0]


def test_unseen_entity_feedback_dropped(rng):
    svc = _service(rng, updates=OnlineUpdateConfig(micro_batch=4))
    try:
        feats, _ids, labels = _feedback(rng, 4)
        ids = {"userId": np.asarray(["u0", "ghost1", "ghost2", "u1"],
                                    dtype=object)}
        out = svc.feedback(feats, ids, labels)
        assert out["dropped_unseen"] == 2
        assert out["accepted"] == 2
        snap = svc.metrics_snapshot()
        assert snap["online"]["dropped_unseen"] == 2
    finally:
        svc.close()


# -- delta durability --------------------------------------------------------

def test_delta_durability_roundtrip_and_verification(rng, tmp_path):
    delta = ModelDelta(
        base_version="v1", seq=3,
        coordinates={"perUser": CoordinateDelta(
            rows=np.asarray([4, 9, 2]),
            values=rng.normal(size=(3, D_U)),
            prior=rng.normal(size=(3, D_U)))},
        created_at=123.5)
    ddir = tmp_path / "delta"
    save_model_delta(delta, str(ddir))
    # durable layout: manifest.json written LAST vouches for completeness
    assert (ddir / "manifest.json").exists()
    assert (ddir / "delta.npz").exists()
    assert not list(ddir.glob("*.tmp*"))        # no torn temporaries
    loaded = load_model_delta(str(ddir))
    assert loaded.base_version == "v1" and loaded.seq == 3
    cd, lcd = delta.coordinates["perUser"], loaded.coordinates["perUser"]
    assert np.array_equal(cd.rows, lcd.rows)
    assert np.array_equal(cd.values, lcd.values)
    assert np.array_equal(cd.prior, lcd.prior)
    # a tampered file must be refused (manifest sha mismatch)
    (ddir / "delta.npz").write_bytes(b"corrupt")
    with pytest.raises(ValueError, match="manifest"):
        load_model_delta(str(ddir))
    # a directory without a completed write must be refused
    with pytest.raises(FileNotFoundError):
        load_model_delta(str(tmp_path / "nowhere"))


def test_delta_validation():
    with pytest.raises(ValueError, match="unique"):
        CoordinateDelta(rows=np.asarray([1, 1]), values=np.zeros((2, 3)),
                        prior=np.zeros((2, 3)))
    with pytest.raises(ValueError, match="at least one"):
        ModelDelta(base_version="v", seq=1, coordinates={})


# -- delta swaps, staleness, rollback ---------------------------------------

def test_stale_delta_refused_and_reenqueued(rng, monkeypatch):
    svc = _service(rng, updates=OnlineUpdateConfig(micro_batch=8))
    try:
        registry = svc.registry
        delta = ModelDelta(
            base_version="not-the-live-version", seq=1,
            coordinates={"perUser": CoordinateDelta(
                rows=np.asarray([0]), values=np.zeros((1, D_U)),
                prior=np.zeros((1, D_U)))})
        with pytest.raises(StaleDeltaError):
            registry.apply_delta(delta)
        # updater-level: a swap racing the publish re-enqueues the rows
        feats, ids, labels = _feedback(rng, 6)
        svc.feedback(feats, ids, labels)
        real_apply = registry.apply_delta
        calls = {"n": 0}

        def flaky_apply(d, publish_s=0.0):
            calls["n"] += 1
            if calls["n"] == 1:
                raise StaleDeltaError("simulated swap race")
            return real_apply(d, publish_s=publish_s)

        monkeypatch.setattr(registry, "apply_delta", flaky_apply)
        out1 = svc.updater.run_once()
        assert out1["deltas"] == 0          # first publish hit the race
        assert svc.updater.buffer.pending_rows > 0   # re-enqueued
        out2 = svc.updater.flush()
        assert out2["deltas"] >= 1          # re-solved and published
        assert svc.metrics_snapshot()["online"]["stale_deltas"] == 1
    finally:
        svc.close()


def test_rollback_interleaved_swaps_and_deltas_under_scoring(rng):
    """ISSUE 9 satellite: interleave full-model swaps, delta swaps and
    rollbacks while a scoring thread hammers the service — rollback after
    N delta swaps restores the exact pre-delta rows, and the full-model
    rollback still works beneath it.  Runs under the ARMED lock-order
    tracker (ISSUE 10): every acquisition order this concurrency test
    actually takes is validated against photonlint's static graph at the
    end."""
    tracker = locktrace.install()
    svc = _service(rng, updates=OnlineUpdateConfig(micro_batch=8))
    stop = threading.Event()
    errors = []

    def scorer_loop():
        r = np.random.default_rng(11)
        while not stop.is_set():
            feats, ids, _ = _feedback(r, 3)
            try:
                svc.score(feats, ids)
            except Exception as e:  # pragma: no cover - the assertion
                errors.append(f"{type(e).__name__}: {e}")

    t = threading.Thread(target=scorer_loop, daemon=True)
    t.start()
    try:
        v1 = svc.model_version
        table_v1 = np.asarray(svc.registry.scorer.re_table("perUser")).copy()
        # deltas on v1
        feats, ids, labels = _feedback(rng, 20)
        svc.feedback(feats, ids, labels)
        svc.updater.flush()
        assert svc.registry.pending_deltas() >= 1
        table_v1_deltas = np.asarray(
            svc.registry.scorer.re_table("perUser")).copy()
        assert not np.array_equal(table_v1_deltas, table_v1)
        # full swap to v2 (fresh random model), then deltas on v2
        from photon_ml_tpu.serving import CompiledScorer
        r2 = np.random.default_rng(123)
        scorer2 = CompiledScorer(_make_model(r2), max_batch=64, min_bucket=4)
        scorer2.warmup()
        svc.registry.install(scorer2, "v2")
        assert svc.registry.pending_deltas() == 0    # log belongs to v1
        table_v2 = np.asarray(scorer2.re_table("perUser")).copy()
        feats, ids, labels = _feedback(rng, 20)
        svc.feedback(feats, ids, labels)
        svc.updater.flush()
        n_deltas = svc.registry.pending_deltas()
        assert n_deltas >= 1
        assert svc.version_vector()["delta_seq"] >= 1
        # rollback 1: delta-aware — v2's exact pre-delta rows return
        assert svc.rollback() == "v2"
        assert np.array_equal(
            np.asarray(svc.registry.scorer.re_table("perUser")), table_v2)
        assert svc.registry.pending_deltas() == 0
        assert svc.version_vector() == {"version": "v2", "delta_seq": 0}
        # rollback 2: full-model — back to v1 AS LAST SERVED (its deltas
        # stayed in its tables when it was swapped out)
        assert svc.rollback() == v1
        assert np.array_equal(
            np.asarray(svc.registry.scorer.re_table("perUser")),
            table_v1_deltas)
    finally:
        stop.set()
        t.join(timeout=5)
        svc.close()
        locktrace.shutdown()
    assert errors == []
    # static/dynamic cross-validation: every lock order this test took
    # must be an edge of the static acquisition-order graph
    tracker.assert_consistent(lock_order_edges_cached())


PACKAGE_DIR = os.path.dirname(os.path.abspath(photon_ml_tpu.__file__))


def test_lock_tracker_stress_scoring_deltas_rollback(rng):
    """ISSUE 10 acceptance: sustained scorer traffic + delta publishes +
    full swap + delta-aware rollback under the ARMED lock tracker.  Every
    observed acquisition order must be an edge consistent with the static
    lock-order graph, and the serving metrics path must actually have
    been observed nesting (the test would silently prove nothing if no
    two locks ever nested)."""
    with locktrace.enabled() as tracker:
        svc = _service(rng, updates=OnlineUpdateConfig(micro_batch=8))
        stop = threading.Event()
        errors = []

        def scorer_loop(seed):
            r = np.random.default_rng(seed)
            while not stop.is_set():
                feats, ids, _ = _feedback(r, 3)
                try:
                    svc.score(feats, ids)
                except Exception as e:  # pragma: no cover
                    errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=scorer_loop, args=(s,),
                                    daemon=True) for s in (11, 13)]
        for t in threads:
            t.start()
        try:
            for _ in range(3):
                feats, ids, labels = _feedback(rng, 16)
                svc.feedback(feats, ids, labels)
                svc.updater.flush()
            assert svc.registry.pending_deltas() >= 1
            from photon_ml_tpu.serving import CompiledScorer
            scorer2 = CompiledScorer(_make_model(np.random.default_rng(7)),
                                     max_batch=64, min_bucket=4)
            scorer2.warmup()
            svc.registry.install(scorer2, "v2")
            feats, ids, labels = _feedback(rng, 16)
            svc.feedback(feats, ids, labels)
            svc.updater.flush()
            svc.rollback()          # delta-aware
            svc.rollback()          # full-model
            svc.metrics_snapshot()
            svc.prometheus_metrics()
            svc.updater.stats()
            svc.updater.frozen_entities()
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=5)
            svc.close()
    assert errors == []
    static = lock_order_edges_cached()
    tracker.assert_consistent(static)
    observed = set(tracker.edges())
    # the nesting the serving hot path is KNOWN to take — proves the
    # tracker saw real contention-relevant structure, not an empty run
    assert ("ServingMetrics._lock", "Counter._lock") in observed
    assert observed <= static
    acq = tracker.acquisitions()
    assert acq.get("ModelRegistry._lock", 0) > 0
    assert acq.get("FeedbackBuffer._lock", 0) > 0
    assert acq.get("OnlineUpdater._state_lock", 0) > 0
    assert acq.get("MicroBatcher._cv", 0) > 0


_STATIC_EDGES = None


def lock_order_edges_cached():
    """The package's static lock-order graph, computed once per test
    session (the interprocedural pass costs ~1s)."""
    global _STATIC_EDGES
    if _STATIC_EDGES is None:
        from photon_ml_tpu.analysis.concurrency import lock_order_edges
        _STATIC_EDGES = lock_order_edges([PACKAGE_DIR])
    return _STATIC_EDGES


def test_updater_start_close_race_spawns_one_thread(rng):
    """Regression for the PH013 check-then-act in OnlineUpdater.start():
    N racing start() calls must launch exactly ONE loop thread, and
    close() must join it without deadlocking (it joins OUTSIDE the state
    lock the loop thread takes — the PH012 hazard)."""
    svc = _service(rng, updates=OnlineUpdateConfig(micro_batch=8),
                   start_updater=False)
    try:
        before = {t.ident for t in threading.enumerate()}
        barrier = threading.Barrier(8)

        def racer():
            barrier.wait(timeout=5)
            svc.updater.start()

        racers = [threading.Thread(target=racer) for _ in range(8)]
        for t in racers:
            t.start()
        for t in racers:
            t.join(timeout=5)
        spawned = [t for t in threading.enumerate()
                   if t.ident not in before
                   and t.name == "photon-online-updater"]
        assert len(spawned) == 1
        svc.updater.close()
        assert not spawned[0].is_alive()
        # restartable after close
        svc.updater.start()
        svc.updater.close()
    finally:
        svc.close()


def test_close_during_warmup_ends_it_and_joins_the_thread(rng, monkeypatch):
    """A close() that lands while the loop thread is still warming up (a
    SIGTERM drain of `cli.serve` soon after start, on a loaded machine)
    stops the warm-up after the program in flight and returns only once
    the thread is gone.  It used to wait 5 s and let the process exit with
    the thread inside XLA: the drained child died by SIGABRT or SIGSEGV."""
    import time

    from photon_ml_tpu.online import updater as updater_mod
    calls = []
    entered = threading.Event()
    real = updater_mod.solve_anchored

    def slow_solve(*args, **kw):
        calls.append(1)
        entered.set()
        time.sleep(0.3)
        return real(*args, **kw)

    monkeypatch.setattr(updater_mod, "solve_anchored", slow_solve)
    svc = _service(rng, updates=OnlineUpdateConfig(
        micro_batch=8, min_rows_bucket=4, max_rows_per_entity=256),
        start_updater=False)
    try:
        svc.updater.start()
        thread = svc.updater._thread
        assert entered.wait(timeout=30)
        svc.updater.close()
        assert not thread.is_alive()
        # seven S-buckets (4..256) were due; the warm-up stopped after
        # the one in flight
        assert len(calls) == 1
        assert not svc.updater.warmed
    finally:
        svc.close()


def test_batcher_shed_callback_runs_outside_the_condition(rng):
    """Regression for the shed callback being invoked under _cv: a
    callback that itself touches the batcher (as ServingMetrics-style
    hooks legitimately may) must not deadlock."""
    from photon_ml_tpu.serving.batcher import BatcherConfig, MicroBatcher

    release = threading.Event()
    calls = []

    def slow_score(feats, ids, num_requests, queue_wait_s):
        release.wait(timeout=10)

        class R:
            scores = np.zeros(int(next(iter(feats.values())).shape[0]))
        return R()

    def on_shed():
        # re-enters the batcher: deadlocks if invoked while _cv is held
        calls.append(batcher.pending)

    batcher = MicroBatcher(slow_score,
                           BatcherConfig(max_wait_s=0.001, max_batch=4,
                                         max_queue=1),
                           on_shed=on_shed)
    try:
        import time as _time
        feats = {"global": np.zeros((1, D_G))}
        ids = {"userId": np.asarray(["u0"], dtype=object)}

        def submit():
            try:
                batcher.score(feats, ids, 1)
            except Exception:
                pass

        def wait_pending(n):
            deadline = _time.monotonic() + 5
            while _time.monotonic() < deadline:
                if batcher.pending == n:
                    return True
                _time.sleep(0.005)
            return False

        # 1st request: picked up by the worker, which blocks in score_fn
        threading.Thread(target=submit, daemon=True).start()
        assert wait_pending(0)
        # 2nd request fills the queue (max_queue=1) behind the stuck worker
        threading.Thread(target=submit, daemon=True).start()
        assert wait_pending(1)
        # 3rd request must shed IMMEDIATELY — and the callback re-enters
        # the batcher, which deadlocks if it ran under _cv
        with pytest.raises(Overloaded):
            batcher.score(feats, ids, 1)
        assert calls and all(isinstance(c, int) for c in calls)
    finally:
        release.set()
        batcher.close()


def test_disarmed_tracker_is_pass_through_on_live_service(rng):
    """ISSUE 10 acceptance (zero overhead disarmed): with no tracker
    installed the serving stack builds on RAW threading primitives — the
    module-global None check returns the lock unchanged, so the warm
    serve loop pays nothing (its zero-fresh-traces gate lives in
    test_zero_fresh_traces_warm_delta_stream)."""
    assert locktrace.active() is None
    svc = _service(rng)
    try:
        assert type(svc.registry._lock) is type(threading.Lock())
        assert isinstance(svc._batcher._cv, threading.Condition)
        assert not isinstance(svc.registry._lock, locktrace.TracedLock)
    finally:
        svc.close()


def test_delta_rollback_bit_exact_multiple_overlapping(rng):
    """Rows touched by SEVERAL deltas restore their original bits
    (newest-first revert)."""
    svc = _service(rng, updates=OnlineUpdateConfig(micro_batch=8))
    try:
        table0 = np.asarray(svc.registry.scorer.re_table("perUser")).copy()
        fixed_ids = np.asarray(["u0", "u1", "u2", "u0", "u1", "u2"],
                               dtype=object)
        for s in range(3):  # 3 deltas over the SAME rows
            r = np.random.default_rng(100 + s)
            feats, ids, labels = _feedback(r, 6, ids=fixed_ids)
            svc.feedback(feats, ids, labels)
            svc.updater.flush()
        assert svc.registry.pending_deltas() == 3
        svc.rollback()
        assert np.array_equal(
            np.asarray(svc.registry.scorer.re_table("perUser")), table0)
    finally:
        svc.close()


# -- compile-count regression (satellite) -----------------------------------

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


def test_zero_fresh_traces_warm_delta_stream(rng):
    """ISSUE 9 satellite: a WARM serve loop absorbing a stream of deltas
    while scoring causes zero fresh XLA traces — scorer buckets, the
    anchored batched solver, and the gather/scatter delta programs all
    stay cached."""
    svc = _service(rng, updates=OnlineUpdateConfig(
        micro_batch=4, max_rows_per_entity=8))
    try:
        svc.updater.warmup()

        def one_round(seed):
            r = np.random.default_rng(seed)
            feats, ids, labels = _feedback(r, 12)
            svc.feedback(feats, ids, labels)
            svc.updater.flush()
            f2, i2, _ = _feedback(r, 5)
            svc.score(f2, i2)

        one_round(0)  # device_put paths
        with _compile_counting() as counter:
            for s in range(1, 6):
                one_round(s)
        assert counter.count == 0, counter.messages
        assert svc.registry.scorer.deltas_applied >= 6
    finally:
        svc.close()


# -- fault containment (satellite) ------------------------------------------

def test_transient_solve_fault_retried(rng):
    svc = _service(rng, updates=OnlineUpdateConfig(micro_batch=8))
    try:
        feats, ids, labels = _feedback(rng, 8)
        svc.feedback(feats, ids, labels)
        plan = faults.FaultPlan([{"site": "online.solve",
                                  "action": "transient", "hits": [1]}])
        with faults.injected(plan):
            out = svc.updater.flush()
        assert out["deltas"] >= 1            # the retry absorbed the fault
        assert plan.report()["total_fired"] == 1
        snap = svc.metrics_snapshot()
        assert snap["online"]["solve_retries"] >= 1
        assert snap["online"]["deltas_published"] >= 1
    finally:
        svc.close()


def test_transient_publish_fault_retried(rng):
    svc = _service(rng, updates=OnlineUpdateConfig(micro_batch=8))
    try:
        feats, ids, labels = _feedback(rng, 8)
        svc.feedback(feats, ids, labels)
        plan = faults.FaultPlan([{"site": "online.publish",
                                  "action": "transient", "hits": [1]}])
        with faults.injected(plan):
            out = svc.updater.flush()
        assert out["deltas"] >= 1
        assert plan.report()["total_fired"] == 1
    finally:
        svc.close()


def test_nonfinite_solve_freezes_entity_not_table(rng):
    """ISSUE 9 satellite: a non-finite online solve FREEZES the entity —
    the live table row is untouched (scoring continues on the batch
    solution) and later feedback for the frozen entity is dropped."""
    svc = _service(rng, updates=OnlineUpdateConfig(micro_batch=4))
    try:
        table0 = np.asarray(svc.registry.scorer.re_table("perUser")).copy()
        feats, ids, labels = _feedback(
            rng, 4, ids=np.asarray(["u3", "u3", "u4", "u4"], dtype=object))
        svc.feedback(feats, ids, labels)
        plan = faults.FaultPlan([{"site": "online.solve",
                                  "action": "poison", "hits": [1]}])
        with faults.injected(plan):
            out = svc.updater.flush()
        assert out["deltas"] == 0            # nothing publishable survived
        # the live table is bit-identical: the poison never landed
        assert np.array_equal(
            np.asarray(svc.registry.scorer.re_table("perUser")), table0)
        frozen = svc.updater.frozen_entities()
        assert {e for _l, e in frozen} == {"u3", "u4"}
        # later feedback for a frozen entity is dropped and counted
        f2, i2, l2 = _feedback(rng, 2,
                               ids=np.asarray(["u3", "u5"], dtype=object))
        out2 = svc.feedback(f2, i2, l2)
        assert out2["dropped_frozen"] == 1 and out2["accepted"] == 1
        snap = svc.metrics_snapshot()
        assert snap["online"]["frozen_entities"] == 2
        # healthy entities keep updating
        assert svc.updater.flush()["deltas"] >= 1
    finally:
        svc.close()


def test_fatal_solve_fault_drops_batch_without_poisoning(rng):
    svc = _service(rng, updates=OnlineUpdateConfig(micro_batch=4))
    try:
        table0 = np.asarray(svc.registry.scorer.re_table("perUser")).copy()
        feats, ids, labels = _feedback(rng, 4)
        svc.feedback(feats, ids, labels)
        plan = faults.FaultPlan([{"site": "online.solve",
                                  "action": "fatal", "hits": [1]}])
        with faults.injected(plan):
            out = svc.updater.flush()
        assert out["deltas"] == 0
        assert np.array_equal(
            np.asarray(svc.registry.scorer.re_table("perUser")), table0)
        assert svc.metrics_snapshot()["online"]["solve_failures"] == 1
    finally:
        svc.close()


# -- metrics / observability -------------------------------------------------

def test_staleness_and_latency_surfaces(rng):
    svc = _service(rng, updates=OnlineUpdateConfig(micro_batch=8))
    try:
        snap0 = svc.metrics_snapshot()
        assert snap0["model_age_s"] >= 0.0
        assert snap0["online"]["feedback_to_publish_ms"] is None
        feats, ids, labels = _feedback(rng, 10)
        svc.feedback(feats, ids, labels)
        svc.updater.flush()
        snap = svc.metrics_snapshot()
        # a delta publish resets model age
        assert snap["model_age_s"] <= snap0["model_age_s"] + 0.5
        f2p = snap["online"]["feedback_to_publish_ms"]
        assert f2p is not None and f2p["p50"] >= 0.0 and \
            f2p["p99"] >= f2p["p50"]
        assert snap["version_vector"]["delta_seq"] >= 1
        # Prometheus text exposition carries the new surfaces
        text = svc.prometheus_metrics()
        assert "photon_serve_model_age_s" in text
        assert 'photon_online_feedback_to_publish_s{quantile="0.99"}' in text
        assert "photon_online_deltas_published_total" in text
    finally:
        svc.close()


def test_background_updater_end_to_end(rng):
    """The real deployment shape: background loop armed, feedback arrives,
    deltas land without any manual flush."""
    svc = _service(rng, updates=OnlineUpdateConfig(
        micro_batch=8, interval_s=0.01), start_updater=True)
    try:
        feats, ids, labels = _feedback(rng, 12)
        svc.feedback(feats, ids, labels)
        import time as _time
        deadline = _time.time() + 60
        while _time.time() < deadline:
            if svc.metrics_snapshot()["online"]["deltas_published"] >= 1 \
                    and svc.updater.buffer.pending_rows == 0:
                break
            _time.sleep(0.02)
        snap = svc.metrics_snapshot()
        assert snap["online"]["deltas_published"] >= 1
        assert snap["online"]["entities_updated"] >= 1
    finally:
        svc.close()


def test_feedback_requires_updates_enabled(rng):
    svc = _service(rng)   # no updates config
    try:
        feats, ids, labels = _feedback(rng, 2)
        with pytest.raises(RuntimeError, match="--enable-updates"):
            svc.feedback(feats, ids, labels)
    finally:
        svc.close()


def test_http_feedback_endpoint(rng):
    """cli.serve's POST /feedback and version-vector /healthz, against an
    in-thread HTTP server (no subprocess: the serve CLI's handler wiring
    is what is under test)."""
    import json as _json
    import time as _time
    import urllib.request

    from photon_ml_tpu.cli.serve import _make_http_server
    svc = _service(rng, updates=OnlineUpdateConfig(
        micro_batch=8, interval_s=0.01), start_updater=True)
    httpd = _make_http_server(svc, "127.0.0.1", 0)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever,
                         kwargs={"poll_interval": 0.05}, daemon=True)
    t.start()

    def post(path, body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            data=_json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req) as resp:
            return resp.status, _json.loads(resp.read())

    try:
        feats, ids, labels = _feedback(rng, 6)
        status, out = post("/feedback", {
            "features": {s: x.tolist() for s, x in feats.items()},
            "ids": {t_: v.tolist() for t_, v in ids.items()},
            "labels": labels.tolist(),
            "event_ids": [f"ev{i}" for i in range(6)]})
        assert status == 202
        assert out["accepted"] == 6
        assert "version_vector" in out
        # same event ids again: all deduped
        status, out2 = post("/feedback", {
            "features": {s: x.tolist() for s, x in feats.items()},
            "ids": {t_: v.tolist() for t_, v in ids.items()},
            "labels": labels.tolist(),
            "event_ids": [f"ev{i}" for i in range(6)]})
        assert status == 202 and out2["accepted"] == 0
        deadline = _time.time() + 60
        while _time.time() < deadline:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz") as resp:
                health = _json.loads(resp.read())
            if health["version_vector"]["delta_seq"] >= 1:
                break
            _time.sleep(0.02)
        assert health["updates_enabled"] is True
        assert health["version_vector"]["delta_seq"] >= 1
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics") as resp:
            text = resp.read().decode()
        assert "photon_serve_model_age_s" in text
    finally:
        httpd.shutdown()
        httpd.server_close()
        t.join(timeout=5)
        svc.close()
