"""Model-health tier tests (photon_ml_tpu/health/).

Covers the ISSUE 11 acceptance scenarios: streaming-calibration parity
against the batch `diagnostics/hl.py` oracle on identical replayed
traffic (f64), the drift detector's false-positive bound on stationary
traffic, the health-gate -> pause -> resume -> rollback lifecycle under
concurrent scoring with the runtime lock tracker armed, metric-surface
parity between the Prometheus text and JSON snapshot (the SNAPSHOT_PATHS
contract), the compile-count regression (warm serve+update loop with
health armed traces NOTHING new), and the `health.evaluate` fault site.
"""
import logging
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import photon_ml_tpu  # noqa: F401  (conftest configures the backend)

from photon_ml_tpu.diagnostics.hl import hosmer_lemeshow
from photon_ml_tpu.health import (DriftDetector, HealthConfig, HealthMonitor,
                                  StreamingCalibration)
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.game import (FixedEffectModel, GameModel,
                                       RandomEffectModel)
from photon_ml_tpu.models.glm import model_for_task
from photon_ml_tpu.online import OnlineUpdateConfig
from photon_ml_tpu.serving import ScoringService, ServingConfig
from photon_ml_tpu.serving.metrics import SNAPSHOT_PATHS, ServingMetrics
from photon_ml_tpu.utils import faults, locktrace

D_G, D_U, N_ENT = 6, 4, 30
TASK = "logistic_regression"


def _make_model(rng, coef_scale=1.0):
    fe = FixedEffectModel(
        model_for_task(TASK, Coefficients(
            jnp.asarray(coef_scale * rng.normal(size=D_G)))), "global")
    re_ = RandomEffectModel(
        random_effect_type="userId", feature_shard="per_user",
        task_type=TASK,
        coefficients=jnp.asarray(coef_scale * rng.normal(size=(N_ENT, D_U))),
        entity_ids=np.asarray([f"u{i}" for i in range(N_ENT)], dtype=object),
        projection=None, global_dim=D_U)
    return GameModel({"fixed": fe, "perUser": re_}, TASK)


def _service(rng, *, health=None, updates=None, **kw):
    kw.setdefault("config", ServingConfig(max_batch=64, min_bucket=4))
    return ScoringService(model=_make_model(rng), health=health,
                          updates=updates, start_updater=False, **kw)


def _requests(rng, n, scale=1.0):
    feats = {"global": scale * rng.normal(size=(n, D_G)),
             "per_user": scale * rng.normal(size=(n, D_U))}
    ids = {"userId": np.asarray(
        [f"u{rng.integers(0, N_ENT)}" for _ in range(n)], dtype=object)}
    return feats, ids


def _calibrated_feedback(svc, rng, n, flip=False):
    """Labels drawn from the live model's own probabilities — perfectly
    calibrated by construction; `flip` inverts them (maximal
    miscalibration, the label-flip drift of the bench)."""
    feats, ids = _requests(rng, n)
    z = svc.registry.scorer.score(feats, ids).scores
    p = 0.5 * (1.0 + np.tanh(0.5 * z))
    y = (rng.uniform(size=n) < p).astype(float)
    if flip:
        y = 1.0 - y
    return feats, ids, y


# -- streaming calibration vs the batch oracle -------------------------------

def test_streaming_hl_matches_batch_oracle(rng):
    """ISSUE 11 satellite: identical replayed traffic through the
    streaming accumulator and through `diagnostics/hl.py` lands on the
    same chi^2 / p-value / per-bin counts in f64 (1e-12 — only float
    summation order differs)."""
    n, bins = 2000, 10
    p = rng.uniform(size=n)
    y = (rng.uniform(size=n) < p).astype(float)
    # dims such that the batch heuristic picks exactly `bins` bins:
    # by_data (~40 at n=2000) > bins, so min(by_data, dims+2) = dims+2
    report = hosmer_lemeshow(p, y, num_dimensions=bins - 2)
    assert report.degrees_of_freedom == bins - 2

    cal = StreamingCalibration(bins)
    for lo in range(0, n, 137):   # deliberately ragged chunking
        cal.update(p[lo:lo + 137], y[lo:lo + 137])
    win = cal.report()
    assert win.count == n
    assert win.chi_squared == pytest.approx(report.chi_squared, rel=1e-12)
    assert win.prob_at_chi_square == pytest.approx(
        report.prob_at_chi_square, rel=1e-12, abs=1e-15)
    assert win.p_value == pytest.approx(report.p_value, rel=1e-9, abs=1e-15)
    for b in range(bins):
        ref = report.bins[b]
        assert win.expected_pos[b] == pytest.approx(ref.expected_pos,
                                                    rel=1e-12, abs=1e-12)
        assert win.expected_neg[b] == pytest.approx(ref.expected_neg,
                                                    rel=1e-12, abs=1e-12)
        assert win.observed_pos[b] == ref.observed_pos
        assert win.observed_neg[b] == ref.observed_neg


def test_streaming_hl_chunking_invariant(rng):
    """Any chunking of the same stream produces the same verdict."""
    n = 1500
    p = rng.uniform(size=n)
    y = (rng.uniform(size=n) < 0.4).astype(float)
    outs = []
    for step in (1500, 251, 17):
        cal = StreamingCalibration(10)
        for lo in range(0, n, step):
            cal.update(p[lo:lo + step], y[lo:lo + step])
        outs.append(cal.take())
    assert outs[0].chi_squared == pytest.approx(outs[1].chi_squared,
                                                rel=1e-12)
    assert outs[1].chi_squared == pytest.approx(outs[2].chi_squared,
                                                rel=1e-12)
    # take() reset the accumulators: a fresh window starts at zero
    cal.update(p[:10], y[:10])
    assert cal.count == 10


def test_streaming_hl_flipped_labels_collapse_p_value(rng):
    n = 1000
    p = rng.uniform(size=n)
    y_cal = (rng.uniform(size=n) < p).astype(float)
    good, bad = StreamingCalibration(10), StreamingCalibration(10)
    good.update(p, y_cal)
    bad.update(p, 1.0 - y_cal)
    assert good.report().p_value > 1e-6
    assert bad.report().p_value < 1e-12


# -- drift detector -----------------------------------------------------------

def test_drift_stationary_false_positive_bound(rng):
    """ISSUE 11 satellite: 30 windows of stationary traffic stay well
    under the PSI/KS gates (the stationary leg of the bench gates the
    full service path; this bounds the detector itself)."""
    det = DriftDetector(bins=10, baseline_size=2048)
    det.observe(rng.normal(size=2048))
    assert det.baseline_ready
    worst_psi = worst_ks = 0.0
    for _ in range(30):
        det.observe(rng.normal(size=2048))
        win = det.take()
        worst_psi = max(worst_psi, win.psi)
        worst_ks = max(worst_ks, win.ks)
    assert worst_psi < 0.25, worst_psi
    assert worst_ks < 0.2, worst_ks


def test_drift_detects_covariate_shift(rng):
    det = DriftDetector(bins=10, baseline_size=2048)
    det.observe(rng.normal(size=2048))
    det.observe(1.5 + 1.2 * rng.normal(size=2048))   # shifted + widened
    win = det.take()
    assert win.psi > 0.25
    assert win.ks > 0.2


def test_drift_baseline_not_ready_yields_no_window(rng):
    det = DriftDetector(bins=10, baseline_size=256)
    det.observe(rng.normal(size=100))
    assert not det.baseline_ready
    assert det.take() is None
    det.observe(rng.normal(size=200))    # crosses the threshold mid-batch
    assert det.baseline_ready
    assert det.window_count == 44        # 300 - 256 landed in the window


# -- config -------------------------------------------------------------------

def test_health_config_roundtrip_and_validation():
    cfg = HealthConfig(window_labels=64, rollback_on=("calibration",),
                       psi_max=0.3)
    again = HealthConfig.from_dict(cfg.to_dict())
    assert again == cfg
    with pytest.raises(ValueError, match="unknown key"):
        HealthConfig.from_dict({"psi_threshold": 0.3})
    with pytest.raises(ValueError, match="unknown gate"):
        HealthConfig(rollback_on=("nonsense",))
    with pytest.raises(ValueError, match="must be >= 1"):
        HealthConfig(window_labels=0)


def test_serve_cli_health_config_flag(tmp_path):
    from photon_ml_tpu.cli.serve import build_parser
    args = build_parser().parse_args(
        ["--model-dir", "m", "--health-config", '{"psi_max": 0.5}'])
    from photon_ml_tpu.cli.train import _load_json_arg
    cfg = HealthConfig.from_dict(_load_json_arg(args.health_config))
    assert cfg.psi_max == 0.5
    f = tmp_path / "health.json"
    f.write_text('{"window_labels": 99}')
    cfg2 = HealthConfig.from_dict(_load_json_arg("@" + str(f)))
    assert cfg2.window_labels == 99


# -- the gate lifecycle -------------------------------------------------------

def _lifecycle_config(**kw):
    kw.setdefault("window_labels", 64)
    kw.setdefault("window_scores", 128)
    kw.setdefault("baseline_scores", 128)
    kw.setdefault("sustain_windows", 2)
    kw.setdefault("recovery_windows", 2)
    kw.setdefault("calibration_p_min", 1e-4)
    # drift gates off: the tiny windows this test uses would trip them
    # on sampling noise (the detector's own bound is tested above)
    kw.setdefault("psi_max", None)
    kw.setdefault("ks_max", None)
    return HealthConfig(**kw)


def test_health_gate_pause_resume_rollback_lifecycle(rng):
    """ISSUE 11 acceptance: a sustained calibration breach pauses the
    updater, flips /healthz to degraded, and (rollback_on) restores the
    pre-delta rows — all without disturbing concurrent scoring; sustained
    recovery resumes updates.  The runtime lock tracker is ARMED for the
    whole lifecycle and cross-validated against the static graph."""
    from photon_ml_tpu.analysis.concurrency import lock_order_edges
    import os
    pkg_dir = os.path.dirname(os.path.abspath(photon_ml_tpu.__file__))
    with locktrace.enabled() as tracker:
        svc = _service(rng, updates=OnlineUpdateConfig(micro_batch=8),
                       health=_lifecycle_config(
                           rollback_on=("calibration",)))
        stop = threading.Event()
        errors = []

        def scorer_loop(seed):
            r = np.random.default_rng(seed)
            while not stop.is_set():
                feats, ids = _requests(r, 3)
                try:
                    svc.score(feats, ids)
                except Exception as e:  # pragma: no cover
                    errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=scorer_loop, args=(s,),
                                    daemon=True) for s in (11, 13)]
        for t in threads:
            t.start()
        try:
            table0 = np.asarray(
                svc.registry.scorer.re_table("perUser")).copy()
            # phase 1: calibrated feedback -> deltas publish, all gates ok
            for s in range(2):
                f, i, y = _calibrated_feedback(
                    svc, np.random.default_rng(20 + s), 64)
                svc.feedback(f, i, y)
                svc.updater.flush()
            assert svc.registry.pending_deltas() >= 1
            assert svc.healthz()["status"] == "ok"
            assert not svc.updater.paused
            # phase 2: label-flip -> 2 consecutive breaches trip the gate
            for s in range(2):
                f, i, y = _calibrated_feedback(
                    svc, np.random.default_rng(30 + s), 64, flip=True)
                svc.feedback(f, i, y)
            hz = svc.healthz()
            assert hz["status"] == "degraded"
            assert hz["health"]["gates"]["calibration"]["tripped"] is True
            assert svc.updater.paused
            assert "health" in (svc.updater.pause_reason or "")
            # the rollback restored the exact pre-delta rows
            assert svc.registry.pending_deltas() == 0
            assert np.array_equal(
                np.asarray(svc.registry.scorer.re_table("perUser")), table0)
            assert hz["health"]["rollbacks"] == 1
            # paused updater buffers but does not publish
            f, i, y = _calibrated_feedback(svc, np.random.default_rng(40),
                                           32)
            svc.feedback(f, i, y)
            assert svc.updater.flush()["deltas"] == 0
            # phase 3: clean windows -> recovery resumes updates
            for s in range(2):
                f, i, y = _calibrated_feedback(
                    svc, np.random.default_rng(50 + s), 64)
                svc.feedback(f, i, y)
            assert svc.healthz()["status"] == "ok"
            assert not svc.updater.paused
            assert svc.updater.flush()["deltas"] >= 1
            snap = svc.metrics_snapshot()
            assert snap["health"]["gate_trips"] >= 1
            assert snap["health"]["recoveries"] >= 1
            assert snap["health"]["rollbacks"] == 1
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=5)
            svc.close()
            locktrace.shutdown()
    assert errors == []
    tracker.assert_consistent(lock_order_edges([pkg_dir]))
    assert tracker.acquisitions().get("HealthMonitor._lock", 0) > 0


def test_gate_trip_dumps_a_flight_bundle_holding_its_window(rng, tmp_path):
    """A health-gate trip dumps the flight ring: the bundle on disk holds
    the `health_gate_tripped` event and the `health_evaluate` spans that
    led to it, before any operator attaches."""
    import json
    import os

    from photon_ml_tpu import telemetry
    from photon_ml_tpu.telemetry import flight
    dumps = str(tmp_path / "dumps")
    with telemetry.enabled(watch_compiles=False):
        with flight.enabled(dumps, proc="serve"):
            svc = _service(rng, updates=OnlineUpdateConfig(micro_batch=8),
                           health=_lifecycle_config())
            try:
                for s in range(2):
                    f, i, y = _calibrated_feedback(
                        svc, np.random.default_rng(20 + s), 64)
                    svc.feedback(f, i, y)
                    svc.updater.flush()
                for s in range(2):
                    f, i, y = _calibrated_feedback(
                        svc, np.random.default_rng(30 + s), 64, flip=True)
                    svc.feedback(f, i, y)
                assert svc.metrics_snapshot()["health"]["gate_trips"] == 1
            finally:
                svc.close()
    bundles = [json.load(open(os.path.join(dumps, name)))
               for name in sorted(os.listdir(dumps))]
    tripped = [b for b in bundles if b["reason"] == "health.gate_trip"]
    assert len(tripped) == 1
    records = tripped[0]["records"]
    assert any(r.get("name") == "health_gate_tripped" for r in records)
    assert any(r.get("kind") == "span" and r.get("name") == "health_evaluate"
               for r in records)


def test_drift_gate_trips_without_labels(rng):
    """Covariate shift is detected from scores alone (no feedback, no
    updater): PSI/KS gates run on pure scoring traffic."""
    svc = _service(rng, health=HealthConfig(
        window_scores=256, baseline_scores=256, sustain_windows=2,
        calibration_p_min=None, psi_max=0.25, ks_max=0.2))
    try:
        r = np.random.default_rng(3)
        for _ in range(3):   # baseline + one clean window
            f, i = _requests(r, 128)
            svc.score(f, i)
        assert svc.health.verdict()["baseline_ready"]
        assert svc.healthz()["status"] == "ok"
        windows_before = svc.health.verdict()["windows_evaluated"]
        tripped_after = None
        for w in range(6):
            for _ in range(2):
                f, i = _requests(r, 128, scale=3.0)   # shifted traffic
                svc.score(f, i)
            if svc.healthz()["status"] == "degraded":
                tripped_after = (svc.health.verdict()["windows_evaluated"]
                                 - windows_before)
                break
        assert tripped_after is not None and tripped_after <= 3
        gates = svc.healthz()["health"]["gates"]
        assert gates["drift_psi"]["tripped"] or gates["drift_ks"]["tripped"]
    finally:
        svc.close()


def test_baseline_resets_on_swap_carried_across_deltas(rng):
    """ISSUE 11 tentpole semantics: the drift baseline belongs to the
    installed full model — a delta publish keeps it, a full swap resets
    it (and clears gate state / resumes a health-paused updater)."""
    from photon_ml_tpu.serving import CompiledScorer
    svc = _service(rng, updates=OnlineUpdateConfig(micro_batch=8),
                   health=_lifecycle_config(sustain_windows=1))
    try:
        r = np.random.default_rng(5)
        f, i = _requests(r, 128)
        svc.score(f, i)   # fills baseline (128)
        assert svc.health.verdict()["baseline_ready"]
        # delta publish: baseline carried
        f, i, y = _calibrated_feedback(svc, r, 32)
        svc.feedback(f, i, y)
        svc.updater.flush()
        assert svc.registry.pending_deltas() >= 1
        assert svc.health.verdict()["baseline_ready"]
        # trip the calibration gate, then swap a new full model in
        f, i, y = _calibrated_feedback(svc, r, 64, flip=True)
        svc.feedback(f, i, y)
        assert svc.healthz()["status"] == "degraded"
        assert svc.updater.paused
        scorer2 = CompiledScorer(_make_model(np.random.default_rng(7)),
                                 max_batch=64, min_bucket=4)
        scorer2.warmup()
        svc.registry.install(scorer2, "v2")
        v = svc.health.verdict()
        assert v["status"] == "ok"                 # fresh start
        assert v["model_version"] == "v2"
        assert not v["baseline_ready"]             # re-collecting
        assert not svc.updater.paused              # health pause released
    finally:
        svc.close()


def test_pause_landing_mid_cycle_requeues_instead_of_publishing(rng):
    """A pause that lands between drain and publish (the health monitor
    pausing from another thread while a cycle is in flight) must NOT
    publish rows solved against the pre-pause state — they requeue and
    re-solve after recovery."""
    svc = _service(rng, updates=OnlineUpdateConfig(micro_batch=8))
    try:
        f, i, y = _calibrated_feedback(svc, rng, 8)
        svc.feedback(f, i, y)
        drained = svc.updater.buffer.drain("perUser", 8)
        assert drained
        table0 = np.asarray(svc.registry.scorer.re_table("perUser")).copy()
        svc.updater.pause(reason="mid-cycle")
        out = svc.updater._solve_and_publish(svc.registry.scorer, "perUser",
                                             "per_user", drained)
        assert out is None
        assert svc.registry.pending_deltas() == 0
        assert np.array_equal(
            np.asarray(svc.registry.scorer.re_table("perUser")), table0)
        svc.updater.resume()
        assert svc.updater.flush()["deltas"] >= 1   # requeued rows drain
    finally:
        svc.close()


def test_updater_pause_resume_buffering(rng):
    svc = _service(rng, updates=OnlineUpdateConfig(micro_batch=8))
    try:
        svc.updater.pause(reason="operator")
        f, i, y = _calibrated_feedback(svc, rng, 16)
        out = svc.feedback(f, i, y)
        assert out["accepted"] > 0                 # intake keeps working
        assert svc.updater.flush()["deltas"] == 0  # but nothing publishes
        assert svc.updater.stats()["paused"] is True
        svc.updater.resume()
        assert svc.updater.flush()["deltas"] >= 1  # buffered rows drain
        assert svc.updater.last_cycle_age_s() is not None
    finally:
        svc.close()


# -- /healthz detail (satellite) ---------------------------------------------

def test_healthz_updater_vitals_and_gate_detail(rng):
    svc = _service(rng, updates=OnlineUpdateConfig(micro_batch=8),
                   health=_lifecycle_config())
    try:
        f, i, y = _calibrated_feedback(svc, rng, 16)
        svc.feedback(f, i, y)
        svc.updater.flush()
        hz = svc.healthz()
        assert hz["status"] == "ok"
        assert hz["updates_enabled"] and hz["health_enabled"]
        up = hz["updater"]
        assert up["alive"] is False        # manual driving: no loop thread
        assert up["frozen"] == 0
        assert up["paused"] is False
        assert up["last_cycle_age_s"] >= 0.0
        assert up["pending_rows"] == 0
        gates = hz["health"]["gates"]
        assert set(gates) == {"calibration", "drift_psi", "drift_ks", "auc",
                              "loss", "delta_l2", "freeze_rate"}
        for g in gates.values():
            assert {"threshold", "value", "breaches", "tripped", "windows",
                    "trips"} <= set(g)
    finally:
        svc.close()


def test_healthz_without_updates_or_health(rng):
    svc = _service(rng)
    try:
        hz = svc.healthz()
        assert hz["status"] == "ok"
        assert "updater" not in hz and "health" not in hz
    finally:
        svc.close()


# -- metric-surface parity (satellite) ---------------------------------------

def _flatten_paths(d, prefix=()):
    out = set()
    for k, v in d.items():
        out.add(prefix + (k,))
        if isinstance(v, dict):
            out |= _flatten_paths(v, prefix + (k,))
    return out


def test_metric_surface_parity_prometheus_vs_json():
    """ISSUE 11 satellite: the Prometheus text and the JSON snapshot
    expose the SAME metric set — every registered instrument has a
    declared JSON path (SNAPSHOT_PATHS), every path resolves in a
    rendered snapshot, and every instrument renders in the text
    exposition.  A metric added to one surface only fails here."""
    m = ServingMetrics()
    names = set(m.registry.names())
    assert names == set(SNAPSHOT_PATHS), (
        "every ServingMetrics instrument needs a SNAPSHOT_PATHS entry "
        f"(missing: {sorted(names - set(SNAPSHOT_PATHS))}, stale: "
        f"{sorted(set(SNAPSHOT_PATHS) - names)})")
    snap = m.snapshot()
    paths = _flatten_paths(snap)
    for name, path in SNAPSHOT_PATHS.items():
        assert path in paths, (f"instrument {name!r} declares JSON path "
                               f"{path} but snapshot() has no such key")
    reg = m.registry.snapshot()
    prom = m.prometheus()
    prom_series = set(re.findall(r"^photon_[a-zA-Z0-9_]+", prom,
                                 flags=re.M))
    clean = lambda n: "photon_" + re.sub(r"[^a-zA-Z0-9_]", "_", n)
    for name in reg["counters"]:
        assert clean(name) + "_total" in prom_series, name
    for name in reg["gauges"]:
        assert clean(name) in prom_series, name
    for name in reg["histograms"]:
        assert clean(name) in prom_series, name


def test_refresh_semantics_match_on_both_render_paths():
    """model_age_s and the updater-vitals gauges refresh at RENDER on
    both surfaces (a scrape and a snapshot can never disagree about
    staleness because one path forgot the refresh)."""
    m = ServingMetrics()
    vitals = {"frozen": 3, "alive": True, "paused": False,
              "last_cycle_age_s": 1.5}
    m.set_online_probe(lambda: dict(vitals))
    snap = m.snapshot()
    assert snap["online"]["frozen_entities"] == 3
    assert snap["online"]["updater_alive"] == 1
    assert snap["online"]["last_cycle_age_s"] == 1.5
    vitals.update(frozen=7, last_cycle_age_s=9.25, alive=False)
    prom = m.prometheus()
    assert "photon_online_frozen_entities 7" in prom
    assert "photon_online_last_cycle_age_s 9.25" in prom
    assert "photon_online_updater_alive 0" in prom
    # age before the first cycle renders as the -1 sentinel
    vitals["last_cycle_age_s"] = None
    assert m.snapshot()["online"]["last_cycle_age_s"] == -1.0


# -- fault site ---------------------------------------------------------------

def test_health_evaluate_transient_fault_skips_window(rng):
    svc = _service(rng, updates=OnlineUpdateConfig(micro_batch=8),
                   health=_lifecycle_config(sustain_windows=1))
    try:
        plan = faults.FaultPlan([{"site": "health.evaluate",
                                  "action": "transient", "hits": [1]}])
        with faults.injected(plan):
            f, i, y = _calibrated_feedback(svc, rng, 64, flip=True)
            svc.feedback(f, i, y)   # window closes, evaluation faulted
        assert plan.report()["total_fired"] == 1
        v = svc.health.verdict()
        assert v["windows_skipped"] == 1
        assert v["status"] == "ok"          # the verdict was dropped
        assert svc.metrics_snapshot()["health"]["evaluate_skipped"] == 1
        # the next window evaluates normally
        f, i, y = _calibrated_feedback(svc, rng, 64, flip=True)
        svc.feedback(f, i, y)
        assert svc.healthz()["status"] == "degraded"
    finally:
        svc.close()


# -- compile-count regression (satellite) ------------------------------------

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


def test_zero_fresh_traces_warm_serve_update_loop_health_armed(rng):
    """ISSUE 11 satellite: a WARM serve+update loop with health ARMED —
    including drift and calibration WINDOW CLOSES inside the counted
    region — traces nothing new.  All health accumulation and evaluation
    is host numpy/scipy; the only device programs involved are the
    already-warm scorer buckets."""
    svc = _service(rng, updates=OnlineUpdateConfig(
        micro_batch=4, max_rows_per_entity=8),
        health=HealthConfig(window_labels=16, window_scores=32,
                            baseline_scores=32, sustain_windows=100))
    try:
        svc.updater.warmup()

        def one_round(seed):
            r = np.random.default_rng(seed)
            f, i, y = _calibrated_feedback(svc, r, 16)  # closes a window
            svc.feedback(f, i, y)
            svc.updater.flush()
            f2, i2 = _requests(r, 32)                   # closes a window
            svc.score(f2, i2)

        one_round(0)
        one_round(1)   # baseline complete + first windows evaluated
        before = svc.metrics_snapshot()["health"]
        assert before["label_windows"] >= 1
        with _compile_counting() as counter:
            for s in range(2, 8):
                one_round(s)
        after = svc.metrics_snapshot()["health"]
        # windows really closed (and evaluated) inside the counted region
        assert after["label_windows"] >= before["label_windows"] + 6
        assert after["score_windows"] > before["score_windows"]
        assert counter.count == 0, counter.messages
        assert svc.registry.scorer.deltas_applied >= 6
    finally:
        svc.close()
