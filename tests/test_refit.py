"""Continuous-training tier tests (photon_ml_tpu/refit/).

Covers the ISSUE 16 acceptance scenarios: f64 refit-from-log parity
(the log -> chunk -> dataset path produces the IDENTICAL fit as the same
rows in memory), the losing-candidate path (no swap, the incumbent keeps
serving), subprocess SIGKILL mid-compaction -> resume converges to
bit-identical chunk files, the durable feedback lane's torn-tail and
retention discipline, the trigger state machine (manual / interval /
on_trip with an injected clock), the refit.validate / refit.swap fault
sites, and the refit.* metrics surface.
"""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import photon_ml_tpu  # noqa: F401  (conftest configures the backend)

from photon_ml_tpu.fleet.replog import (FeedbackLog, feedback_from_record,
                                        record_for_feedback)
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.game import (FixedEffectModel, GameModel,
                                       RandomEffectModel)
from photon_ml_tpu.models.glm import model_for_task
from photon_ml_tpu.online import OnlineUpdateConfig
from photon_ml_tpu.refit import (CompactorConfig, LogCompactor, RefitConfig,
                                 RefitDriver, RefitError, RefitTrigger,
                                 TriggerConfig)
from photon_ml_tpu.serving import ScoringService, ServingConfig
from photon_ml_tpu.utils import faults

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
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


def _service(rng, tmp_path, **kw):
    kw.setdefault("config", ServingConfig(max_batch=64, min_bucket=4))
    kw.setdefault("updates", OnlineUpdateConfig(micro_batch=8))
    return ScoringService(model=_make_model(rng), start_updater=False,
                          feedback_log_dir=str(tmp_path / "fb"), **kw)


def _feedback(svc, rng, n, flip=False):
    """Labels drawn from the live model's own probabilities; `flip`
    inverts them (the label-flip drift the refit must learn)."""
    feats = {"global": rng.normal(size=(n, D_G)),
             "per_user": rng.normal(size=(n, D_U))}
    ids = {"userId": np.asarray(
        [f"u{rng.integers(0, N_ENT)}" for _ in range(n)], dtype=object)}
    z = svc.registry.scorer.score(feats, ids).scores
    p = 0.5 * (1.0 + np.tanh(0.5 * z))
    y = (rng.uniform(size=n) < p).astype(float)
    if flip:
        y = 1.0 - y
    return feats, ids, y


def _driver(svc, tmp_path, chunk_rows=64, **cfg_kw):
    comp = LogCompactor(svc.feedback_log, str(tmp_path / "chunks"),
                        CompactorConfig(chunk_rows=chunk_rows))
    svc.feedback_log.register_consumer("refit-compactor",
                                       comp.checkpoint_seq)
    cfg_kw.setdefault("outer_iterations", 1)
    cfg_kw.setdefault("fe_iterations", 15)
    cfg_kw.setdefault("re_iterations", 20)
    driver = RefitDriver(svc.registry, comp, str(tmp_path / "models"),
                         RefitConfig(**cfg_kw), metrics=svc.metrics)
    return driver, comp


# -- f64 refit-from-log parity ------------------------------------------------

def test_refit_from_log_f64_parity(rng, tmp_path):
    """A refit FROM THE LOG (append -> compact -> chunk files -> merged
    dataset) is the same fit as one from the identical rows in memory:
    the objective histories and final coefficients agree to <= 1e-6 in
    f64 (transport is raw-byte exact, so they are in fact identical)."""
    svc = _service(rng, tmp_path)
    try:
        batches = []
        for _ in range(5):
            f, i, y = _feedback(svc, rng, 32, flip=True)
            svc.feedback(f, i, y)
            batches.append((f, i, y))
        driver, comp = _driver(svc, tmp_path)
        m = comp.compact()
        assert m["sealed_rows"] == 128 and len(m["chunks"]) == 2

        fit_log = driver.fit_candidate(driver.gather_rows())
        n = 5 * 32
        rows_mem = {
            "features": {s: np.concatenate([b[0][s] for b in batches])
                         for s in batches[0][0]},
            "ids": {"userId": np.concatenate(
                [b[1]["userId"] for b in batches])},
            "labels": np.concatenate([b[2] for b in batches]),
            "weights": np.ones(n), "offsets": np.zeros(n),
            "wall": np.zeros(n)}
        fit_mem = driver.fit_candidate(rows_mem)

        hist_log = np.asarray(fit_log.objective_history, np.float64)
        hist_mem = np.asarray(fit_mem.objective_history, np.float64)
        assert hist_log.shape == hist_mem.shape and hist_log.size > 0
        np.testing.assert_allclose(hist_log, hist_mem, rtol=0, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(fit_log.model.coordinates["fixed"]
                       .glm.coefficients.means, np.float64),
            np.asarray(fit_mem.model.coordinates["fixed"]
                       .glm.coefficients.means, np.float64),
            rtol=0, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(fit_log.model.coordinates["perUser"].coefficients,
                       np.float64),
            np.asarray(fit_mem.model.coordinates["perUser"].coefficients,
                       np.float64),
            rtol=0, atol=1e-6)
    finally:
        svc.close()


# -- the cycle's verdicts -----------------------------------------------------

def test_winning_candidate_swaps_and_records_metrics(rng, tmp_path):
    svc = _service(rng, tmp_path)
    try:
        for _ in range(5):
            f, i, y = _feedback(svc, rng, 32, flip=True)
            svc.feedback(f, i, y)
        driver, _ = _driver(svc, tmp_path)
        before = svc.registry.version
        result = driver.run_once()
        assert result.swapped and result.version != before
        assert svc.registry.version == result.version
        assert result.candidate["loss"] < result.incumbent["loss"]
        assert os.path.isdir(str(tmp_path / "models" / result.version))
        snap = svc.metrics_snapshot()["refit"]
        assert snap["runs"] == 1 and snap["swaps"] == 1
        assert snap["failures"] == 0
        assert snap["last_success_age_s"] is not None
    finally:
        svc.close()


def test_serving_traces_nothing_on_either_side_of_a_refit_swap(rng,
                                                                tmp_path):
    """Scoring rounds before the cycle and against the freshly installed
    candidate trace nothing: install warms the candidate's bucket programs
    off the request path."""
    from test_pipeline import _compile_counting
    svc = _service(rng, tmp_path)
    try:
        for _ in range(5):
            f, i, y = _feedback(svc, rng, 32, flip=True)
            svc.feedback(f, i, y)
        driver, _ = _driver(svc, tmp_path)

        def score_round(seed):
            f, i, _ = _feedback(svc, np.random.default_rng(seed), 16)
            svc.score(f, i)

        for seed in range(2):                   # warm the bucket programs
            score_round(seed)
        with _compile_counting() as before:
            for seed in range(10, 13):
                score_round(seed)
        version = svc.registry.version
        assert driver.run_once().swapped
        assert svc.registry.version != version
        with _compile_counting() as after:
            for seed in range(20, 23):
                score_round(seed)
        assert (before.count, after.count) == (0, 0)
    finally:
        svc.close()


def test_drift_trip_refit_swap_recovery_closed_loop(rng, tmp_path):
    """The loop end to end on a live service: label-flip feedback trips
    the calibration gate and pauses the updater; the on-trip trigger runs
    a cycle whose candidate wins and swaps in; the swap resets every gate
    and resumes the updater; stationary traffic against the new model
    trips nothing."""
    from photon_ml_tpu.health import HealthConfig
    svc = _service(rng, tmp_path, health=HealthConfig(
        window_labels=64, window_scores=256, baseline_scores=256,
        sustain_windows=2, recovery_windows=2, calibration_p_min=1e-4,
        psi_max=None, ks_max=None))
    try:
        for _ in range(2):
            f, i, y = _feedback(svc, rng, 64)
            svc.feedback(f, i, y)
            svc.updater.flush()
        assert svc.healthz()["status"] == "ok"
        incumbent = svc.registry.version
        windows_to_trip = None
        for w in range(1, 8):
            f, i, y = _feedback(svc, rng, 64, flip=True)
            svc.feedback(f, i, y)
            if svc.healthz()["status"] == "degraded":
                windows_to_trip = w
                break
        assert windows_to_trip is not None and windows_to_trip <= 3
        assert svc.updater.paused

        driver, _ = _driver(svc, tmp_path)
        trigger = RefitTrigger(driver, health=svc.health,
                               config=TriggerConfig(mode="on_trip",
                                                    trip_polls=2,
                                                    cooloff_s=0.0))
        result = None
        for _ in range(4):
            result = trigger.poll()
            if result is not None:
                break
        assert result is not None and result.swapped
        assert result.version != incumbent
        assert result.candidate["loss"] < result.incumbent["loss"]
        verdict = svc.health.verdict()
        assert verdict["status"] == "ok"
        assert not verdict["updates_paused_by_health"]
        assert not any(g["tripped"] for g in verdict["gates"].values())
        assert not svc.updater.paused

        # the refit learnt the flipped labels: they are the stationary
        # traffic of the new model
        trips = svc.metrics_snapshot()["health"]["gate_trips"]
        for _ in range(3):
            f, i, y = _feedback(svc, rng, 64)
            svc.feedback(f, i, y)
            svc.updater.flush()
        assert svc.metrics_snapshot()["health"]["gate_trips"] == trips
        assert svc.healthz()["status"] == "ok"
        assert svc.metrics_snapshot()["refit"]["swaps"] == 1
    finally:
        svc.close()


def test_cli_refit_runs_one_cycle_from_a_feedback_lane(rng, tmp_path,
                                                       capsys):
    """`python -m photon_ml_tpu.cli.refit` as the batch job beside a
    serving process: it opens the lane the service wrote, compacts, refits
    and lands the winning candidate under --model-root."""
    from photon_ml_tpu.cli.refit import main as refit_main
    from photon_ml_tpu.models.io import save_game_model
    mdir = str(tmp_path / "incumbent")
    svc = _service(rng, tmp_path)
    try:
        save_game_model(svc.registry.scorer.model, mdir)
        for _ in range(5):
            f, i, y = _feedback(svc, rng, 32, flip=True)
            svc.feedback(f, i, y)
    finally:
        svc.close()
    rc = refit_main([
        "--model-dir", mdir, "--feedback-log", str(tmp_path / "fb"),
        "--chunks", str(tmp_path / "chunks"),
        "--model-root", str(tmp_path / "models"), "--chunk-rows", "64",
        "--outer-iterations", "1", "--fe-iterations", "15",
        "--re-iterations", "20"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["swapped"] is True
    assert os.listdir(str(tmp_path / "models")) == [line["version"]]


def test_losing_candidate_keeps_incumbent(rng, tmp_path):
    """An impossible win margin forces the loss: no swap, no version
    directory, the registry keeps serving the incumbent."""
    svc = _service(rng, tmp_path)
    try:
        for _ in range(5):
            f, i, y = _feedback(svc, rng, 32, flip=True)
            svc.feedback(f, i, y)
        driver, _ = _driver(svc, tmp_path, min_loss_improvement=1e6)
        before = svc.registry.version
        result = driver.run_once()
        assert not result.swapped
        assert "incumbent" in result.reason
        assert svc.registry.version == before
        models = str(tmp_path / "models")
        assert not os.path.isdir(models) or not os.listdir(models)
        snap = svc.metrics_snapshot()["refit"]
        assert snap["runs"] == 1 and snap["swaps"] == 0
    finally:
        svc.close()


def test_tail_only_refit_without_sealed_chunks(rng, tmp_path):
    """Fewer rows than one chunk: nothing seals, the refit still trains
    on the lane's unsealed tail."""
    svc = _service(rng, tmp_path)
    try:
        f, i, y = _feedback(svc, rng, 48, flip=True)
        svc.feedback(f, i, y)
        driver, comp = _driver(svc, tmp_path, chunk_rows=64)
        result = driver.run_once()
        assert comp.manifest()["sealed_rows"] == 0
        assert result.sealed_rows == 0 and result.tail_rows == 48
        assert result.swapped
    finally:
        svc.close()


def test_empty_lane_is_a_clean_noop(rng, tmp_path):
    svc = _service(rng, tmp_path)
    try:
        driver, _ = _driver(svc, tmp_path)
        result = driver.run_once()
        assert not result.swapped and "not enough" in result.reason
        assert svc.metrics_snapshot()["refit"]["failures"] == 0
    finally:
        svc.close()


# -- fault sites --------------------------------------------------------------

def test_validate_fatal_fault_raises_and_keeps_incumbent(rng, tmp_path):
    svc = _service(rng, tmp_path)
    try:
        for _ in range(3):
            f, i, y = _feedback(svc, rng, 32, flip=True)
            svc.feedback(f, i, y)
        driver, _ = _driver(svc, tmp_path)
        plan = faults.FaultPlan([{"site": "refit.validate",
                                  "action": "fatal", "hits": [1]}])
        before = svc.registry.version
        with faults.injected(plan):
            with pytest.raises(RefitError, match="validation"):
                driver.run_once()
        assert svc.registry.version == before
        snap = svc.metrics_snapshot()["refit"]
        assert snap["runs"] == 1 and snap["failures"] == 1
    finally:
        svc.close()


def test_swap_transient_fault_retried_to_success(rng, tmp_path):
    svc = _service(rng, tmp_path)
    try:
        for _ in range(3):
            f, i, y = _feedback(svc, rng, 32, flip=True)
            svc.feedback(f, i, y)
        driver, _ = _driver(svc, tmp_path, backoff_s=0.001)
        plan = faults.FaultPlan([{"site": "refit.swap",
                                  "action": "transient", "hits": [1]}])
        with faults.injected(plan):
            result = driver.run_once()
        assert result.swapped and svc.registry.version == result.version
    finally:
        svc.close()


# -- trigger state machine ----------------------------------------------------

class _FakeDriver:
    def __init__(self, fail=False):
        self.calls = 0
        self.fail = fail

    def run_once(self, version=None):
        self.calls += 1
        if self.fail:
            raise ValueError("boom")
        from photon_ml_tpu.refit.driver import RefitResult
        return RefitResult(swapped=True, version=f"v{self.calls}",
                           reason="ok", train_rows=1, holdout_rows=1,
                           sealed_rows=0, tail_rows=2, checkpoint_seq=0,
                           objective_history=[], candidate={},
                           incumbent={})


class _FakeHealth:
    degraded = False


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_trigger_manual_never_fires_on_poll():
    drv = _FakeDriver()
    trig = RefitTrigger(drv, config=TriggerConfig(mode="manual"))
    assert trig.poll() is None and drv.calls == 0
    result = trig.run_once()
    assert result.swapped and drv.calls == 1
    assert trig.state()["fires"] == 1 and trig.state()["swaps"] == 1


def test_trigger_interval_respects_spacing():
    drv, clock = _FakeDriver(), _Clock()
    trig = RefitTrigger(drv, config=TriggerConfig(mode="interval",
                                                  interval_s=100.0),
                        clock=clock)
    assert trig.poll() is not None          # first poll fires immediately
    clock.t = 50.0
    assert trig.poll() is None              # inside the interval
    clock.t = 100.0
    assert trig.poll() is not None
    assert drv.calls == 2
    assert trig.state()["last_reason"] == "interval"


def test_trigger_on_trip_debounces_and_cools_off():
    drv, clock, health = _FakeDriver(), _Clock(), _FakeHealth()
    trig = RefitTrigger(drv, health=health,
                        config=TriggerConfig(mode="on_trip", trip_polls=2,
                                             cooloff_s=60.0),
                        clock=clock)
    assert trig.poll() is None              # healthy
    health.degraded = True
    assert trig.poll() is None              # 1 degraded poll < trip_polls
    assert trig.poll() is not None          # sustained -> fires
    assert trig.state()["last_reason"] == "health_trip"
    # still degraded but inside the cooloff: de-bounce counts, no fire
    assert trig.poll() is None and trig.poll() is None
    clock.t = 60.0
    assert trig.poll() is not None          # cooled off -> fires again
    health.degraded = False
    trig.poll()
    assert trig.state()["degraded_polls"] == 0   # healthy poll resets
    assert drv.calls == 2


def test_trigger_on_trip_debounce_resets_on_healthy_poll():
    drv, health = _FakeDriver(), _FakeHealth()
    trig = RefitTrigger(drv, health=health,
                        config=TriggerConfig(mode="on_trip", trip_polls=2,
                                             cooloff_s=0.0),
                        clock=_Clock())
    health.degraded = True
    assert trig.poll() is None
    health.degraded = False
    assert trig.poll() is None              # resets the counter
    health.degraded = True
    assert trig.poll() is None              # back to 1, not 2
    assert drv.calls == 0


def test_trigger_records_cycle_errors_and_keeps_going():
    drv = _FakeDriver(fail=True)
    trig = RefitTrigger(drv, config=TriggerConfig(mode="interval",
                                                  interval_s=1.0),
                        clock=_Clock())
    assert trig.poll() is None              # the failure is swallowed
    assert drv.calls == 1
    state = trig.state()
    assert state["fires"] == 1 and "boom" in state["last_error"]


def test_trigger_on_trip_requires_health():
    with pytest.raises(ValueError, match="health"):
        RefitTrigger(_FakeDriver(),
                     config=TriggerConfig(mode="on_trip"))


def test_trigger_config_rejects_bad_modes():
    with pytest.raises(ValueError, match="mode"):
        TriggerConfig(mode="cron")
    with pytest.raises(ValueError):
        TriggerConfig(trip_polls=0)


# -- the durable feedback lane ------------------------------------------------

def test_feedback_record_round_trip_is_bit_exact(rng):
    feats = {"global": rng.normal(size=(7, D_G))}
    ids = {"userId": np.asarray([f"u{i}" for i in range(7)], dtype=object)}
    labels = rng.uniform(size=7)
    rec = record_for_feedback(feats, ids, labels, wall_s=123.0)
    back = feedback_from_record(rec)
    np.testing.assert_array_equal(back["features"]["global"],
                                  feats["global"])
    np.testing.assert_array_equal(back["labels"], labels)
    np.testing.assert_array_equal(back["ids"]["userId"], ids["userId"])
    assert back["wall_s"] == 123.0


def test_feedback_lane_truncates_torn_tail(rng, tmp_path):
    log = FeedbackLog(str(tmp_path / "fb"))
    for k in range(3):
        feats = {"global": rng.normal(size=(4, D_G))}
        ids = {"userId": np.asarray(["u1"] * 4, dtype=object)}
        log.append(record_for_feedback(feats, ids, np.ones(4), wall_s=k))
    seg = sorted(p for p in os.listdir(str(tmp_path / "fb"))
                 if p.startswith("feedback-") and p.endswith(".seg"))[-1]
    path = str(tmp_path / "fb" / seg)
    with open(path, "rb") as f:
        data = f.read()
    with open(path, "wb") as f:
        f.write(data[:-20])                # tear the newest record
    log2 = FeedbackLog(str(tmp_path / "fb"))
    assert log2.recover() > 0
    seqs = [int(env["log_seq"]) for env in log2.read(0)]
    assert seqs == [1, 2]                  # the torn record is gone


def test_feedback_retention_clamped_by_compactor_checkpoint(rng, tmp_path):
    log = FeedbackLog(str(tmp_path / "fb"), segment_records=1)
    for k in range(6):
        feats = {"global": rng.normal(size=(4, D_G))}
        ids = {"userId": np.asarray(["u1"] * 4, dtype=object)}
        log.append(record_for_feedback(feats, ids, np.ones(4),
                                       wall_s=float(k)))
    comp = LogCompactor(log, str(tmp_path / "chunks"),
                        CompactorConfig(chunk_rows=8))
    log.register_consumer("refit-compactor", comp.checkpoint_seq)
    # nothing sealed yet: the clamp pins retention at seq 0
    assert log.compact(6) is None or log.compact(6)["upto_seq"] == 0
    assert [int(e["log_seq"]) for e in log.read(0)] == list(range(1, 7))
    comp.compact()                          # seals 24 rows = seqs 1..6
    ckpt = comp.checkpoint_seq()
    assert ckpt >= 4
    before = log.live_records()
    snap = log.compact(10_000)              # still clamped to the ckpt
    assert snap is not None and snap["upto_seq"] == ckpt
    assert log.live_records() < before
    # every surviving row is still readable past the pruned horizon
    assert all(int(e["log_seq"]) > ckpt for e in log.read(ckpt))


# -- SIGKILL mid-compaction -> bit-identical resume ---------------------------

_CHILD = """\
import sys
sys.path.insert(0, {repo!r})
from photon_ml_tpu.utils import faults
faults.install_from_env()
from photon_ml_tpu.fleet.replog import FeedbackLog
from photon_ml_tpu.refit import CompactorConfig, LogCompactor
log = FeedbackLog({fb!r})
log.recover()
LogCompactor(log, {chunks!r}, CompactorConfig(chunk_rows=64)).compact()
print("OK")
"""


def _compact_child(tmp_path, chunks, plan=None, expect_kill=False):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PHOTON_FAULT_PLAN", None)
    if plan is not None:
        env["PHOTON_FAULT_PLAN"] = json.dumps(plan)
    code = _CHILD.format(repo=_REPO, fb=str(tmp_path / "fb"),
                         chunks=chunks)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=_REPO)
    if expect_kill:
        assert p.returncode == -9, (p.returncode, p.stderr[-500:])
        return None
    assert p.returncode == 0, p.stderr[-800:]
    return p.stdout


def test_sigkill_mid_compaction_resumes_bit_identical(rng, tmp_path):
    """SIGKILL at the refit.compact fault site while sealing chunk 1 ->
    a fresh process converges to chunk files BIT-IDENTICAL to an
    uninterrupted compaction of the same lane (sha-checked resume over
    the already-sealed prefix; deterministic replay of the rest)."""
    log = FeedbackLog(str(tmp_path / "fb"))
    for k in range(5):
        feats = {"global": rng.normal(size=(32, D_G)),
                 "per_user": rng.normal(size=(32, D_U))}
        ids = {"userId": np.asarray(
            [f"u{rng.integers(0, N_ENT)}" for _ in range(32)],
            dtype=object)}
        log.append(record_for_feedback(feats, ids, rng.uniform(size=32),
                                       wall_s=1000.0 + k))

    ref_dir = str(tmp_path / "chunks_ref")
    _compact_child(tmp_path, ref_dir)       # uninterrupted reference
    ref_chunks = sorted(p for p in os.listdir(ref_dir)
                        if p.startswith("chunk-"))
    assert len(ref_chunks) == 2             # 160 rows / 64 -> 2 sealed

    kill_dir = str(tmp_path / "chunks")
    plan = {"seed": 0, "faults": [{"site": "refit.compact",
                                   "action": "kill", "hits": [2]}]}
    _compact_child(tmp_path, kill_dir, plan=plan, expect_kill=True)
    # chunk 0 survived the kill; chunk 1 never sealed
    sealed = sorted(p for p in os.listdir(kill_dir)
                    if p.startswith("chunk-"))
    assert len(sealed) == 1

    _compact_child(tmp_path, kill_dir)      # resume, no faults
    for name in ref_chunks:
        with open(os.path.join(ref_dir, name), "rb") as f:
            want = f.read()
        with open(os.path.join(kill_dir, name), "rb") as f:
            got = f.read()
        assert got == want, f"{name} differs after kill+resume"
    with open(os.path.join(ref_dir, "manifest.json")) as f:
        want_m = json.load(f)
    with open(os.path.join(kill_dir, "manifest.json")) as f:
        got_m = json.load(f)
    assert got_m == want_m
