"""Fault-contained GAME training (ISSUE 5): the fault-injection registry,
streaming retry/backoff, crash-safe manifest checkpoints + verified
fallback, graceful preemption, and the non-finite solve quarantine."""
import json
import logging
import os
import subprocess
import sys

import numpy as np
import pytest

from photon_ml_tpu.data.game_data import build_game_dataset
from photon_ml_tpu.data.streaming import (ChunkPlan, ChunkStagingError,
                                          Prefetcher)
from photon_ml_tpu.game import (
    FixedEffectCoordinateConfig, GameEstimator, GameTrainingConfig,
    GLMOptimizationConfig, RandomEffectCoordinateConfig,
)
from photon_ml_tpu.game.coordinate_descent import (read_checkpoint,
                                                   verify_checkpoint_dir)
from photon_ml_tpu.optim import (OptimizerConfig, RegularizationContext,
                                 RegularizationType)
from photon_ml_tpu.utils import faults

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
L2 = RegularizationContext(RegularizationType.L2)


@pytest.fixture(autouse=True)
def _clean_fault_state():
    """No fault plan or preemption flag leaks between tests."""
    faults.install_plan(None)
    faults.clear_preemption()
    yield
    faults.install_plan(None)
    faults.clear_preemption()


def _glmix(rng, n=900, n_users=30):
    xg = rng.normal(size=(n, 8)); xg[:, -1] = 1.0
    xu = rng.normal(size=(n, 4)); xu[:, -1] = 1.0
    users = np.arange(n) % n_users
    z = xg @ rng.normal(size=8)
    y = (rng.uniform(size=n) < 1 / (1 + np.exp(-z))).astype(float)
    return build_game_dataset(
        y, {"g": xg, "u": xu},
        entity_ids={"userId": np.asarray([f"u{i:04d}" for i in users])})


def _opt(iters=15):
    return GLMOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=iters),
        regularization=L2, regularization_weight=1.0)


def _config(iters=3, coords=("fixed", "perUser")):
    cmap = {}
    if "fixed" in coords:
        cmap["fixed"] = FixedEffectCoordinateConfig("g", _opt())
    if "perUser" in coords:
        cmap["perUser"] = RandomEffectCoordinateConfig(
            "userId", "u", _opt(), projector="identity")
    return GameTrainingConfig(task_type="logistic_regression",
                              coordinates=cmap,
                              updating_sequence=list(coords),
                              num_outer_iterations=iters)


# --------------------------------------------------------------------------
# FaultPlan registry
# --------------------------------------------------------------------------

def test_fault_plan_hits_and_match():
    plan = faults.FaultPlan([
        {"site": "solve.poison", "action": "transient", "hits": [2],
         "match": {"coordinate": "a"}}])
    with faults.injected(plan):
        assert faults.fire("solve.poison", coordinate="b",
                           iteration=0) is None  # no match
        assert faults.fire("solve.poison", coordinate="a",
                           iteration=0) is None  # hit 1
        with pytest.raises(faults.TransientFault):
            faults.fire("solve.poison", coordinate="a",
                        iteration=1)             # hit 2 fires
        assert faults.fire("solve.poison", coordinate="a",
                           iteration=2) is None  # hit 3
    rep = plan.report()
    assert rep["sites"]["solve.poison"] == {"calls": 3, "fired": 1}
    assert rep["total_fired"] == 1


def test_fault_plan_probability_is_seeded():
    def fires(seed):
        plan = faults.FaultPlan(
            [{"site": "stage.fetch", "probability": 0.5,
              "max_fires": 100}], seed=seed)
        out = []
        for i in range(50):
            try:
                plan.fire("stage.fetch", chunk=i)
                out.append(False)
            except faults.TransientFault:
                out.append(True)
        return out
    assert fires(7) == fires(7)          # deterministic per seed
    assert any(fires(7)) and not all(fires(7))


def test_fault_plan_json_round_trip_and_env(monkeypatch):
    plan = faults.FaultPlan([{"site": "model.save", "action": "fatal",
                              "hits": [1]}], seed=3)
    monkeypatch.setenv("PHOTON_FAULT_PLAN", json.dumps(plan.to_dict()))
    installed = faults.install_from_env()
    assert installed is not None and faults.active_plan() is installed
    assert installed.to_dict() == plan.to_dict()
    with pytest.raises(faults.FatalFault):
        faults.fire("model.save", directory="x")


def test_fire_without_plan_is_noop():
    assert faults.active_plan() is None
    assert faults.fire("stage.fetch", chunk=1) is None


def test_transient_classification():
    assert faults.is_transient(OSError("flaky disk"))
    assert faults.is_transient(TimeoutError())
    assert faults.is_transient(faults.TransientFault("x"))
    assert not faults.is_transient(faults.FatalFault("x"))
    assert not faults.is_transient(KeyboardInterrupt())
    assert not faults.is_transient(SystemExit())
    assert not faults.is_transient(MemoryError())
    assert not faults.is_transient(ValueError("bug"))


def test_unknown_action_rejected():
    with pytest.raises(ValueError, match="unknown fault action"):
        faults.FaultPlan([{"site": "stage.fetch", "action": "explode",
                           "hits": [1]}])
    with pytest.raises(ValueError, match="never fires"):
        faults.FaultPlan([{"site": "stage.fetch"}])


def test_unknown_site_rejected_at_install_time():
    # a typo'd site would arm a fault that silently never fires — the
    # registry (utils.faults.SITES) rejects it up front, by name
    with pytest.raises(ValueError, match="unknown fault site 'stage.ftch'"):
        faults.FaultPlan([{"site": "stage.ftch", "hits": [1]}])
    with pytest.raises(ValueError, match="stage.fetch"):  # helpful listing
        faults.FaultPlan([{"site": "nope", "hits": [1]}])


def test_unknown_match_key_rejected_at_install_time():
    with pytest.raises(ValueError, match=r"unknown context key\(s\) "
                                         r"\['chunk_index'\]"):
        faults.FaultPlan([{"site": "stage.fetch", "hits": [1],
                           "match": {"chunk_index": 3}}])


def test_match_key_missing_from_fire_context_is_an_error():
    # the site declares the key but the fire() call didn't pass it: that
    # is a real bug at the site, not a silent no-match
    plan = faults.FaultPlan([{"site": "solve.poison", "hits": [1],
                              "match": {"coordinate": "a"}}])
    with faults.injected(plan):
        with pytest.raises(ValueError, match="did not pass"):
            faults.fire("solve.poison", iteration=0)


# --------------------------------------------------------------------------
# Prefetcher retry / backoff / classification (tentpole part 3 + satellite)
# --------------------------------------------------------------------------

def _flaky_fetch(fail_on, kind=OSError, times=1):
    failures = {}

    def fetch(spec):
        if spec.index in fail_on and failures.get(spec.index, 0) < times:
            failures[spec.index] = failures.get(spec.index, 0) + 1
            raise kind(f"flaky chunk {spec.index}")
        return {"v": np.full(spec.padded_rows, float(spec.index))}
    return fetch


def test_prefetcher_retries_transient_and_counts():
    plan = ChunkPlan.build(2048, chunk_rows=256)
    pf = Prefetcher(plan, _flaky_fetch({1, 3, 5}), backoff_s=0.001)
    chunks = list(pf.stream())
    assert len(chunks) == plan.num_chunks
    snap = pf.stats.snapshot()
    assert snap["retries"] == 3 and snap["gave_up"] == 0
    # retried chunks carry the SAME data the clean path would have staged
    for spec, dev in chunks:
        np.testing.assert_array_equal(np.asarray(dev["v"]),
                                      float(spec.index))


def test_prefetcher_exhausted_budget_names_chunk():
    plan = ChunkPlan.build(2048, chunk_rows=256)
    pf = Prefetcher(plan, _flaky_fetch({3}, times=99), max_attempts=3,
                    backoff_s=0.001)
    with pytest.raises(ChunkStagingError,
                       match=r"chunk staging failed for chunk 3 of 8 "
                             r"after 3 attempt"):
        list(pf.stream())
    assert pf.stats.snapshot()["gave_up"] == 1
    assert pf.stats.snapshot()["retries"] == 2


def test_prefetcher_fatal_skips_retry():
    plan = ChunkPlan.build(1024, chunk_rows=256)
    pf = Prefetcher(plan, _flaky_fetch({2}, kind=ValueError),
                    backoff_s=0.001)
    with pytest.raises(ChunkStagingError, match="fatal ValueError"):
        list(pf.stream())
    assert pf.stats.snapshot()["retries"] == 0


def test_prefetcher_interrupt_not_laundered():
    """KeyboardInterrupt/SystemExit in the staging thread must re-raise AS
    THEMSELVES in the consumer (ISSUE 5 satellite: not swallowed into a
    RuntimeError('chunk staging failed'))."""
    plan = ChunkPlan.build(1024, chunk_rows=256)
    for kind in (KeyboardInterrupt, SystemExit):
        pf = Prefetcher(plan, _flaky_fetch({1}, kind=kind), backoff_s=0.001)
        with pytest.raises(kind):
            list(pf.stream())


def test_injected_staging_faults_keep_streamed_fit_exact(rng):
    """Transient staging faults under a streamed FE solve change NOTHING
    about the math: identical objective history, retries accounted."""
    import dataclasses as _dc
    n = 2048
    x = rng.normal(size=(n, 8)); x[:, -1] = 1.0
    y = (rng.uniform(size=n) < 0.5).astype(float)
    fe = FixedEffectCoordinateConfig("g", _opt(8), memory_mode="streamed",
                                     chunk_rows=256)
    cfg = _dc.replace(_config(2, coords=("fixed",)),
                      coordinates={"fixed": fe})

    def run(plan):
        ds = build_game_dataset(y, {"g": x})
        est = GameEstimator(cfg)
        coords = est._build_coordinates(ds)
        from photon_ml_tpu.game.coordinate_descent import \
            run_coordinate_descent
        if plan is None:
            res = run_coordinate_descent(coords, ["fixed"], 2, ds,
                                         cfg.task_type)
        else:
            with faults.injected(plan):
                res = run_coordinate_descent(coords, ["fixed"], 2, ds,
                                             cfg.task_type)
        return res, coords["fixed"]._stream.stats.snapshot()

    ref, _ = run(None)
    plan = faults.FaultPlan([
        {"site": "stage.fetch", "action": "transient", "hits": [1, 4]},
        {"site": "stage.transfer", "action": "transient", "hits": [2]}])
    faulted, stats = run(plan)
    assert plan.report()["total_fired"] == 3
    assert stats["retries"] == 3 and stats["gave_up"] == 0
    np.testing.assert_array_equal(ref.objective_history,
                                  faulted.objective_history)


# --------------------------------------------------------------------------
# non-finite solve quarantine
# --------------------------------------------------------------------------

def test_guard_rolls_back_nonfinite_coefficients():
    from photon_ml_tpu.game import quarantine
    from photon_ml_tpu.models.coefficients import Coefficients
    from photon_ml_tpu.models.game import FixedEffectModel
    from photon_ml_tpu.models.glm import model_for_task
    import jax.numpy as jnp
    good = FixedEffectModel(model_for_task(
        "logistic_regression", Coefficients(jnp.asarray([1.0, 2.0]))), "g")
    bad = FixedEffectModel(model_for_task(
        "logistic_regression",
        Coefficients(jnp.asarray([jnp.nan, 3.0]))), "g")
    guarded, flag = quarantine.guard(bad, good)
    assert not bool(flag)
    np.testing.assert_array_equal(
        np.asarray(guarded.glm.coefficients.means), [1.0, 2.0])
    # healthy solve passes through bitwise
    guarded2, flag2 = quarantine.guard(good, bad)
    assert bool(flag2)
    np.testing.assert_array_equal(
        np.asarray(guarded2.glm.coefficients.means), [1.0, 2.0])


@pytest.mark.parametrize("timing_mode", ["pipelined", "strict"])
def test_poisoned_solve_quarantined_and_recovered(rng, timing_mode):
    """One poisoned solve: the device-side guard rolls it back (history
    stays finite), the tightened-budget retry recovers, and the fit lands
    on the fault-free trajectory."""
    ds = _glmix(rng)
    ref = GameEstimator(_config(4)).fit(ds, timing_mode=timing_mode)
    plan = faults.FaultPlan([
        {"site": "solve.poison", "action": "poison", "hits": [2],
         "match": {"coordinate": "perUser"}}])
    with faults.injected(plan):
        poisoned = GameEstimator(_config(4)).fit(ds, timing_mode=timing_mode)
    assert plan.report()["total_fired"] == 1
    hist = poisoned.objective_history
    assert len(hist) == len(ref.objective_history)
    assert np.all(np.isfinite(hist))
    actions = [e["action"] for e in poisoned.descent.containment_events]
    assert actions == ["rolled_back", "retry_ok"]
    assert poisoned.descent.frozen_coordinates == []
    # recovered: final objective back on the fault-free trajectory
    rel = abs(hist[-1] - ref.objective_history[-1]) \
        / abs(ref.objective_history[-1])
    assert rel < 1e-4
    diag = poisoned.descent.solver_diagnostics()
    assert diag["perUser"]["containment"] == {"retry_ok": 1}


def test_repeated_divergence_freezes_coordinate(rng):
    """Two strikes: a coordinate that diverges again after a successful
    quarantine retry is frozen for the rest of the fit while the other
    coordinate keeps descending."""
    ds = _glmix(rng)
    plan = faults.FaultPlan([
        {"site": "solve.poison", "action": "poison", "hits": [2, 3, 4, 5],
         "match": {"coordinate": "perUser"}}])
    with faults.injected(plan):
        res = GameEstimator(_config(5)).fit(ds)
    assert res.descent.frozen_coordinates == ["perUser"]
    assert np.all(np.isfinite(res.objective_history))
    assert len(res.objective_history) == 10  # canonical length kept
    actions = [e["action"] for e in res.descent.containment_events]
    assert "frozen" in actions
    diag = res.descent.solver_diagnostics()
    assert diag["perUser"]["containment"].get("frozen", 0) >= 1
    # the OTHER coordinate kept making progress after the freeze
    assert res.objective_history[-1] < res.objective_history[1]


class _CompileCounter(logging.Handler):
    def __init__(self):
        super().__init__()
        self.count = 0

    def emit(self, record):
        if record.getMessage().startswith("Compiling "):
            self.count += 1


class _compile_counting:
    def __enter__(self):
        import jax
        self._jax = jax
        self.handler = _CompileCounter()
        self.logger = logging.getLogger("jax._src.interpreters.pxla")
        self._level = self.logger.level
        self.logger.addHandler(self.handler)
        self.logger.setLevel(logging.WARNING)
        jax.config.update("jax_log_compiles", True)
        return self.handler

    def __exit__(self, *exc):
        self._jax.config.update("jax_log_compiles", False)
        self.logger.removeHandler(self.handler)
        self.logger.setLevel(self._level)


def test_health_flag_adds_zero_traces_to_warm_fit(rng):
    """ISSUE 5 satellite: the per-update health flag + where-guard are
    module-level jits — a warm fit (same shapes) compiles NOTHING new."""
    ds = _glmix(rng)
    GameEstimator(_config(1)).fit(ds)  # warmup traces everything
    with _compile_counting() as counter:
        GameEstimator(_config(3)).fit(ds)
    assert counter.count == 0, (
        f"{counter.count} fresh XLA compiles after warmup — the health "
        "flag / rollback guard broke the trace cache")


# --------------------------------------------------------------------------
# crash-safe checkpoints: manifests, fallback, pruning
# --------------------------------------------------------------------------

def test_checkpoint_dirs_carry_verifying_manifests(rng, tmp_path):
    ds = _glmix(rng)
    ckpt = str(tmp_path / "ckpt")
    GameEstimator(_config(2)).fit(ds, checkpoint_dir=ckpt)
    iter_dirs = sorted(p for p in os.listdir(ckpt) if p.startswith("iter-"))
    assert iter_dirs
    for d in iter_dirs:
        ok, reason = verify_checkpoint_dir(os.path.join(ckpt, d))
        assert ok is True, reason
        record = json.load(open(os.path.join(ckpt, d, "record.json")))
        assert record["model_dir"] == d  # self-contained, relative
    state = json.load(open(os.path.join(ckpt, "state.json")))
    assert state["completed_iterations"] == 2


def test_corrupt_primary_falls_back_to_verified_record(rng, tmp_path):
    """Torn/corrupt newest record -> resume from the RETAINED previous
    verified record, with the corrupt directory pruned."""
    import glob
    ds = _glmix(rng)
    ckpt = str(tmp_path / "ckpt")
    GameEstimator(_config(3, coords=("fixed",))).fit(ds, checkpoint_dir=ckpt)
    newest = sorted(glob.glob(os.path.join(ckpt, "iter-*")))[-1]
    npz = glob.glob(os.path.join(newest, "**", "*.npz"), recursive=True)[0]
    with open(npz, "wb") as f:
        f.write(b"torn write")
    state = read_checkpoint(ckpt)
    assert state is not None
    assert state.recovery["fallback"] is True
    assert state.completed_iterations >= 1
    assert not os.path.exists(newest)  # corrupt record pruned
    # and the resumed fit completes + matches the straight run's tail
    resumed = GameEstimator(_config(3, coords=("fixed",))).fit(
        ds, checkpoint_dir=ckpt)
    straight = GameEstimator(_config(3, coords=("fixed",))).fit(ds)
    np.testing.assert_allclose(resumed.objective_history,
                               straight.objective_history, rtol=0,
                               atol=1e-9)


def test_stale_tmp_and_orphan_partials_pruned(rng, tmp_path):
    ds = _glmix(rng)
    ckpt = str(tmp_path / "ckpt")
    GameEstimator(_config(2, coords=("fixed",))).fit(ds, checkpoint_dir=ckpt)
    (tmp_path / "ckpt" / "state.json.tmp").write_text("partial")
    orphan = tmp_path / "ckpt" / "iter-0099"
    orphan.mkdir()
    (orphan / "half.npz").write_bytes(b"partial write")
    state = read_checkpoint(ckpt)
    assert state is not None and state.recovery["fallback"] is False
    assert not (tmp_path / "ckpt" / "state.json.tmp").exists()
    assert not orphan.exists()
    assert len(state.recovery["pruned"]) == 2


def test_fallback_record_respects_fingerprint(rng, tmp_path):
    ds = _glmix(rng)
    ckpt = str(tmp_path / "ckpt")
    est = GameEstimator(_config(2, coords=("fixed",)))
    est.fit(ds, checkpoint_dir=ckpt)
    os.remove(os.path.join(ckpt, "state.json"))  # force the fallback path
    assert read_checkpoint(ckpt, fingerprint="different") is None
    good = read_checkpoint(
        ckpt, fingerprint=est._config_fingerprint(None))
    assert good is not None and good.recovery["fallback"] is True


def test_async_checkpointer_final_record_failure_surfaces(rng, tmp_path):
    """ISSUE 5 satellite: a failed fit-end durable record raises
    immediately at fit end with the ORIGINAL exception as __cause__."""
    ds = _glmix(rng)
    plan = faults.FaultPlan([{"site": "model.save", "action": "fatal",
                              "hits": [1]}])
    with faults.injected(plan):
        with pytest.raises(RuntimeError,
                           match="final fit-end record") as err:
            GameEstimator(_config(1)).fit(
                ds, checkpoint_dir=str(tmp_path / "ckpt"),
                timing_mode="pipelined")
    assert isinstance(err.value.__cause__, faults.FatalFault)


# --------------------------------------------------------------------------
# graceful preemption
# --------------------------------------------------------------------------

def test_preemption_writes_durable_checkpoint_and_resumes(rng, tmp_path):
    """A preemption request stops the fit AFTER the in-flight update with
    a durable record; clearing the flag and re-running reproduces the
    uninterrupted trajectory."""
    ds = _glmix(rng)
    straight = GameEstimator(_config(3, coords=("fixed",))).fit(ds)
    ckpt = str(tmp_path / "ckpt")
    faults.request_preemption()
    with pytest.raises(faults.Preempted) as err:
        GameEstimator(_config(3, coords=("fixed",))).fit(
            ds, checkpoint_dir=ckpt)
    assert err.value.completed_iterations == 1
    assert err.value.checkpointed is True
    faults.clear_preemption()
    state = read_checkpoint(ckpt)
    assert state is not None and state.completed_iterations == 1
    resumed = GameEstimator(_config(3, coords=("fixed",))).fit(
        ds, checkpoint_dir=ckpt)
    np.testing.assert_allclose(resumed.objective_history,
                               straight.objective_history, rtol=0,
                               atol=1e-9)


def test_preemption_mid_iteration_finishes_inflight_update(rng):
    """Multi-coordinate fit: the preemption lands after the FIRST
    coordinate's update of the iteration (finished, not aborted)."""
    ds = _glmix(rng)
    faults.request_preemption()
    with pytest.raises(faults.Preempted) as err:
        GameEstimator(_config(3)).fit(ds)
    # no checkpoint dir -> not resumable, but the update still finished
    assert err.value.completed_iterations == 0
    assert err.value.checkpointed is False


def test_sigterm_handler_sets_flag_then_escalates():
    import signal
    with faults.GracefulPreemption():
        assert not faults.preemption_requested()
        os.kill(os.getpid(), signal.SIGTERM)
        assert faults.preemption_requested()
        with pytest.raises(KeyboardInterrupt):
            os.kill(os.getpid(), signal.SIGTERM)
    assert not faults.preemption_requested()  # cleared on exit


def test_exit_preempted_is_distinct():
    assert faults.EXIT_PREEMPTED == 75  # EX_TEMPFAIL: retry the job


# --------------------------------------------------------------------------
# kill-during-checkpoint crash test (satellite: subprocess SIGKILL at the
# injected fsync site -> resume from last verified record -> fault-free
# f64 trajectory)
# --------------------------------------------------------------------------

# One seeded f64 GLMix fit (this file's own _glmix/_config) in a process of
# its own, because the leg needs true process death: the fault plan arrives
# through PHOTON_FAULT_PLAN, one JSON line comes back.
_CHILD = """
import json, sys
sys.path.insert(0, {tests!r})
import numpy as np
from photon_ml_tpu.utils.jax_cache import enable_persistent_cache
enable_persistent_cache()
from photon_ml_tpu.game import GameEstimator
from photon_ml_tpu.utils import faults
from test_faults import _config, _glmix
faults.install_from_env()
res = GameEstimator(_config(3)).fit(
    _glmix(np.random.default_rng(31), n=700, n_users=50),
    checkpoint_dir={ckpt!r}, timing_mode="strict")
print(json.dumps({{
    "objective_history": [float(v) for v in res.objective_history],
    "checkpoint_recovery": res.checkpoint_recovery}}))
"""


def _run_child(tmp_path, ckpt=None, plan=None, expect_kill=False):
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_ENABLE_X64="1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jaxcache"))
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    env.pop("PHOTON_FAULT_PLAN", None)
    if plan is not None:
        env["PHOTON_FAULT_PLAN"] = json.dumps(plan)
    code = _CHILD.format(tests=os.path.dirname(os.path.abspath(__file__)),
                         ckpt=ckpt)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=420,
                       cwd=_REPO)
    if expect_kill:
        assert p.returncode not in (0, 1), (p.returncode, p.stderr[-500:])
        return p.returncode
    assert p.returncode == 0, p.stderr[-800:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sigkill_during_checkpoint_then_resume_reproduces_f64(tmp_path):
    ref = _run_child(tmp_path)
    ckpt = str(tmp_path / "ckpt")
    plan = {"seed": 0, "faults": [
        {"site": "checkpoint.fsync", "action": "kill", "hits": [2]}]}
    rc = _run_child(tmp_path, ckpt=ckpt, plan=plan, expect_kill=True)
    assert rc == -9  # SIGKILL mid-fsync
    # the torn write left a stale tmp; the sealed-but-unreferenced record
    # and the previous verified record are both on disk
    assert os.path.exists(os.path.join(ckpt, "state.json.tmp"))
    resumed = _run_child(tmp_path, ckpt=ckpt)
    recovery = resumed["checkpoint_recovery"]
    assert recovery is not None
    assert any(p.endswith("state.json.tmp") for p in recovery["pruned"])
    # resume reproduced the fault-free float64 trajectory exactly
    np.testing.assert_allclose(resumed["objective_history"],
                               ref["objective_history"], rtol=0, atol=1e-9)
