"""bench.py --smoke end-to-end in the tier-1 suite (ISSUE 2 satellite):
bench-harness regressions (broken entry plumbing, pipeline parity drift)
surface in the normal test run instead of only at bench time.
"""
import importlib.util
import json
import os

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_smoke_under_test", os.path.join(_REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_bench_smoke_end_to_end(tmp_path):
    bench = _load_bench()
    out = tmp_path / "BENCH_smoke.json"
    result = bench.smoke_bench(str(out))

    # the kill-safe contract: the file on disk IS the returned result
    assert out.exists()
    on_disk = json.loads(out.read_text())
    assert on_disk == json.loads(json.dumps(result))

    glm = result["detail"]["glm"]
    assert glm["final_value_finite"] is True
    assert glm["n"] > 0 and glm["d"] > 0 and glm["wall_s"] > 0

    game = result["detail"]["game_pipeline"]
    # the strict-vs-pipelined smoke pair is a REAL parity gate: identical
    # objective histories (1e-9) and bit-identical final model directories
    assert game["parity_ok"] is True
    assert game["objective_history_max_abs_gap"] <= 1e-9
    assert game["final_model_bit_identical"] is True
    for mode in ("strict", "pipelined"):
        stats = game[mode]
        assert stats["fit_s"] > 0
        assert 0.0 <= stats["host_blocked_frac"] <= 1.0


def test_stoch_smoke(tmp_path):
    """bench.py --stoch --smoke end-to-end in tier-1 (ISSUE 15 satellite):
    the stochastic solver lane's hard gates — examples_per_staged_byte >=
    1.5x the host-stepped LBFGS mirror on an out-of-core shape, f64
    fixed-point parity <= 1e-6 after the polish, zero fresh traces across
    warm epochs, and mesh objective-history parity — run on every tier-1
    pass, so the lane cannot silently regress into re-staging or
    divergence."""
    bench = _load_bench()
    out = tmp_path / "BENCH_stoch.json"
    result = bench.stoch_bench(str(out), smoke=True)

    # kill-safe contract: the file on disk IS the returned result
    assert out.exists()
    assert json.loads(out.read_text()) == json.loads(json.dumps(result))

    detail = result["detail"]
    assert detail["all_gates_ok"] is True
    assert detail["ratio_ok"] and result["value"] >= 1.5
    assert detail["parity_ok"] and detail["traces_ok"]
    assert detail["data_exceeds_budget"] and detail["under_budget"]
    oc = next(e for e in detail["entries"]
              if e["name"] == "stoch_out_of_core")
    assert oc["fixed_point_rel_gap"] <= 1e-6
    # the pinned chunks really did multiple local epochs per staging
    sp = oc["stochastic_polish"]
    assert sp["local_epochs"] > sp["chunks_staged"]
    if detail["mesh_parity_ok"] is not None:
        assert detail["mesh_parity_ok"] is True


def test_sweep_smoke(tmp_path):
    """bench.py --sweep --smoke end-to-end in tier-1 (ISSUE 17 satellite):
    the vectorized-sweep gates — zero fresh XLA traces across a 16-point
    sweep after warmup (lambda is a traced operand of the compiled
    solvers), per-candidate objective parity <= 1e-6 vs isolated f64
    fits, sublinear sweep wall-clock, and zero fresh traces along the
    warm-start path after the first candidate — run on every tier-1 pass,
    so the sweep lane cannot silently regress into per-lambda retracing
    or diverge from the isolated-fit arithmetic."""
    bench = _load_bench()
    out = tmp_path / "BENCH_sweep.json"
    result = bench.sweep_bench(str(out), smoke=True)

    # kill-safe contract: the file on disk IS the returned result
    assert out.exists()
    assert json.loads(out.read_text()) == json.loads(json.dumps(result))

    detail = result["detail"]
    assert detail["smoke"] is True
    assert detail["all_gates_ok"] is True
    assert detail["traces_ok"] and detail["parity_ok"]
    assert detail["sublinear_ok"] and detail["path_traces_ok"]
    vm = next(e for e in detail["entries"] if e["name"] == "sweep_vmap")
    assert vm["candidates"] == 16
    assert vm["fresh_traces_after_warmup"] == 0
    assert vm["objective_parity_rel"] <= 1e-6
    assert vm["wall_ratio_vs_one_fit"] <= vm["candidates"] / 2.0
    pa = next(e for e in detail["entries"] if e["name"] == "sweep_path")
    assert pa["fresh_traces_after_first_candidate"] == 0
    assert pa["warm_start_quality_ok"] is True
    # the sweep counters rode into the embedded telemetry snapshot
    counters = detail["telemetry"]["metrics"]["counters"]
    assert counters["sweep.candidates"] >= 2 * vm["candidates"]
    assert counters["sweep.dispatches"] > 0


def test_admm_smoke(tmp_path):
    """bench.py --admm --smoke end-to-end in tier-1 (ISSUE 18 satellite):
    the feature-axis consensus-ADMM gates — f64 parity <= 1e-6 of the
    pure consensus solve vs monolithic LBFGS across 1x1/1x2/2x2/4x2
    meshes, near-linear per-device aggregator memory reduction as the
    feature axis widens (with the monolithic layout busting the
    per-device budget and the widest mesh training inside it), zero
    fresh XLA traces across warm solves and rho sweeps, and exactly one
    feature-axis vector all-reduce per compiled iteration — run on every
    tier-1 pass, so the lane cannot silently regress into retracing,
    extra collectives or divergence."""
    bench = _load_bench()
    out = tmp_path / "BENCH_admm.json"
    result = bench.admm_bench(str(out), smoke=True)

    # kill-safe contract: the file on disk IS the returned result
    assert out.exists()
    assert json.loads(out.read_text()) == json.loads(json.dumps(result))

    detail = result["detail"]
    assert detail["smoke"] is True
    assert detail["all_gates_ok"] is True
    assert detail["parity_ok"] and detail["memory_ok"]
    assert detail["traces_ok"] and detail["collectives_ok"]
    par = next(e for e in detail["entries"] if e["name"] == "admm_parity")
    assert par["worst_rel_gap"] <= 1e-6
    assert {c["mesh"] for c in par["cells"]} == {"1x1", "1x2", "2x2", "4x2"}
    mem = next(e for e in detail["entries"] if e["name"] == "admm_memory")
    assert mem["monolithic_busts_budget"] and mem["wide_fits_budget"]
    assert mem["wide_trains"] and result["value"] >= 2.0
    tr = next(e for e in detail["entries"]
              if e["name"] == "admm_warm_traces")
    assert tr["fresh_traces"] == 0
    col = next(e for e in detail["entries"]
               if e["name"] == "admm_collectives")
    assert col["feature_vector_allreduces"] == 1
    assert col["data_block_allreduces"] == 1


def test_stream_smoke(tmp_path):
    """bench.py --stream --smoke end-to-end in tier-1 (ISSUE 3 satellite):
    the out-of-core harness — ChunkedGLMObjective streaming, HBM-budgeted
    residency rotation, parity gating, transfer-size accounting — cannot
    rot without failing the normal test run.  Timing numbers are smoke
    signals only; the >= 0.7x throughput bar is enforced by the full
    (accelerator) bench, not here."""
    bench = _load_bench()
    out = tmp_path / "BENCH_stream.json"
    result = bench.stream_bench(str(out), smoke=True)

    # kill-safe contract: the file on disk IS the returned result
    assert out.exists()
    assert json.loads(out.read_text()) == json.loads(json.dumps(result))

    detail = result["detail"]
    assert detail["smoke"] is True
    assert detail["all_parity_ok"] is True
    (entry,) = detail["entries"]
    # the out-of-core claim, gated: the streamed fit trained a config whose
    # coordinate data exceeds the budget while tracked peak stayed under it
    assert entry["data_exceeds_budget"] is True
    assert entry["streamed"]["under_budget"] is True
    assert entry["streamed"]["peak_tracked_bytes"] <= entry["hbm_budget_bytes"]
    assert entry["coordinate_data_bytes"] > entry["hbm_budget_bytes"]
    assert entry["streamed"]["streamed_coordinates"] == ["fixed"]
    # parity: identical history length, relative gap within the gate
    assert entry["parity_ok"] is True
    assert entry["objective_history_max_rel_gap"] <= entry["parity_gate"]
    for mode in ("resident", "streamed"):
        assert entry[mode]["fit_s"] > 0


def test_inexact_smoke(tmp_path):
    """bench.py --inexact --smoke end-to-end in tier-1 (ISSUE 4 satellite):
    the strict-vs-scheduled harness — budget plumbing, warm latent init,
    per-solve diagnostics, parity gating — cannot rot without failing the
    normal test run.  Timing is a smoke signal; the >= 2x speedup bar is
    enforced by the full bench leg, not here."""
    bench = _load_bench()
    out = tmp_path / "BENCH_inexact.json"
    result = bench.inexact_bench(str(out), smoke=True)

    # kill-safe contract: the file on disk IS the returned result
    assert out.exists()
    assert json.loads(out.read_text()) == json.loads(json.dumps(result))

    detail = result["detail"]
    assert detail["smoke"] is True
    # the convex entry is the hard parity gate (unique optimum: the final
    # full-tolerance visit must land strict and scheduled together)
    assert detail["all_parity_ok"] is True
    convex = next(e for e in detail["entries"] if "convex" in e["name"])
    assert convex["parity_ok"] is True
    assert convex["final_rel_gap_vs_strict"] <= convex["parity_gate"]
    # every entry actually ran INEXACTLY: fewer inner iterations than the
    # strict full-solve leg, capped early visits, full final visit
    assert detail["all_iterations_saved"] is True
    for e in detail["entries"]:
        assert e["iterations_saved"] > 0
        for coord, caps in e["scheduled"]["iteration_caps"].items():
            assert caps[0] is not None and caps[0] <= 4
        assert all(c is None for caps in
                   e["strict"]["iteration_caps"].values() for c in caps)
        assert e["strict"]["fit_s"] > 0 and e["scheduled"]["fit_s"] > 0
    mf = next(e for e in detail["entries"] if "mf" in e["name"])
    assert "perUserMF" in mf["coordinates"]


def test_faults_smoke(tmp_path, monkeypatch):
    """bench.py --faults --smoke end-to-end in tier-1 (ISSUE 5 satellite):
    the chaos harness — injected staging faults absorbed by retry/backoff,
    SIGKILL mid-checkpoint-fsync recovered by the manifest-verified resume,
    a poisoned coordinate quarantined and re-run — cannot rot without
    failing the normal test run.  Every leg is parity-gated against its
    fault-free trajectory at the 1e-4 gate."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jaxcache"))
    bench = _load_bench()
    out = tmp_path / "BENCH_faults.json"
    result = bench.faults_bench(str(out), smoke=True)

    # kill-safe contract: the file on disk IS the returned result
    assert out.exists()
    assert json.loads(out.read_text()) == json.loads(json.dumps(result))

    detail = result["detail"]
    assert detail["smoke"] is True
    assert detail["all_parity_ok"] is True
    assert result["value"] <= 1e-4

    staging = next(e for e in detail["entries"] if "staging" in e["name"])
    assert staging["retries"] >= 4 and staging["gave_up"] == 0
    assert staging["injected"]["total_fired"] >= 4
    assert staging["objective_history_max_abs_gap"] == 0.0

    kill = next(e for e in detail["entries"] if "kill" in e["name"])
    assert kill["killed_returncode"] not in (0, 1)  # actually SIGKILLed
    assert kill["stale_tmp_left_by_kill"] is True
    assert kill["pruned_on_resume"] >= 1
    assert kill["objective_history_max_rel_gap"] <= kill["parity_gate"]

    poisoned = next(e for e in detail["entries"] if "poison" in e["name"])
    actions = [ev["action"] for ev in poisoned["containment_events"]]
    assert "rolled_back" in actions
    assert poisoned["history_finite"] is True
    assert poisoned["final_rel_gap_vs_fault_free"] <= \
        poisoned["parity_gate"]


def test_mesh_smoke(tmp_path):
    """bench.py --mesh --smoke end-to-end in tier-1 (ISSUE 6 satellite):
    the multi-chip harness — mesh-resident staging, per-device budgets,
    mesh-streamed out-of-core, transfer + compile gates — cannot rot
    without failing the normal test run.  This is ALSO the tier-1
    multichip coverage that replaces the ad-hoc dryrun_multichip entry
    (which now drives this same path).  Wall-clock is a smoke signal only:
    virtual CPU devices share cores, so the honest gates are parity,
    transfer behavior, and compile stability."""
    bench = _load_bench()
    out = tmp_path / "BENCH_mesh.json"
    result = bench.mesh_bench(str(out), smoke=True)

    # kill-safe contract: the file on disk IS the returned result
    assert out.exists()
    assert json.loads(out.read_text()) == json.loads(json.dumps(result))

    detail = result["detail"]
    assert detail["smoke"] is True
    assert detail["devices"] >= 8
    # f64 parity, hard-gated on EVERY leg (FE, RE, factored, streamed)
    assert detail["all_parity_ok"] is True
    assert result["value"] <= 1e-4
    names = {e["name"] for e in detail["entries"]}
    assert {"mesh_fe", "mesh_re", "mesh_factored", "mesh_streamed"} <= names
    # warm iterations move only coefficients+offsets — never the dataset
    # (the factored leg's latent blocks legitimately re-project per visit,
    # so only its plain coordinates enter the warm gate)
    assert detail["all_warm_transfer_ok"] is True
    for e in detail["entries"]:
        if e["name"] in ("mesh_fe", "mesh_re"):
            assert e["warm_run_staged"]["cold_bytes"] == 0
        if "warm_run_bit_identical_history" in e:
            assert e["warm_run_bit_identical_history"] is True
    re_leg = next(e for e in detail["entries"] if e["name"] == "mesh_re")
    assert re_leg["warm_run_staged"]["warm_bytes"] > 0
    # zero fresh traces across warm outer iterations
    assert detail["all_zero_fresh_traces"] is True
    # mesh x streaming: per-device data > per-device budget, peak under it
    stream = next(e for e in detail["entries"] if e["name"] == "mesh_streamed")
    assert stream["data_exceeds_budget"] is True
    assert stream["streamed_engaged_ok"] is True
    assert stream["under_budget_ok"] is True
    assert stream["per_device_accounting"]["data_devices"] >= 8


def test_trace_smoke(tmp_path):
    """bench.py --trace --smoke end-to-end in tier-1 (ISSUE 8 satellite):
    the telemetry harness — disarmed zero-overhead contract, zero fresh
    XLA traces on a warm fit armed or disarmed, cli.train --trace-out
    emitting valid Chrome-trace JSON with a correctly nested span tree and
    fault/quarantine events attached to the right spans — cannot rot
    without failing the normal test run."""
    bench = _load_bench()
    out = tmp_path / "BENCH_trace.json"
    result = bench.trace_bench(str(out), smoke=True)

    # kill-safe contract: the file on disk IS the returned result
    assert out.exists()
    assert json.loads(out.read_text()) == json.loads(json.dumps(result))

    detail = result["detail"]
    assert detail["smoke"] is True
    assert detail["all_ok"] is True
    # every bench mode embeds the telemetry snapshot (ISSUE 8 satellite)
    assert "metrics" in detail["telemetry"]

    overhead = next(e for e in detail["entries"]
                    if e["name"] == "disarmed_overhead")
    # disarmed AND armed warm fits: zero fresh XLA traces
    assert overhead["fresh_traces_disarmed_warm"] == 0
    assert overhead["fresh_traces_armed_warm"] == 0
    # the 1%-of-wall-clock gate on the disarmed instrumentation
    assert overhead["overhead_frac_estimate"] <= overhead["overhead_gate"]
    assert overhead["span_calls_per_fit"] > 0

    cli = next(e for e in detail["entries"] if e["name"] == "cli_trace")
    assert cli["returncode"] == 0
    # the emitted trace validates against the Chrome trace format's
    # required keys (name/ph/ts/pid/tid, dur on complete events)
    assert cli["trace_valid"] is True and cli["trace_problems"] == []
    # span tree: outer iterations -> coordinate visits -> solves
    assert cli["nesting_ok"] is True
    assert cli["solves_nest_in_visits"] is True
    # the injected solve.poison landed on the perUser visit's spans and
    # its quarantine containment recovered
    assert cli["fault_attributed_coordinates"] == ["perUser"]
    assert cli["quarantine_recovered"] is True
    assert cli["run_log_records"] > 0


def test_online_smoke(tmp_path):
    """bench.py --online --smoke end-to-end in tier-1 (ISSUE 9 satellite):
    the online-learning harness — feedback intake, anchored micro-batch
    solves, delta swaps into the live scorer, offline-refit parity, the
    steady-state compile gate, and delta-aware rollback — cannot rot
    without failing the normal test run.  The scoring-p99-under-update
    gate is a smoke SIGNAL here (shared-core CI); the full bench run
    enforces it hard."""
    bench = _load_bench()
    out = tmp_path / "BENCH_online.json"
    result = bench.online_bench(str(out), smoke=True)

    # kill-safe contract: the file on disk IS the returned result
    assert out.exists()
    assert json.loads(out.read_text()) == json.loads(json.dumps(result))

    detail = result["detail"]
    assert detail["smoke"] is True
    assert detail["all_ok"] is True
    # online-updated rows match the offline refit of the same entities
    parity = next(e for e in detail["entries"]
                  if e["name"] == "online_parity")
    assert parity["parity_ok"] is True
    assert parity["max_rel_gap_vs_offline_refit"] <= parity["parity_gate"]
    assert max(parity["scipy_oracle_rel_gaps"]) <= 1e-4
    assert parity["deltas"] >= 1
    # warm serve loop absorbing deltas: zero fresh XLA traces
    traces = next(e for e in detail["entries"]
                  if e["name"] == "online_steady_state_traces")
    assert traces["fresh_traces_steady_state"] == 0
    assert traces["deltas_absorbed"] >= traces["steady_rounds"]
    # delta-aware rollback round-trips bit-exact + durable persistence
    rollback = next(e for e in detail["entries"]
                    if e["name"] == "online_rollback")
    assert rollback["rollback_bit_exact"] is True
    assert rollback["delta_durable_roundtrip_ok"] is True
    assert rollback["deltas_applied"] >= 3
    # updates actually ran concurrent with scoring traffic
    latency = next(e for e in detail["entries"]
                   if e["name"] == "online_latency")
    assert latency["under_updates"]["entities_updated"] > 0
    assert latency["under_updates"]["deltas_published"] > 0
    assert latency["baseline"]["errors"] == 0
    assert latency["under_updates"]["errors"] == 0


def test_health_smoke(tmp_path):
    """bench.py --health --smoke end-to-end in tier-1 (ISSUE 11
    satellite): the model-health harness — streaming calibration windows,
    drift baselines, gate trips on injected label-flip and covariate
    shift, pause + delta rollback, the armed/disarmed compile gate —
    cannot rot without failing the normal test run.  The p99 gate is a
    smoke SIGNAL here (shared-core CI); the full bench run enforces it
    hard."""
    bench = _load_bench()
    out = tmp_path / "BENCH_health.json"
    result = bench.health_bench(str(out), smoke=True)

    # kill-safe contract: the file on disk IS the returned result
    assert out.exists()
    assert json.loads(out.read_text()) == json.loads(json.dumps(result))

    detail = result["detail"]
    assert detail["smoke"] is True
    assert detail["all_ok"] is True
    # zero false alarms across the stationary leg (deltas flowing live)
    stationary = next(e for e in detail["entries"]
                      if e["name"] == "health_stationary")
    assert stationary["gate_trips"] == 0
    assert stationary["deltas_published"] > 0
    assert stationary["status"] == "ok"
    # injected label flip: calibration gate trips within <= 3 windows,
    # updater pauses, the pending deltas roll back bit-exact
    flip = next(e for e in detail["entries"]
                if e["name"] == "health_label_flip")
    assert flip["windows_to_trip"] is not None
    assert flip["windows_to_trip"] <= 3
    assert flip["status"] == "degraded" and flip["updater_paused"]
    assert flip["deltas_published_while_paused"] == 0
    assert flip["rollback_restored_pre_delta_rows"] is True
    # injected covariate shift: a drift gate trips within <= 3 windows
    covariate = next(e for e in detail["entries"]
                     if e["name"] == "health_covariate_shift")
    assert covariate["windows_to_trip"] is not None
    assert covariate["windows_to_trip"] <= 3
    assert covariate["tripped_gates"]
    # zero fresh traces armed AND disarmed, with windows closing inside
    # the counted region
    traces = next(e for e in detail["entries"]
                  if e["name"] == "health_steady_state_traces")
    assert traces["armed"]["fresh_traces"] == 0
    assert traces["disarmed"]["fresh_traces"] == 0
    assert traces["armed"]["label_windows"] >= 3
    # the latency leg ran without errors on both sides (ratio is gated
    # by the full bench, not here)
    latency = next(e for e in detail["entries"]
                   if e["name"] == "health_latency")
    assert latency["disarmed"]["errors"] == 0
    assert latency["armed"]["errors"] == 0
    assert latency["armed"]["score_windows"] > 0


def test_refit_smoke(tmp_path):
    """bench.py --refit --smoke end-to-end in tier-1 (ISSUE 16
    satellite): the continuous-training harness — f64 refit-from-log
    parity, the drift-trip -> compact -> warm refit -> validate -> swap
    -> recovery loop, and the zero-fresh-traces-across-the-swap gate —
    cannot rot without failing the normal test run.  The p99 gate is a
    smoke SIGNAL here (shared-core CI; the nice'd cli.refit child
    competes with the whole suite); the full bench run enforces it
    hard."""
    bench = _load_bench()
    out = tmp_path / "BENCH_refit.json"
    result = bench.refit_bench(str(out), smoke=True)

    # kill-safe contract: the file on disk IS the returned result
    assert out.exists()
    assert json.loads(out.read_text()) == json.loads(json.dumps(result))

    detail = result["detail"]
    assert detail["smoke"] is True
    assert detail["all_ok"] is True
    # refitting from the log is the IDENTICAL fit as from memory (f64)
    parity = next(e for e in detail["entries"]
                  if e["name"] == "refit_parity")
    assert parity["parity_ok"] is True
    assert parity["history_max_abs_diff"] <= parity["parity_gate"]
    assert parity["sealed_chunks"] >= 1
    # the closed loop: trip -> pause -> refit -> swap -> gates reset ->
    # resume -> zero trips across a post-swap stationary window
    loop = next(e for e in detail["entries"] if e["name"] == "refit_loop")
    assert loop["loop_ok"] is True
    assert loop["windows_to_trip"] is not None
    assert loop["updater_paused_on_trip"] is True
    assert loop["swapped"] is True
    assert loop["candidate_version"] != loop["incumbent_version"]
    assert loop["candidate"]["loss"] < loop["incumbent"]["loss"]
    assert loop["gates_reset"] and loop["updater_resumed"]
    assert loop["post_swap_trips"] == 0
    assert loop["post_swap_status"] == "ok"
    assert loop["refit_metrics"]["swaps"] >= 1
    # zero fresh XLA traces in the serving path on BOTH sides of the swap
    traces = next(e for e in detail["entries"]
                  if e["name"] == "refit_traces")
    assert traces["zero_traces_ok"] is True
    assert traces["fresh_traces_before_swap"] == 0
    assert traces["fresh_traces_after_swap"] == 0
    assert traces["version_after"] != traces["version_before"]
    # the latency leg's subprocess refit ran cycles and exited cleanly
    # (the 1.2x ratio is the full bench's hard gate, not smoke's)
    latency = next(e for e in detail["entries"]
                   if e["name"] == "refit_latency")
    assert latency["child_rc"] == 0
    assert latency["first_cycle_before_measurement"] is True
    assert latency["refit_cycles"] >= 1 or latency["refit_swap_dirs"] >= 1
    assert latency["overlapped_reps"] == latency["reps"]


def test_fleet_smoke(tmp_path):
    """bench.py --fleet --smoke end-to-end in tier-1 (ISSUE 12
    satellite): the replicated-serving harness — log replay with zero
    fresh traces, mid-stream rollback convergence, transient-fault
    trajectory parity, and the subprocess crash/catch-up leg with a real
    SIGKILL — cannot rot without failing the normal test run.  The
    1->2-replica throughput-scaling gate is a smoke SIGNAL here
    (shared-core CI; on a single-core host it is measured and reported
    ungated); the full bench run enforces it hard on multi-core hosts."""
    bench = _load_bench()
    out = tmp_path / "BENCH_fleet.json"
    result = bench.fleet_bench(str(out), smoke=True)

    # kill-safe contract: the file on disk IS the returned result
    assert out.exists()
    assert json.loads(out.read_text()) == json.loads(json.dumps(result))

    detail = result["detail"]
    assert detail["smoke"] is True
    assert detail["all_ok"] is True
    # (d) zero fresh traces on the replica during steady-state replay
    traces = next(e for e in detail["entries"]
                  if e["name"] == "fleet_replay_traces")
    assert traces["fresh_traces_replay"] == 0
    assert traces["records_applied"] >= traces["steady_rounds"]
    assert traces["converged"] is True
    # (b) a mid-stream rollback converges identically on every replica
    rollback = next(e for e in detail["entries"]
                    if e["name"] == "fleet_rollback_convergence")
    assert rollback["rollback_ok"] is True
    assert rollback["publisher_restored_pre_delta_rows"] is True
    assert rollback["deltas_rolled_back"] >= 1
    # (e) injected transient replog/replica faults absorbed with
    # exact-trajectory parity vs the fault-free run
    parity = next(e for e in detail["entries"]
                  if e["name"] == "fleet_fault_parity")
    assert parity["fault_parity_ok"] is True
    assert parity["faults_fired"] >= 4
    assert parity["fault_free_vv"] == parity["faulted_vv"]
    # (a) SIGKILLed follower restarts from durable state and the whole
    # fleet reports bit-identical version vectors + table hashes
    crash = next(e for e in detail["entries"]
                 if e["name"] == "fleet_crash_catchup")
    assert crash["killed_returncode"] not in (0, 1)   # actually SIGKILLed
    assert crash["rejoined_ready"] is True
    assert crash["bit_identical"] is True
    assert crash["rows_scored"] > 0 and crash["feedback_rows"] > 0
    assert crash["deltas_published"] > 0
    # (c) both scaling phases served their full stream error-free (the
    # ratio is the full bench's hard gate on multi-core hosts)
    scaling = next(e for e in detail["entries"]
                   if e["name"] == "fleet_scaling")
    assert scaling["one_replica"]["errors"] == 0
    assert scaling["two_replicas"]["errors"] == 0
    assert scaling["throughput_ratio"] > 0


def test_fleetobs_smoke(tmp_path):
    """bench.py --fleetobs --smoke end-to-end in tier-1 (ISSUE 13
    satellite): the fleet-observability harness — cross-process trace
    merge (one connected tree per request id, feedback flow crossing
    front -> publisher -> follower), clock-probe alignment keeping
    children inside parents, federated per-replica lag that goes
    0 -> >0 -> 0 around a SIGKILL + catch-up, correlated flight-recorder
    bundles on the crash and on a health-gate trip, and the zero-fresh-
    traces contract — cannot rot without failing the normal test run.
    The armed-vs-disarmed p99 ratio is a smoke SIGNAL here (shared-core
    CI); the full bench run gates it at 1.1x."""
    bench = _load_bench()
    out = tmp_path / "BENCH_fleetobs.json"
    result = bench.fleetobs_bench(str(out), smoke=True)

    # kill-safe contract: the file on disk IS the returned result
    assert out.exists()
    assert json.loads(out.read_text()) == json.loads(json.dumps(result))

    detail = result["detail"]
    assert detail["smoke"] is True
    assert detail["all_ok"] is True
    fleet = next(e for e in detail["entries"]
                 if e["name"] == "fleetobs_fleet")
    # the merged Perfetto export validates and every sampled request id
    # is ONE connected tree
    assert fleet["merge_valid"] is True and fleet["merge_problems"] == []
    assert fleet["score_trees_ok"] is True
    # the feedback flow crosses >= 3 processes with the full span chain
    assert fleet["feedback_tree_ok"] is True
    assert {"front_request", "serve_request", "online_update",
            "replica_apply"} <= set(fleet["feedback_tree"]["span_names"])
    assert len(fleet["feedback_tree"]["processes"]) >= 3
    # clock alignment keeps children inside their parents
    assert fleet["containment"]["checked"] > 0
    assert fleet["containment_violations"] == 0
    # federated lag: 0 converged -> >0 while the follower is down and
    # the publisher advances -> 0 after restart + catch-up
    assert fleet["killed_returncode"] not in (0, 1)
    assert fleet["lag_at_converged"]["lag_records"] == 0
    assert fleet["lag_while_down"]["lag_records"] > 0
    assert fleet["lag_after_catchup"]["lag_seq"] == 0
    assert fleet["federated_ok"] is True
    # the crash produced correlated bundles from >= 2 live processes
    assert fleet["flight_ok"] is True
    assert "front" in fleet["flight_bundle_procs"]
    # a health-gate trip dumps the triggering window
    health = next(e for e in detail["entries"]
                  if e["name"] == "fleetobs_health_flight")
    assert health["gate_trips"] >= 1
    assert health["trip_event_in_bundle"] is True
    assert health["evaluate_span_in_bundle"] is True
    # zero fresh XLA traces armed AND disarmed
    overhead = next(e for e in detail["entries"]
                    if e["name"] == "fleetobs_overhead")
    assert overhead["fresh_traces_disarmed"] == 0
    assert overhead["fresh_traces_armed"] == 0
    assert overhead["p99_ratio_armed_vs_disarmed"] > 0


def test_store_smoke(tmp_path):
    """bench.py --store --smoke end-to-end in tier-1 (ISSUE 14
    satellite): the tiered-entity-store harness — budgeted-vs-all-
    resident serving through the store, hot+warm delta swaps with
    bit-exact rollback, the budgeted training parity gate, and the
    zero-fresh-traces regression — cannot rot without failing the
    normal test run.  The p99 latency half of the serving gate is a
    smoke signal here (shared CPUs); it is HARD on the committed full
    bench run."""
    bench = _load_bench()
    out = tmp_path / "BENCH_store.json"
    result = bench.store_bench(str(out), smoke=True)

    # kill-safe contract: the file on disk IS the returned result
    assert out.exists()
    assert json.loads(out.read_text()) == json.loads(json.dumps(result))

    detail = result["detail"]
    assert detail["smoke"] is True
    assert detail["all_ok"] is True
    by_name = {e["name"]: e for e in detail["entries"]}
    serving = by_name["store_serving"]
    # the residency claim: far more entities than device-resident rows,
    # served at >= 90% hot hit rate
    assert serving["hot_rows"] < serving["entities"]
    assert serving["hit_rate_ok"] is True
    assert serving["budgeted"]["hit_rate"] >= 0.90
    # promotions flush BETWEEN measurement windows (the off-peak pacing
    # the bench documents), so assert on the store's cumulative counter
    assert serving["budgeted"]["residency"]["promotions"] > 0
    delta = by_name["store_delta"]
    assert delta["rollback_bit_exact"] is True
    assert delta["durable_round_trip_exact"] is True
    assert delta["delta_rows_hot_tier"] > 0
    assert delta["delta_rows_warm_tier"] > 0
    training = by_name["store_training"]
    assert training["objective_history_max_rel_gap"] <= 1e-10
    assert training["evictions"] > 0 and training["store_fetches"] > 0
    traces = by_name["store_traces"]
    assert traces["serving_fresh_traces"] == 0
    assert traces["training_fresh_traces"] == 0
    assert traces["serving_exercised"] is True


def test_multihost_smoke(tmp_path):
    """bench.py --multihost --smoke (ISSUE 19 satellite): the 2-subprocess
    jax.distributed pair on tiny shapes, with the parity + staging gates
    asserted and the wall budget honored — an exhausted --max-wall skips
    the trace-differential and kill/resume legs with explicit "truncated"
    markers instead of blowing the suite budget."""
    bench = _load_bench()
    out = tmp_path / "BENCH_multihost.json"
    result = bench.multihost_bench(str(out), smoke=True, max_wall=0.05)

    # kill-safe contract: the file on disk IS the returned result
    assert out.exists()
    assert json.loads(out.read_text()) == json.loads(json.dumps(result))

    d = result["detail"]
    # the 2proc x 1dev vs 1proc x 2dev pair keeps the global mesh, so
    # parity is bit-exact, not approximate
    assert d["parity_ok"] is True and d["parity_gap_abs"] == 0.0
    assert d["model_bit_identical"] is True
    # per-process staging: symmetric cold shards, bounded warm traffic
    assert d["staging_ok"] is True
    assert len(d["cold_bytes_per_process"]) == 2
    # the wall budget was exhausted after the parity leg: the remaining
    # legs are skipped WITH markers, and the skipped gates stay non-False
    assert set(d["truncated"]) == {"multihost_traces",
                                   "multihost_kill_resume"}
    assert d["max_wall_s"] == 0.05
    assert d["zero_fresh_traces_ok"] is None
    assert d["kill_resume"] is None
    assert d["gates_green"] is True


def test_shards_smoke(tmp_path):
    """bench.py --shards --smoke end-to-end in tier-1 (ISSUE 20
    satellite): the entity-sharded-serving harness — deterministic/total
    shard map with spec_id rejection, fan-out merge bit-parity vs the
    monolithic scorer with zero fresh traces, shard-filtered replay to
    sha256-exact per-shard audits, the 4x-store-budget capacity claim,
    and the subprocess SIGKILL/degrade/rejoin leg — cannot rot without
    failing the normal test run.  The surviving-shard p99 gate is a
    smoke SIGNAL here (shared-core CI); the committed full bench run
    gates it hard at 1.2x."""
    bench = _load_bench()
    out = tmp_path / "BENCH_shards.json"
    result = bench.shards_bench(str(out), smoke=True)

    # kill-safe contract: the file on disk IS the returned result
    assert out.exists()
    assert json.loads(out.read_text()) == json.loads(json.dumps(result))

    detail = result["detail"]
    assert detail["smoke"] is True
    assert detail["all_ok"] is True
    by_name = {e["name"]: e for e in detail["entries"]}
    smap = by_name["shards_map"]
    assert smap["deterministic"] and smap["total"] and smap["roundtrip"]
    assert smap["spec_id_mismatch_rejected"] is True
    parity = by_name["shards_parity"]
    assert parity["rounds_bit_exact"] == parity["rounds"]
    assert parity["fresh_traces_fanout"] == 0
    assert parity["all_primaries_exact"] is True
    replay = by_name["shards_replay"]
    assert replay["fresh_traces_replay"] == 0
    assert replay["per_shard_audits_sha256_exact"] is True
    capacity = by_name["shards_capacity"]
    assert capacity["rounds_bit_exact"] == capacity["rounds"]
    assert result["value"] >= 4.0
    failover = by_name["shards_failover"]
    assert failover["killed_returncode"] not in (0, 1)  # real SIGKILL
    assert failover["baseline"]["errors"] == 0
    assert failover["baseline"]["inexact"] == 0
    assert failover["one_shard_down"]["errors"] == 0
    assert failover["one_shard_down"]["inexact"] == 0
    assert failover["errors_confined_to_lost_shard"] is True
    assert failover["rejoin_audit_sha256_exact"] is True
    assert failover["rejoin_heals_degraded_request"] is True

    # --max-wall is honored: an exhausted budget skips the heavy legs
    # with explicit "truncated" markers instead of blowing the suite
    # budget (the JSON still lands atomically, exit stays clean)
    out2 = tmp_path / "BENCH_shards_wall.json"
    result2 = bench.shards_bench(str(out2), smoke=True, max_wall=0.0)
    assert out2.exists()
    d2 = result2["detail"]
    assert set(d2["truncated"]) == {
        "shards_map", "shards_parity", "shards_replay",
        "shards_capacity", "shards_failover"}
    assert d2["all_ok"] is False


def test_max_wall_truncates_and_exits_cleanly(tmp_path, monkeypatch):
    """--max-wall budget (ISSUE 4 satellite): an exhausted wall budget
    SKIPS the remaining configs, writes the partial JSON with a
    "truncated" marker, and returns normally (exit 0) — instead of the
    harness timeout killing the run at rc=124 with the JSON lost."""
    bench = _load_bench()
    monkeypatch.chdir(tmp_path)
    result = bench.main(max_wall=0.0, platform="cpu")  # CPU on purpose
    assert result["detail"]["device"]["platform"] == "cpu"
    assert result["detail"]["truncated"]          # every config skipped
    assert result["detail"]["configs"] == {}
    assert result["detail"]["max_wall_s"] == 0.0
    on_disk = json.loads((tmp_path / "BENCH.json").read_text())
    assert on_disk["detail"]["truncated"] == result["detail"]["truncated"]
    # the inexact leg honors the same budget
    out = tmp_path / "BENCH_inexact.json"
    r = bench.inexact_bench(str(out), smoke=False, max_wall=0.0)
    assert r["detail"]["truncated"]
    assert r["detail"]["entries"] == []


def test_default_run_refuses_a_device_it_was_not_asked_for(tmp_path,
                                                           monkeypatch):
    """The default bench run times the attached TPU: on a machine where
    JAX finds only the CPU it refuses (no silent fallback) unless the CPU
    is asked for on purpose, and the HBM roofline share errors on a
    device kind that has no recorded peak instead of dividing by the
    v5e's."""
    import pytest
    bench = _load_bench()
    monkeypatch.chdir(tmp_path)
    with pytest.raises(SystemExit, match="pass --cpu"):
        bench.main(max_wall=0.0)
    assert not (tmp_path / "BENCH.json").exists()
    with pytest.raises(KeyError, match="no HBM peak recorded"):
        bench._hbm_fields(1.0)
    assert bench.HBM_PEAK_GBPS == {"TPU v5 lite": 819.0}


def test_bench_smoke_writes_no_repo_state(tmp_path, monkeypatch):
    """Smoke mode must not touch the committed bench caches (it is run by
    the tier-1 suite, which may not write repo files)."""
    bench = _load_bench()
    before = os.path.getmtime(os.path.join(_REPO, "bench_ref_cache.json"))
    monkeypatch.chdir(tmp_path)
    bench.smoke_bench(str(tmp_path / "s.json"))
    assert os.path.getmtime(
        os.path.join(_REPO, "bench_ref_cache.json")) == before
    assert not os.path.exists(os.path.join(_REPO, "BENCH_smoke.json"))
