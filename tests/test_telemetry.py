"""Unified telemetry (ISSUE 8): span tracer disarm semantics, thread-aware
hierarchy, metrics registry, exporters, run-log correlation, EventEmitter
routing/isolation, and the compile-count + sync-point regression gates
that keep the instrumentation off the device hot path.
"""
import json
import logging
import threading

import numpy as np
import pytest

from photon_ml_tpu import telemetry
from photon_ml_tpu.telemetry.export import prometheus_text
from photon_ml_tpu.telemetry.metrics import MetricsRegistry
from photon_ml_tpu.utils.events import (
    EventEmitter, EventListener, ScoringBatchEvent, TrainingStartEvent,
)


# --------------------------------------------------------------------------
# disarm semantics
# --------------------------------------------------------------------------

def test_disarmed_span_is_the_shared_noop_singleton():
    """faults.fire()-style disarm: a module-global None check returning
    ONE shared object — no span allocation, no record, no tracer."""
    assert not telemetry.armed()
    a = telemetry.span("anything", attr=1)
    b = telemetry.span("other")
    assert a is b is telemetry.NOOP_SPAN
    with a:
        assert telemetry.current_span_id() is None
    assert telemetry.push("x") is None
    telemetry.pop(None)                    # no-op, no error
    telemetry.event("nothing", k=2)        # no-op


def test_enabled_scope_arms_and_disarms():
    assert not telemetry.armed()
    with telemetry.enabled(watch_compiles=False) as tracer:
        assert telemetry.armed()
        assert telemetry.active_tracer() is tracer
    assert not telemetry.armed()
    assert telemetry.last_tracer() is tracer  # still exportable


# --------------------------------------------------------------------------
# span hierarchy
# --------------------------------------------------------------------------

def test_span_nesting_parents_and_attrs():
    with telemetry.enabled(watch_compiles=False) as tracer:
        with telemetry.span("outer", iteration=3) as outer:
            assert telemetry.current_span_id() == outer.span_id
            with telemetry.span("inner", coordinate="perUser") as inner:
                pass
        assert telemetry.current_span_id() is None
    assert inner.parent_id == outer.span_id
    assert outer.parent_id is None
    assert inner.attrs == {"coordinate": "perUser"}
    assert all(s.dur_s is not None and s.dur_s >= 0 for s in tracer.spans)


def test_push_pop_self_heals_abandoned_spans():
    with telemetry.enabled(watch_compiles=False) as tracer:
        a = telemetry.push("a")
        telemetry.push("b")  # never popped explicitly
        telemetry.pop(a)     # closes b, then a
        assert telemetry.current_span_id() is None
    names = [s.name for s in tracer.spans]
    assert names == ["b", "a"]


def test_finish_closes_spans_left_open_by_an_exception():
    with telemetry.enabled(watch_compiles=False) as tracer:
        telemetry.push("leaked")
    # enabled.__exit__ -> shutdown -> finish heals the stack
    assert [s.name for s in tracer.spans] == ["leaked"]
    assert tracer.stats()["open_spans"] == 0


def test_threads_get_their_own_span_roots():
    with telemetry.enabled(watch_compiles=False) as tracer:
        with telemetry.span("main_root"):
            def work():
                with telemetry.span("bg_root"):
                    pass
            t = threading.Thread(target=work, name="photon-test-bg")
            t.start()
            t.join()
    bg = next(s for s in tracer.spans if s.name == "bg_root")
    main = next(s for s in tracer.spans if s.name == "main_root")
    assert bg.parent_id is None          # thread root, not nested in main
    assert bg.tid != main.tid
    assert bg.thread_name == "photon-test-bg"


def test_event_attaches_to_current_span():
    with telemetry.enabled(watch_compiles=False) as tracer:
        with telemetry.span("visit") as visit:
            telemetry.event("fault", site="solve.poison")
        telemetry.event("orphan")
    assert tracer.events[0]["span"] == visit.span_id
    assert tracer.events[1]["span"] is None


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------

def test_counters_gauges_and_type_collision():
    r = MetricsRegistry()
    r.counter("c").inc()
    r.counter("c").inc(4)
    assert r.counter("c").value == 5
    with pytest.raises(ValueError):
        r.counter("c").inc(-1)
    r.gauge("g").set(2.5)
    assert r.gauge("g").value == 2.5
    with pytest.raises(TypeError):
        r.gauge("c")  # name already a counter


def test_histogram_reservoir_is_bounded_and_exact_counts():
    r = MetricsRegistry()
    h = r.histogram("lat", reservoir=64)
    for i in range(10_000):
        h.observe(i)
    snap = h.snapshot()
    assert snap["count"] == 10_000          # exact
    assert snap["max"] == 9_999.0           # exact
    assert snap["window"] == 64             # bounded
    # the reservoir is a newest-N window, so percentiles track the tail
    assert snap["p50"] >= 9_900
    assert snap["p99"] >= snap["p95"] >= snap["p50"]
    assert json.dumps(r.snapshot())         # JSON-safe


def test_snapshot_includes_collectors():
    telemetry.register_collector("test_collector", lambda: {"x": 1})
    try:
        snap = telemetry.snapshot()
        assert snap["test_collector"] == {"x": 1}
        assert "metrics" in snap
        json.dumps(snap)
    finally:
        telemetry.unregister_collector("test_collector")
    assert "test_collector" not in telemetry.snapshot()


def test_prometheus_text_exposition():
    r = MetricsRegistry()
    r.counter("serving.requests").inc(7)
    r.gauge("train.host_blocked_frac").set(0.25)
    h = r.histogram("serving.latency_s", reservoir=16)
    h.observe(0.01)
    h.observe(0.02)
    text = prometheus_text(r, extra_info={"model_version": "v3"})
    assert "# TYPE photon_serving_requests_total counter" in text
    assert "photon_serving_requests_total 7" in text
    assert "photon_train_host_blocked_frac 0.25" in text
    assert "# TYPE photon_serving_latency_s summary" in text
    assert 'photon_serving_latency_s{quantile="0.99"}' in text
    assert "photon_serving_latency_s_count 2" in text
    assert 'photon_info{model_version="v3"} 1' in text
    assert text.endswith("\n")


# --------------------------------------------------------------------------
# exporters + run log
# --------------------------------------------------------------------------

def test_chrome_trace_export_required_keys_and_tree(tmp_path):
    with telemetry.enabled(watch_compiles=False):
        with telemetry.span("outer_iteration", iteration=0):
            with telemetry.span("coordinate_visit", coordinate="fixed"):
                telemetry.event("fault", site="stage.fetch")
    out = tmp_path / "trace.json"
    info = telemetry.write_chrome_trace(str(out))
    assert info["events"] >= 3
    payload = json.loads(out.read_text())
    assert telemetry.validate_chrome_trace(payload) == []
    events = payload["traceEvents"]
    spans = {e["args"]["span"]: e for e in events if e["ph"] == "X"}
    visit = next(e for e in events if e["name"] == "coordinate_visit")
    assert spans[visit["args"]["parent"]]["name"] == "outer_iteration"
    instant = next(e for e in events if e["name"] == "fault")
    assert instant["ph"] == "i"
    assert instant["args"]["span"] == visit["args"]["span"]


def test_validate_chrome_trace_flags_missing_keys():
    bad = {"traceEvents": [{"name": "x", "ph": "X", "ts": 0, "pid": 1}]}
    problems = telemetry.validate_chrome_trace(bad)
    assert any("tid" in p for p in problems)
    assert any("dur" in p for p in problems)
    assert telemetry.validate_chrome_trace({"traceEvents": []})


def test_run_log_correlates_spans_and_events(tmp_path):
    log_path = tmp_path / "run.jsonl"
    with telemetry.enabled(run_log=str(log_path), watch_compiles=False):
        with telemetry.span("coordinate_visit", coordinate="perUser"):
            telemetry.event("quarantine", action="rolled_back")
    records = [json.loads(line) for line in log_path.read_text().splitlines()]
    ev = next(r for r in records if r["kind"] == "event")
    span = next(r for r in records if r["kind"] == "span")
    assert ev["span"] == span["span"]
    assert span["attrs"]["coordinate"] == "perUser"
    assert ev["attrs"]["action"] == "rolled_back"


# --------------------------------------------------------------------------
# EventEmitter routing + listener isolation (ISSUE 8 satellite)
# --------------------------------------------------------------------------

class _Boom(EventListener):
    def handle(self, event):
        raise RuntimeError("listener exploded")


class _Sink(EventListener):
    def __init__(self):
        self.got = []

    def handle(self, event):
        self.got.append(event)


def test_listener_exception_is_isolated_from_remaining_listeners(caplog):
    emitter = EventEmitter()
    first, last = _Sink(), _Sink()
    emitter.register_listener(first)
    emitter.register_listener(_Boom())
    emitter.register_listener(last)
    with caplog.at_level(logging.ERROR, "photon_ml_tpu.utils.events"):
        emitter.send_event(TrainingStartEvent(time=1.0))
    # the raising listener neither killed emission nor starved the
    # listeners registered AFTER it
    assert len(first.got) == 1 and len(last.got) == 1
    assert any("event listener failed" in r.message for r in caplog.records)


def test_emitted_events_route_into_run_log_with_span_id(tmp_path):
    log_path = tmp_path / "run.jsonl"
    emitter = EventEmitter()
    emitter.register_listener(_Sink())
    with telemetry.enabled(run_log=str(log_path), watch_compiles=False):
        with telemetry.span("serve_batch") as batch_span:
            emitter.send_event(ScoringBatchEvent(
                time=1.0, num_requests=3, num_rows=7, bucket_size=8,
                queue_wait_s=0.001, score_s=0.002, model_version="v1"))
    records = [json.loads(line) for line in log_path.read_text().splitlines()]
    ev = next(r for r in records
              if r["name"] == "emitted.ScoringBatchEvent")
    assert ev["span"] == batch_span.span_id
    assert ev["attrs"]["num_rows"] == 7
    assert ev["attrs"]["model_version"] == "v1"


def test_emitter_without_tracer_stays_silent():
    emitter = EventEmitter()
    sink = _Sink()
    emitter.register_listener(sink)
    emitter.send_event(TrainingStartEvent(time=2.0))  # disarmed: no crash
    assert len(sink.got) == 1


def test_clear_listeners_close_hooks_run_outside_the_lock():
    """Regression for listener close hooks running under the emitter
    lock: a close() that re-enters the emitter (registering a
    replacement, clearing again) must not deadlock — the listener list
    is swapped under the lock and closed OUTSIDE it."""
    emitter = EventEmitter()
    closed = []

    class Reentrant(EventListener):
        def handle(self, event):
            pass

        def close(self):
            closed.append(True)
            emitter.register_listener(_Sink())   # takes the emitter lock

    emitter.register_listener(Reentrant())
    emitter.clear_listeners()                    # deadlocked before fix
    assert closed == [True]
    # the re-registered sink survived the clear (it landed after swap)
    emitter.send_event(TrainingStartEvent(time=3.0))


# --------------------------------------------------------------------------
# hot-path regression gates
# --------------------------------------------------------------------------

def _tiny_game(rng):
    from photon_ml_tpu.data.game_data import build_game_dataset
    n, E = 400, 20
    xg = rng.normal(size=(n, 5))
    xu = rng.normal(size=(n, 3))
    users = np.asarray([f"u{i % E}" for i in range(n)], dtype=object)
    y = (rng.uniform(size=n) < 0.5).astype(float)
    return build_game_dataset(y, {"global": xg, "per_user": xu},
                              entity_ids={"userId": users})


def _tiny_config(outer=2):
    from photon_ml_tpu.game import (FixedEffectCoordinateConfig,
                                    GameTrainingConfig,
                                    GLMOptimizationConfig,
                                    RandomEffectCoordinateConfig)
    from photon_ml_tpu.optim import (OptimizerConfig, RegularizationContext,
                                     RegularizationType)
    l2 = RegularizationContext(RegularizationType.L2)
    opt = GLMOptimizationConfig(
        optimizer=OptimizerConfig(max_iterations=4),
        regularization=l2, regularization_weight=1.0)
    return GameTrainingConfig(
        task_type="logistic_regression",
        coordinates={
            "fixed": FixedEffectCoordinateConfig("global", opt),
            "perUser": RandomEffectCoordinateConfig(
                "userId", "per_user", opt, projector="identity")},
        updating_sequence=["fixed", "perUser"],
        num_outer_iterations=outer)


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


def test_armed_telemetry_adds_zero_fresh_traces_to_a_warm_fit(rng):
    """The compile-count regression the tentpole promises: once a fit's
    shapes are warm, running the SAME fit with the tracer armed must not
    introduce a single fresh XLA trace (span names/attrs never reach a
    jit boundary), and disarmed instrumentation obviously must not
    either."""
    from photon_ml_tpu.game import GameEstimator
    ds = _tiny_game(rng)
    GameEstimator(_tiny_config()).fit(ds)  # warm every program

    with _compile_counting() as counter:
        GameEstimator(_tiny_config()).fit(ds)
    assert counter.count == 0, (
        f"{counter.count} fresh traces on a warm DISARMED fit")

    with _compile_counting() as counter:
        with telemetry.enabled(watch_compiles=False) as tracer:
            result = GameEstimator(_tiny_config()).fit(ds)
    assert counter.count == 0, (
        f"{counter.count} fresh traces on a warm ARMED fit — telemetry "
        "leaked into a trace cache key or forced a retrace")
    # the armed fit actually traced spans (it wasn't a silent no-op)
    names = {s.name for s in tracer.spans}
    assert {"fit", "outer_iteration", "coordinate_visit", "solve"} <= names
    # and the per-coordinate retrace surface reports zero everywhere
    for diag in result.descent.solver_diagnostics().values():
        assert diag["retraces"] == 0
        assert "host_blocked_s" in diag


def test_retrace_counter_counts_fresh_compiles_with_signature():
    """The PH002 runtime counterpart: a genuinely fresh compile under an
    armed compile watch increments jax.retraces and records a compile
    event carrying the triggering signature."""
    import jax
    import jax.numpy as jnp
    before = telemetry.retrace_count()
    with telemetry.enabled() as tracer:  # watch_compiles=True default
        with telemetry.span("coordinate_visit", coordinate="fresh"):
            # a shape this process has never traced (odd prime size)
            f = jax.jit(lambda x: (x * 1.000173).sum())
            float(f(jnp.zeros(1913)))
    assert telemetry.retrace_count() > before
    compiles = [e for e in tracer.events if e["name"] == "compile"]
    assert compiles, "no compile events recorded by the watch"
    assert any("1913" in e["attrs"].get("signature", "")
               for e in compiles)
    # attribution: the compile event is attached to the span that
    # triggered the trace
    visit = next(s for s in tracer.spans
                 if s.name == "coordinate_visit")
    assert any(e["span"] == visit.span_id for e in compiles)
    assert not jax.config.jax_log_compiles  # restored on disarm


def test_phase_timings_bridges_to_telemetry_spans():
    from photon_ml_tpu.telemetry.timings import PhaseTimings
    spans = PhaseTimings()
    with telemetry.enabled(watch_compiles=False) as tracer:
        with spans.span("0/fixed/solve", name="solve", coordinate="fixed",
                        iteration=0):
            pass
        with spans.blocked("0/fixed/solve"):
            pass
    assert "0/fixed/solve" in spans               # dict accounting intact
    assert spans.host_blocked["0/fixed/solve"] >= 0
    solve = next(s for s in tracer.spans if s.name == "solve")
    assert solve.attrs == {"coordinate": "fixed", "iteration": 0}
    # disarmed: the dict side keeps working with zero tracer records
    with spans.span("1/fixed/solve"):
        pass
    assert "1/fixed/solve" in spans


def test_fired_fault_lands_in_trace_with_site(tmp_path):
    from photon_ml_tpu.utils import faults
    plan = faults.FaultPlan([{"site": "stage.fetch", "action": "transient",
                              "hits": [1]}])
    before = telemetry.counter("faults.fired").value
    with telemetry.enabled(watch_compiles=False) as tracer:
        with telemetry.span("stage", chunk=0):
            with faults.injected(plan):
                with pytest.raises(faults.TransientFault):
                    faults.fire("stage.fetch", chunk=0)
    fault = next(e for e in tracer.events if e["name"] == "fault")
    assert fault["attrs"]["site"] == "stage.fetch"
    assert fault["attrs"]["action"] == "transient"
    stage = next(s for s in tracer.spans if s.name == "stage")
    assert fault["span"] == stage.span_id
    assert telemetry.counter("faults.fired").value == before + 1


def test_instrumented_hot_modules_stay_ph001_clean():
    """Armed tracing must stay off the device hot path: photonlint PH001
    (host-sync rule) over exactly the modules this PR instrumented."""
    import photon_ml_tpu
    from photon_ml_tpu.analysis.engine import lint_paths
    import os
    pkg = os.path.dirname(os.path.abspath(photon_ml_tpu.__file__))
    instrumented = [
        os.path.join(pkg, "game", "coordinate_descent.py"),
        os.path.join(pkg, "game", "quarantine.py"),
        os.path.join(pkg, "parallel", "mesh_residency.py"),
        os.path.join(pkg, "serving", "service.py"),
        os.path.join(pkg, "serving", "metrics.py"),
        os.path.join(pkg, "serving", "scorer.py"),
    ]
    findings = lint_paths(instrumented, select=["PH001", "PH007"])
    assert findings == [], "\n".join(f.render() for f in findings)


def test_serving_metrics_latency_reservoir_is_bounded():
    """ISSUE 8 satellite: ServingMetrics percentiles come from the
    registry's bounded reservoir — 100k observations cost a fixed window,
    and p50/p95/p99 all surface in snapshot()."""
    from photon_ml_tpu.serving.metrics import ServingMetrics
    m = ServingMetrics(latency_window=128)
    for i in range(100_000):
        m.observe_request(latency_s=0.001 + (i % 10) * 1e-4, rows=1)
    snap = m.snapshot(model_version="vX")
    assert snap["requests"] == 100_000
    assert snap["latency_ms"]["window"] == 128
    for key in ("p50", "p90", "p95", "p99", "max"):
        assert snap["latency_ms"][key] >= 0
    assert snap["latency_ms"]["p99"] >= snap["latency_ms"]["p50"]
    assert snap["model_version"] == "vX"
    prom = m.prometheus(model_version="vX")
    assert "photon_serving_requests_total 100000" in prom
    assert 'photon_serving_latency_s{quantile="0.95"}' in prom


# --------------------------------------------------------------------------
# one clock: program spans in the profiler's trace, programs named for their
# layer, always-on trace/lower/compile counters (ISSUE 25)
# --------------------------------------------------------------------------

def _profiled_host_events(trace_dir):
    """[(name, start_ns, end_ns)] of the host planes of the newest
    `*.xplane.pb` under `trace_dir`."""
    import glob
    import os

    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


def test_phase_timings_and_annotate_reach_the_profiler_trace(tmp_path):
    """A PhaseTimings span and an `annotate` leaf inside it are
    `photon/<name>` events of the profiler's host plane, nested, and the
    dict is charged under the same key, no tracer armed."""
    import jax
    from photon_ml_tpu.telemetry.timings import PhaseTimings
    spans = PhaseTimings()
    assert not telemetry.armed()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with spans.span("0/x/solve", name="solve", coordinate="x"):
            with telemetry.annotate("re/dispatch"):
                jax.block_until_ready(jax.numpy.ones(8) + 1)
    finally:
        jax.profiler.stop_trace()
    assert list(spans) == ["0/x/solve"] and spans["0/x/solve"] > 0
    events = _profiled_host_events(str(tmp_path))
    (outer,) = [ev for ev in events if ev[0] == "photon/0/x/solve"]
    (inner,) = [ev for ev in events if ev[0] == "photon/re/dispatch"]
    assert outer[1] <= inner[1] and inner[2] <= outer[2]
    # what the dict was charged is what the annotation spans (both ends
    # are taken within the same few Python statements)
    assert abs((outer[2] - outer[1]) * 1e-9 - spans["0/x/solve"]) < 0.05


def test_spans_cost_nothing_more_without_a_profiler_session():
    """No profiler, no tracer: the dict is charged all the same, the leaf
    is a bare TraceMe (no record anywhere), and `telemetry.span()` is
    still the shared no-op."""
    from photon_ml_tpu.telemetry.timings import PhaseTimings
    assert not telemetry.armed()
    spans = PhaseTimings()
    with spans.span("0/x/solve", host_blocked=True):
        with telemetry.annotate("re/dispatch") as leaf:
            pass
    assert list(spans) == ["0/x/solve"] and spans["0/x/solve"] >= 0
    assert spans.host_blocked == {"0/x/solve": spans["0/x/solve"]}
    assert telemetry.span("anything") is telemetry.NOOP_SPAN
    assert leaf is not telemetry.NOOP_SPAN   # JAX is importable here


def test_armed_span_and_profiler_give_one_timeline(tmp_path):
    """With the tracer armed, a PhaseTimings span is ONE annotation, under
    the dict's key, and a plain `telemetry.span` is annotated under its
    name: `--trace-out X --profile-dir Y` agree."""
    import jax
    from photon_ml_tpu.telemetry.timings import PhaseTimings
    spans = PhaseTimings()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with telemetry.enabled(watch_compiles=False) as tracer:
            with spans.span("1/y/solve", name="solve", coordinate="y"):
                with telemetry.span("checkpoint_write", iteration=1):
                    pass
    finally:
        jax.profiler.stop_trace()
    assert [s.name for s in tracer.spans] == ["checkpoint_write", "solve"]
    names = [ev[0] for ev in _profiled_host_events(str(tmp_path))
             if ev[0].startswith("photon/")]
    assert sorted(names) == ["photon/1/y/solve", "photon/checkpoint_write"]


def test_solve_programs_are_named_for_their_layer():
    """The module name is what a profiler trace (and the persistent cache's
    key) carries: `jit_fe_solve` and `jit_re_bucket_solve`, from one cached
    wrapper per static signature."""
    import jax.numpy as jnp
    from photon_ml_tpu.ops import TASK_LOSSES, GLMObjective
    from photon_ml_tpu.optim import OptimizerConfig, RegularizationContext
    from photon_ml_tpu.parallel.fixed_effect import _cached_solver
    from photon_ml_tpu.parallel.random_effect import _cached_batched_solver
    loss = TASK_LOSSES["logistic_regression"]
    config, reg = OptimizerConfig(max_iterations=3), RegularizationContext()
    E, S, d = 4, 8, 3
    batched = _cached_batched_solver(loss, config, reg, False, True)
    assert batched is _cached_batched_solver(loss, config, reg, False, True)
    lowered = batched.lower(
        jnp.ones((E, S, d)), jnp.ones((E, S)), jnp.ones((E, S)), None,
        jnp.zeros((E, S)), jnp.zeros((E, d)), jnp.asarray(1.0), None)
    assert lowered.as_text().startswith("module @jit_re_bucket_solve ")
    solver = _cached_solver(config, reg)
    assert solver is _cached_solver(config, reg)
    lowered = solver.lower(GLMObjective(loss, jnp.ones((S, d)), jnp.ones(S)),
                           jnp.zeros(d), jnp.asarray(1.0), None)
    assert lowered.as_text().startswith("module @jit_fe_solve ")


def _jax_counters():
    counters = telemetry.snapshot()["metrics"]["counters"]
    return {k: v for k, v in counters.items() if k.startswith("jax.")}


def test_trace_lower_compile_counters_are_always_on():
    """One process-wide listener, no tracer armed: a fresh jit call is one
    trace, one lowering, one backend compile; a repeat call is none.
    CompileTimeTracker still counts backend compiles only."""
    import jax
    from jax._src import monitoring
    from photon_ml_tpu.utils import jax_cache
    jax_cache.install_compile_counters()
    listeners = len(monitoring.get_event_time_span_listeners())
    jax_cache.install_compile_counters()
    jax_cache.enable_persistent_cache()
    assert len(monitoring.get_event_time_span_listeners()) == listeners
    assert monitoring.get_event_time_span_listeners().count(
        jax_cache._publish) == 1

    x = jax.numpy.ones(1789)            # a shape no other test uses
    tracker = jax_cache.CompileTimeTracker().install()
    before = _jax_counters()
    f = jax.jit(lambda v: jax.lax.neg(v))   # no jitted function inside
    jax.block_until_ready(f(x))
    after = _jax_counters()
    for count in ("jax.traces", "jax.lowerings", "jax.backend_compiles"):
        assert after[count] == before.get(count, 0) + 1, count
    for seconds in ("jax.trace_s", "jax.lower_s", "jax.backend_compile_s"):
        assert after[seconds] > before.get(seconds, 0.0), seconds
    # the tracker is the backend-compile counters since its install()
    assert tracker.count == 1
    assert tracker.seconds == pytest.approx(
        after["jax.backend_compile_s"] - before["jax.backend_compile_s"])
    assert jax_cache.CompileTimeTracker().install().count == 0
    jax.block_until_ready(f(x))
    assert _jax_counters() == after and tracker.count == 1


def test_nested_traces_charge_each_second_once():
    """Tracing a function traces the jitted functions it calls: many trace
    events, nested. `jax.trace_s` takes the inner ones out of the outer, so
    trace + lower + compile seconds stay within the call's wall time."""
    import time

    import jax
    import jax.numpy as jnp

    def nested(v):
        return jnp.where(v > 0, jnp.sum(v * v), jnp.linalg.norm(v)) \
            + jnp.arange(1787.0).sum()

    x = jnp.ones(1787)
    before = _jax_counters()
    t0 = time.time()
    jax.block_until_ready(jax.jit(nested)(x))
    wall = time.time() - t0
    after = _jax_counters()
    assert after["jax.traces"] - before["jax.traces"] > 1
    spent = sum(after[k] - before[k] for k in
                ("jax.trace_s", "jax.lower_s", "jax.backend_compile_s"))
    assert 0 < spent <= wall


def test_a_second_identical_fit_traces_nothing(rng):
    """What an operator reads `jax.traces` for: a nightly refit of the same
    shapes moves it by nothing, no tracer armed, no log scraped."""
    from photon_ml_tpu.game import GameEstimator
    ds = _tiny_game(rng)
    GameEstimator(_tiny_config()).fit(ds)
    after_first = _jax_counters()
    GameEstimator(_tiny_config()).fit(ds)
    assert _jax_counters() == after_first


# --------------------------------------------------------------------------
# cli.train --trace-out / --run-log / --fault-plan, end to end
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cli_trace_run(tmp_path_factory):
    """One `cli.train` run of a two-coordinate GAME fit, two outer
    iterations, with the tracer armed by --trace-out and --run-log and the
    second solve (perUser, outer iteration 0) poisoned by --fault-plan.
    Run once; the tests below read what it wrote."""
    from photon_ml_tpu.cli.train import main as train_main
    from photon_ml_tpu.data.game_data import save_game_dataset
    tmp = tmp_path_factory.mktemp("cli_trace")
    data = str(tmp / "train.npz")
    save_game_dataset(_tiny_game(np.random.default_rng(17)), data)
    (tmp / "game.json").write_text(_tiny_config(outer=2).to_json())
    out = tmp / "out"
    rc = train_main([
        "--train-data", data, "--task", "logistic_regression",
        "--config", str(tmp / "game.json"), "--output-dir", str(out),
        "--mesh", "none", "--trace-out", str(out / "trace.json"),
        "--run-log", str(out / "run-log.jsonl"),
        "--checkpoint-dir", str(tmp / "ckpt"),
        "--fault-plan", json.dumps({"faults": [
            {"site": "solve.poison", "action": "poison", "hits": [2]}]})])
    return {
        "rc": rc,
        "trace": json.loads((out / "trace.json").read_text()),
        "records": [json.loads(line) for line in
                    (out / "run-log.jsonl").read_text().splitlines()],
        "summary": json.loads((out / "training-summary.json").read_text()),
    }


def test_cli_trace_out_is_a_valid_chrome_trace(cli_trace_run):
    assert cli_trace_run["rc"] == 0
    assert telemetry.validate_chrome_trace(cli_trace_run["trace"]) == []
    assert cli_trace_run["records"]


def test_cli_trace_nests_outer_iterations_visits_and_solves(cli_trace_run):
    """The exported span TREE, by the args.span / args.parent ids: two
    outer iterations, a visit per coordinate inside each, every solve
    inside a visit, and the checkpoint writes present."""
    spans = {e["args"]["span"]: e
             for e in cli_trace_run["trace"]["traceEvents"]
             if e.get("ph") == "X" and "span" in e.get("args", {})}
    by_name = {}
    for e in spans.values():
        by_name.setdefault(e["name"], []).append(e)

    def parent_name(e):
        parent = spans.get(e["args"].get("parent"))
        return parent["name"] if parent else None

    assert len(by_name["outer_iteration"]) == 2
    assert len(by_name["coordinate_visit"]) == 4
    assert {parent_name(e) for e in by_name["coordinate_visit"]} == {
        "outer_iteration"}
    assert by_name["solve"]
    assert {parent_name(e) for e in by_name["solve"]} == {"coordinate_visit"}
    assert by_name.get("checkpoint_write") or by_name.get("checkpoint")


def test_cli_run_log_attributes_the_fault_to_its_coordinate_visit(
        cli_trace_run):
    """The injected solve.poison is an event under the perUser visit's
    span chain, its quarantine is logged, and the summary says the retry
    recovered."""
    records = cli_trace_run["records"]
    spans = {r["span"]: r for r in records if r["kind"] == "span"}

    def visit_coordinate(record):
        sid = record["span"]
        while sid is not None and sid in spans:
            if spans[sid]["name"] == "coordinate_visit":
                return spans[sid]["attrs"].get("coordinate")
            sid = spans[sid]["parent"]
        return None

    fired = [r for r in records
             if r["kind"] == "event" and r["name"] == "fault"]
    assert [visit_coordinate(r) for r in fired] == ["perUser"]
    assert any(r["kind"] == "event" and r["name"] == "quarantine"
               for r in records)
    diagnostics = cli_trace_run["summary"]["solver_diagnostics"]
    assert "retry_ok" in diagnostics["perUser"]["containment"]
