"""Unified telemetry: span tracing, a metrics registry, and exportable
run timelines across training and serving.

Three layers, one import:

  * the SPAN TRACER (`core`) — `telemetry.span(name, **attrs)` produces a
    hierarchical, thread-aware trace of a run (outer iterations ->
    coordinate visits -> inner solves / chunk staging / checkpoint writes
    / serving batches), with `utils.faults.fire()`-style disarm semantics:
    a module-global None check and a shared no-op singleton when off —
    zero traces, zero device reads, nothing allocated.  PhaseTimings spans
    and `telemetry.annotate(name)` leaves are also `photon/<name>`
    annotations in any JAX profiler trace, armed or not, so the host's
    spans and the device's programs share the profiler's clock.
  * the METRICS REGISTRY (`metrics`) — counters/gauges/bounded-reservoir
    histograms that the existing accounting surfaces (PhaseTimings'
    host-blocked time, StreamStats, TransferStats, ServingMetrics,
    quarantine/containment events, checkpoint/retry counters, the
    always-on `jax.traces`/`jax.lowerings`/`jax.backend_compiles` counters
    and their seconds from utils/jax_cache.py) publish through, so ONE
    `telemetry.snapshot()` returns everything.  Always live (an increment
    costs what the bespoke accumulators already cost).
  * EXPORTERS (`export`) — Chrome-trace/Perfetto JSON (`--trace-out` on
    cli.train), a JSONL run log correlated with EventEmitter
    events and fault/quarantine/recovery records by span id, and
    Prometheus text exposition (mounted at `/metrics` on the serving HTTP
    service).

Arming:

    tracer = telemetry.install(run_log="out/run-log.jsonl")
    ... run the fit ...
    telemetry.write_chrome_trace("out/trace.json")
    telemetry.shutdown()

or scoped: `with telemetry.enabled() as tracer: ...`.

photonlint PH007 enforces that hot-path modules time spans through this
package (PhaseTimings / `timings.clock()`), never raw
`time.perf_counter()` — one trace, not thirty stopwatches.
"""
from photon_ml_tpu.telemetry.core import (  # noqa: F401
    MAX_RECORDS, NOOP_SPAN, SpanRecord, Tracer, active_tracer, annotate,
    armed, current_span_id, enabled, event, install, last_tracer, mark, pop,
    push, retrace_count, set_observer, shutdown, span,
)
from photon_ml_tpu.telemetry.export import (  # noqa: F401
    CHROME_REQUIRED_KEYS, chrome_trace_events, prometheus_text,
    render_prometheus_snapshot, validate_chrome_trace,
)
from photon_ml_tpu.telemetry.export import (
    write_chrome_trace as _write_chrome_trace,
)
from photon_ml_tpu.telemetry.metrics import (  # noqa: F401
    Counter, Gauge, Histogram, LabeledCounter, MetricsRegistry, counter,
    default_registry, gauge, histogram,
)
from photon_ml_tpu.telemetry import distributed, events, flight  # noqa: F401
from photon_ml_tpu.telemetry.timings import PhaseTimings, clock  # noqa: F401

# collectors: named callables whose dict results ride along in snapshot()
# (a ScoringService registers its metrics snapshot here so one call
# returns training AND serving state); unregister on close.
_COLLECTORS = {}


def register_collector(name: str, fn) -> None:
    _COLLECTORS[name] = fn


def unregister_collector(name: str) -> None:
    _COLLECTORS.pop(name, None)


def snapshot() -> dict:
    """Everything: the default registry's instruments, every registered
    collector, and (when a tracer is or was armed) its record counts.
    All values JSON-safe — this dict lands verbatim in training-summary.json."""
    out = {"metrics": default_registry().snapshot()}
    for name, fn in sorted(_COLLECTORS.items()):
        try:
            out[name] = fn()
        except Exception as e:  # a dead collector must not kill a snapshot
            out[name] = {"error": f"{type(e).__name__}: {e}"}
    tracer = last_tracer()
    if tracer is not None:
        out["tracer"] = tracer.stats()
    return out


def write_chrome_trace(path: str, tracer=None) -> dict:
    """Export the active (or most recently finished) tracer's timeline."""
    tracer = tracer if tracer is not None else last_tracer()
    if tracer is None:
        raise RuntimeError("no tracer has been installed this process — "
                           "call telemetry.install() before the run")
    return _write_chrome_trace(tracer, path)
