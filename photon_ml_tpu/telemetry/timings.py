"""PhaseTimings: contiguous per-fit span accounting, bridged into the
span tracer.

Moved here from game/coordinate_descent.py: photonlint PH007 forbids raw
`time.perf_counter()` span timing inside the hot-path modules, and this is
the ONE sanctioned implementation — every timed phase of a fit lands both
in the per-fit dict (the cli summary and the benchmark's `build_s.fit` /
`descent_s.fit`, armed or not), in
any JAX profiler trace as the annotation `photon/<label>` and, when the
tracer is armed, in the hierarchical trace as a named span.

`clock()` is the sanctioned raw timestamp for hot modules that need a
bare duration.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict

from photon_ml_tpu.telemetry import core as _core


def clock() -> float:
    """Monotonic high-resolution seconds (the telemetry time base)."""
    return time.perf_counter()


class PhaseTimings(dict):
    """Accumulating span timer (reference: Timer/Timed spans at every driver
    stage, photon-lib/.../util/Timer.scala:32-234 used ~30x).  Spans are
    CONTIGUOUS over the descent loop so their sum accounts for the whole
    fit wall-clock — an unattributed gap means an untimed stage
    (tests/test_game.py holds the span sum to the fit's wall-clock).

    `host_blocked` tracks, per span label, the seconds the host spent
    BLOCKED on device readbacks (scalar syncs, `float()` objective fetches,
    [n]-array transfers into numpy evaluators, the pipelined boundary
    flush).  host_blocked_total()/wall is the host-blocked fraction — the quantity pipelining exists to shrink; it also
    lands in the `train.host_blocked_s`/`train.host_blocked_frac` gauges
    at fit end (game/coordinate_descent.py).

    When the tracer is armed, `span(label, name=..., **attrs)` also emits
    a telemetry span (`name` defaults to the label) so the per-fit dict
    and the exported timeline are the same measurement, not two.  Either
    way the region is the profiler annotation `photon/<label>`.  A region
    INSIDE a span that a profiler trace should name takes a bare
    `telemetry.annotate(...)`, never a nested key: nested keys would break
    the sum-equals-wall contract above."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self.host_blocked: Dict[str, float] = {}

    @contextlib.contextmanager
    def span(self, label: str, host_blocked: bool = False,
             name: str = None, **attrs):
        # one profiler annotation `photon/<label>` either way: the armed
        # span enters it itself, under the key the dict is charged with
        tracer = _core.active_tracer()
        region = (_core.annotate(label) if tracer is None else tracer.span(
            name if name is not None else label, attrs, label=label))
        t0 = clock()
        try:
            with region:
                yield
        finally:
            dt = clock() - t0
            self[label] = self.get(label, 0.0) + dt
            if host_blocked:
                self.add_blocked(label, dt)

    @contextlib.contextmanager
    def blocked(self, label: str):
        """Time a host-blocking readback into `host_blocked` WITHOUT
        opening a new accounting span (the enclosing span already covers
        the wall time)."""
        t0 = clock()
        try:
            yield
        finally:
            self.add_blocked(label, clock() - t0)

    def add_blocked(self, label: str, seconds: float) -> None:
        self.host_blocked[label] = self.host_blocked.get(label, 0.0) + seconds

    def host_blocked_total(self) -> float:
        return float(sum(self.host_blocked.values()))

    def total(self) -> float:
        return float(sum(self.values()))
