"""From a traced run to the per-entity solve's own count of its lock step,
per traced whole fit.

Every run of `jit_re_bucket_solve` (one bucket of one coordinate's visit)
counts its lock step on the device, and the descent's flush reads the
counts in its one batched read and puts them on the host plane of the
trace as one zero-length `photon/re/lockstep` event a run, the counts as
the event's arguments (`game/coordinate_descent.py::_mark_lock_step`,
PR 37): `coordinate`, `visit`, `run`, the bucket's `entities` x `samples`,
and `lanes`, `trips`, `lane_iterations`, `lockstep_trials`,
`running_trials`, `data_passes` (`photon_ml_tpu/optim/types.py::LOCKSTEP`
says what each counts). Nothing here is timed: the trace only places each
event inside the `bench/fit` mark of the fit it belongs to, so a fit run
after tracing stopped (the factored cell's check replays one) cannot leak
into a traced fit's counts.

All but `read_events` is arithmetic on (name, start, end, stats) tuples and
is checked on hand-made ones (tests/test_benchmark_lockstep.py).
"""
from __future__ import annotations

import functools
import glob
import os
import statistics

EVENT = "photon/re/lockstep"
FIT_MARK = "bench/fit"
HERE = os.path.dirname(os.path.abspath(__file__))


def read_events(path: str):
    """[(name, start_s, end_s, {argument: value})] of the host planes'
    `bench/fit` marks and `photon/re/lockstep` events in one
    `*.xplane.pb`."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in (EVENT, FIT_MARK):
                    out.append((ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9,
                                dict(ev.stats)))
    return out


def per_fit(events):
    """For each `bench/fit` mark in time order, the arguments of the
    `photon/re/lockstep` events that start inside it; events outside
    every mark are dropped."""
    fits = sorted((s, e) for name, s, e, _ in events if name == FIT_MARK)
    return [[stats for name, s, _, stats in sorted(events,
                                                   key=lambda ev: ev[1])
             if name == EVENT and lo <= s < hi] for lo, hi in fits]


def trace_path(record):
    """The run's `*.xplane.pb`: the one `trace_reduce` read, else the
    newest under the directory `run.py` traces the cell into (a CPU
    rehearsal has no device plane, so `trace_reduce` gives no record)."""
    if record.get("trace"):
        return record["trace"]["path"]
    cell = record.get("cell", {}).get("name")
    paths = sorted(glob.glob(os.path.join(
        HERE, "out", "trace", cell, "plugins", "profile", "*",
        "*.xplane.pb"))) if cell else []
    return paths[-1] if paths else None


@functools.lru_cache(maxsize=2)
def _fits(path):
    return per_fit(read_events(path))


def median_per_fit(record, reduce_runs):
    """Median over the traced fits of `reduce_runs(runs)`, `runs` a fit's
    lock-step events' arguments; None where no fit was traced or a traced
    fit has no such event (a commit that does not count its lock step)."""
    path = trace_path(record)
    fits = _fits(path) if path else []
    if not fits or not all(fits):
        return None
    return statistics.median(reduce_runs(runs) for runs in fits)


def total(runs, key):
    return sum(int(run[key]) for run in runs)


def trips(runs):
    """Trips of the vmapped loop over a fit's runs."""
    return total(runs, "trips")


def lane_occupancy(runs):
    """Percent of the lane-trips a fit's runs held that a lane still
    running filled: 100 less it is the most that compacting ended lanes
    away can take off the trips' work."""
    held = sum(int(run["lanes"]) * int(run["trips"]) for run in runs)
    return 100.0 * total(runs, "lane_iterations") / held


def ended_trial_share(runs):
    """Percent of the lock-step trial values a fit's runs evaluated that no
    running lane needed: what the batched line search ran for lanes that
    had ended."""
    return 100.0 * (1.0 - total(runs, "running_trials")
                    / total(runs, "lockstep_trials"))
