"""Device seconds of the one-lane solve program, `jit_fe_solve`, by
COORDINATE, per traced whole fit.

A factored random effect refits its shared projection through the fixed
effect's solver, so the trace's module names cannot tell that refit from the
fixed effect's own solve. The host can, by `coordinate_reduce.py`'s rule for
the per-entity solve: the jitted call of each run is made in a
`photon/fe/dispatch` span (`span_reduce.match_calls` pairs the k-th run with
the k-th such span), and that span lies inside the `photon/{iteration}/
{coordinate}/solve` span of the coordinate whose update made the call.

`split` is `coordinate_reduce.split` with the program as an argument; it is
interval arithmetic on (name, start, end) tuples and is checked on hand-made
ones (tests/test_benchmark_game_mf.py). A commit whose projection refit
opens no `fe/dispatch` span has more runs than spans, `match_calls` leaves
the program unmatched, and everything here reads None.
"""
from __future__ import annotations

import bisect
import functools
import os
import statistics

from benchmark import span_reduce as sr
from benchmark.coordinate_reduce import CLOSURE, SOLVE_SPAN
from benchmark.trace_reduce import (MODULES_LINE, OPS_LINE, busy_seconds,
                                    merge, read_trace)


def split(ops, modules, host, lo, hi, program=sr.FE_SOLVE):
    """{coordinate: device seconds inside the runs of `program` whose call
    was made in that coordinate's solve span} within [lo, hi], for ONE
    device plane (`coordinate_reduce.split` has the arguments). None where a
    run cannot be placed (no call span, or no solve span around it)."""
    runs = sorted(((sr.program_name(name), max(s, lo), min(e, hi))
                   for name, s, e in modules if e > lo and s < hi),
                  key=lambda run: run[1])
    spans = [ev for ev in host if ev[0].startswith(sr.SPAN)
             and ev[2] > lo and ev[1] < hi]
    calls = sr.match_calls(runs, spans)
    solves = [(m.group(1), s, e) for name, s, e in spans
              for m in [SOLVE_SPAN.match(name)] if m]
    in_fit = sr.within(ops, lo, hi)
    starts = [s for s, _ in in_fit]
    out = {}
    for i, (name, s, e) in enumerate(runs):
        if name != program:
            continue
        if i not in calls:
            return None
        opened = calls[i][0]
        owners = [owner for owner, a, b in solves if a <= opened < b]
        if len(owners) != 1:
            return None
        out[owners[0]] = out.get(owners[0], 0.0) + busy_seconds(
            in_fit[max(0, bisect.bisect_right(starts, s) - 1):
                   bisect.bisect_left(starts, e)], s, e)
    return out


@functools.lru_cache(maxsize=2)
def _read(path, fits):
    # <trace_dir>/plugins/profile/<time>/<host>.xplane.pb
    trace_dir = path
    for _ in range(4):
        trace_dir = os.path.dirname(trace_dir)
    trace = read_trace(trace_dir)
    if trace is None:
        return []
    planes = [(merge((s, e) for _, s, e in lines.get(OPS_LINE) or []),
               lines.get(MODULES_LINE) or [])
              for _, lines in sorted(trace["devices"].items())
              if lines.get(OPS_LINE)]
    out = []
    for lo, hi in fits:
        per_plane = [split(ops, modules, trace["host"], lo, hi)
                     for ops, modules in planes]
        if not per_plane or any(p is None for p in per_plane):
            out.append(None)
            continue
        names = {name for p in per_plane for name in p}
        out.append({name: sum(p.get(name, 0.0) for p in per_plane)
                    / len(per_plane) for name in names})
    return out


def fe_solve_seconds(record, coordinate):
    """Median over the traced fits of the device seconds of `jit_fe_solve`
    that belong to `coordinate`. None where there is no device trace, where
    a fit has no such coordinate or a run that cannot be placed, or where
    the coordinates do not add up to `fe_solve_device_s.fit`'s figure for
    the fit to CLOSURE."""
    trace = record.get("trace")
    reduced = sr.fits_of(record)
    if not trace or not reduced:
        return None
    by_coordinate = _read(trace["path"], tuple(
        (m["start"], m["end"]) for m in trace["marks"]
        if m["name"] == sr.FIT_MARK and m["busy_s"] > 0))
    values = []
    for fit, mine in zip(reduced, by_coordinate):
        whole = sr.solve_seconds(fit, sr.FE_SOLVE)
        if (mine is None or whole is None or coordinate not in mine
                or abs(sum(mine.values()) - whole) > CLOSURE * whole):
            return None
        values.append(mine[coordinate])
    return statistics.median(values) if values else None
