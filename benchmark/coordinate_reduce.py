"""Device seconds of the per-entity solve program by COORDINATE, per traced
whole fit.

Every random-effect coordinate runs the same program, `jit_re_bucket_solve`,
so the trace's module names cannot tell the per-user solves from the
per-item ones. The host can: the jitted call of each run is made in a
`photon/re/dispatch` span (`span_reduce.match_calls` pairs the k-th run with
the k-th such span), and that span lies inside the `photon/{iteration}/
{coordinate}/solve` span of the coordinate whose update made the call, on
the same thread and the same clock. Host spans of one thread nest, so the
call's start falls in exactly one of them: time alone places a run, in the
pipelined descent too (the DEVICE may run it much later; the call does not
move).

`split` is interval arithmetic on (name, start, end) tuples and is checked
on hand-made ones (tests/test_benchmark_user_item.py).
"""
from __future__ import annotations

import bisect
import functools
import os
import re
import statistics

from benchmark import span_reduce as sr
from benchmark.trace_reduce import (MODULES_LINE, OPS_LINE, busy_seconds,
                                    merge, read_trace)

SOLVE_SPAN = re.compile("^" + re.escape(sr.SPAN) + r"\d+/(.+)/solve$")
#: the coordinates' seconds have to add up to the program's this closely
CLOSURE = 0.01


def split(ops, modules, host, lo, hi):
    """{coordinate: device seconds inside the runs of the per-entity solve
    whose call was made in that coordinate's solve span} within [lo, hi],
    for ONE device plane: `ops` the merged op intervals, `modules`
    [(name, start, end)] its program runs, `host` the host's events. None
    where a run cannot be placed (no call span, or no solve span around
    it)."""
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
    for i, (program, s, e) in enumerate(runs):
        if program != sr.RE_SOLVE:
            continue
        if i not in calls:
            return None
        opened = calls[i][0]
        owners = [name for name, a, b in solves if a <= opened < b]
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


def re_solve_seconds(record, coordinate):
    """Median over the traced fits of the device seconds of the per-entity
    solve that belong to `coordinate`. None where there is no device trace,
    where a fit has no such coordinate or a run that cannot be placed, or
    where the coordinates do not add up to `re_solve_device_s.fit`'s figure
    for the fit to CLOSURE."""
    trace = record.get("trace")
    reduced = sr.fits_of(record)
    if not trace or not reduced:
        return None
    by_coordinate = _read(trace["path"], tuple(
        (m["start"], m["end"]) for m in trace["marks"]
        if m["name"] == sr.FIT_MARK and m["busy_s"] > 0))
    values = []
    for fit, mine in zip(reduced, by_coordinate):
        whole = sr.solve_seconds(fit, sr.RE_SOLVE)
        if (mine is None or whole is None or coordinate not in mine
                or abs(sum(mine.values()) - whole) > CLOSURE * whole):
            return None
        values.append(mine[coordinate])
    return statistics.median(values) if values else None
