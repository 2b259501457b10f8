"""From a traced run to device seconds by PROGRAM and device-idle seconds by
what the DEVICE WAITED FOR, per traced whole fit.

`trace_reduce.py` names device time by op (`fusion.23 f32[30518]`) and idle
gaps by the host event that overlaps them most. This reads the same
`*.xplane.pb` for what the program itself says:

- the `XLA Modules` line has one event per program run, named
  `jit_<function>(<fingerprint>)`. A module's name is part of the persistent
  compile cache's key, so it is the same from a cold and from a cached
  executable (a `jax.named_scope` is debug info, which the key strips: a
  cached executable keeps the op names it was compiled with). Device seconds
  of a program are the busy seconds of the `XLA Ops` union inside its runs;
  the ops outside every run are counted on their own, so that the programs
  adding up to `trace_reduce`'s busy seconds is a check and not a definition
  (runs that overlapped would add up to more);
- the program's own `photon/*` TraceAnnotations (PhaseTimings spans and
  `telemetry.annotate` leaves) are on the host plane, on the same clock. An
  idle gap belongs to the next SOLVE program the device runs, at the gap's
  end or after it (the device runs what the host enqueues in order, so
  whatever ends the gap was enqueued no later than that solve). The host
  span that solve's jitted call was made in (CALLS) cuts the gap in three:
  before the call opened the device waits for the host, and that part is
  named by the INNERMOST `photon/*` span that holds its middle; while the
  call is open it is `call of <program>`; after the call returned the solve
  and all before it are enqueued and the device waits for their operands:
  `operands of <program>`. In the pipelined descent the host is by then
  several calls further on, so the host's span at that moment says nothing
  of what the device waits for. A gap with no solve after it in the fit is
  named by the innermost span whole; an op-free hole in a run is
  `inside <program>`.

Everything but `fits_of`'s one read is interval arithmetic on (name, start,
end) tuples and is checked on hand-made ones (tests/test_benchmark_spans.py).
"""
from __future__ import annotations

import bisect
import functools
import os
import re
import statistics
from collections import defaultdict

from benchmark.trace_reduce import (MODULES_LINE, OPS_LINE, busy_seconds,
                                    gaps, merge, read_trace)

FIT_MARK = "bench/fit"
SPAN = "photon/"
NO_SPAN = "no program span"
NO_PROGRAM = "outside every program"
FINGERPRINT = re.compile(r"\(\d+\)$")
RE_SOLVE, FE_SOLVE = "jit_re_bucket_solve", "jit_fe_solve"
#: solve program -> the leaf annotation its jitted call is made in
CALLS = {RE_SOLVE: "re/dispatch", FE_SOLVE: "fe/dispatch"}
CALL, OPERANDS, INSIDE = "call of ", "operands of ", "inside "
#: the programs of a fit have to add up to its busy seconds this closely
CLOSURE = 0.01


def program_name(event: str) -> str:
    """'jit_fe_solve(1234567890)' -> 'jit_fe_solve'."""
    return FINGERPRINT.sub("", event)


def innermost(spans, t):
    """Name of the shortest (name, start, end) span that holds time t."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else None


def span_pieces(spans):
    """(cuts, names): between cuts[i] and cuts[i + 1] the innermost span is
    names[i] (None where no span is open), so that naming a gap is one
    bisection and not a walk over every span."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    return cuts, [innermost(spans, 0.5 * (a + b))
                  for a, b in zip(cuts, cuts[1:])]


def within(merged, lo, hi):
    """The part of a sorted disjoint interval list that can touch [lo, hi]."""
    return merged[bisect.bisect_right([e for _, e in merged], lo):
                  bisect.bisect_left([s for s, _ in merged], hi)]


def match_calls(runs, spans):
    """{index into `runs`: (start, end) of the host span that run's call was
    made in}: the k-th run of a solve program with the k-th span of its
    CALLS name. A program whose runs and spans differ in number, or one of
    whose runs starts before its call opens, is left unmatched."""
    out = {}
    for program, span in CALLS.items():
        mine = [i for i, run in enumerate(runs) if run[0] == program]
        made = sorted((s, e) for name, s, e in spans if name == SPAN + span)
        if len(mine) == len(made) and all(
                call[0] <= runs[i][1] for i, call in zip(mine, made)):
            out.update(zip(mine, made))
    return out


def idle_causes(idle, runs, spans):
    """{cause: seconds} of the idle gaps [(start, end)] of one device, given
    its program runs [(program, start, end)], sorted and disjoint, and the
    host's photon/* spans (the module's docstring has the rule)."""
    cuts, names = span_pieces(spans)
    calls = match_calls(runs, spans)
    ends = [e for _, _, e in runs]
    solve_after, upcoming = [], None    # per run: the next run with a call
    for i in reversed(range(len(runs))):
        upcoming = i if i in calls else upcoming
        solve_after.append(upcoming)
    solve_after.reverse()
    out = defaultdict(float)

    def host(s, e):
        i = bisect.bisect_right(cuts, 0.5 * (s + e)) - 1
        return (names[i] if 0 <= i < len(names) else None) or NO_SPAN

    for s, e in idle:
        middle = 0.5 * (s + e)
        i = bisect.bisect_right(ends, middle)   # the first run still to end
        if i < len(runs) and runs[i][1] <= middle:
            out[INSIDE + runs[i][0]] += e - s
            continue
        solve = solve_after[i] if i < len(runs) else None
        if solve is None:       # nothing the host calls by name follows
            out[host(s, e)] += e - s
            continue
        opened, returned = calls[solve]
        program = runs[solve][0]
        for cause, a, b in ((None, s, min(e, opened)),
                            (CALL + program, max(s, opened), min(e, returned)),
                            (OPERANDS + program, max(s, returned), e)):
            if b > a:
                out[cause or host(a, b)] += b - a
    return dict(out)


def reduce_fits(devices, host, fits):
    """One dict per (start, end) of `fits`:

    `busy_s`: device busy seconds, as trace_reduce counts them (the union of
    the ops line, averaged over the device planes that ran anything);
    `programs`: {program: device seconds inside its runs}, with NO_PROGRAM
    for the ops outside every run, and `program_runs`: {program: runs, over
    all device planes}; `idle_s` and `idle`: {cause: idle seconds of the
    first device plane} (idle_causes); `spans`: how many photon/* spans the
    fit has."""
    planes = []
    for _, lines in sorted(devices.items()):
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        if ops:
            planes.append((merge((s, e) for _, s, e in ops),
                           lines.get(MODULES_LINE) or []))
    out = []
    for lo, hi in fits:
        if not planes:
            break
        programs, runs, busy = defaultdict(float), defaultdict(int), 0.0
        in_fits = [within(merged, lo, hi) for merged, _ in planes]
        plane_runs = [sorted(((program_name(name), max(s, lo), min(e, hi))
                              for name, s, e in modules if e > lo and s < hi),
                             key=lambda run: run[1])
                      for _, modules in planes]
        for in_fit, mine in zip(in_fits, plane_runs):
            starts = [s for s, _ in in_fit]

            def ops_in(s, e):
                return busy_seconds(
                    in_fit[max(0, bisect.bisect_right(starts, s) - 1):
                           bisect.bisect_left(starts, e)], s, e)

            for name, s, e in mine:
                programs[name] += ops_in(s, e)
                runs[name] += 1
            busy += ops_in(lo, hi)
            programs[NO_PROGRAM] += ops_in(lo, hi) - sum(
                ops_in(s, e) for s, e in merge((s, e) for _, s, e in mine))
        programs = {k: v / len(planes) for k, v in programs.items()
                    if k != NO_PROGRAM or v > 1e-6 * busy}
        spans = [ev for ev in host if ev[0].startswith(SPAN)
                 and ev[2] > lo and ev[1] < hi]
        idle = idle_causes(gaps(in_fits[0], lo, hi), plane_runs[0], spans)
        out.append({"busy_s": busy / len(planes), "programs": programs,
                    "program_runs": dict(runs),
                    "idle_s": sum(idle.values()), "idle": idle,
                    "spans": len(spans)})
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
    return reduce_fits(trace["devices"], trace["host"], fits)


def fits_of(record):
    """The traced whole fits of a run record, reduced; [] where the run has
    no device trace. Read once per process, whichever metric asks first."""
    trace = record.get("trace")
    if not trace:
        return []
    return _read(trace["path"], tuple(
        (m["start"], m["end"]) for m in trace["marks"]
        if m["name"] == FIT_MARK and m["busy_s"] > 0))


def median_per_fit(record, read_fit):
    """Median over the traced fits of `read_fit(fit)`; None where there is
    no traced fit, or where a fit has nothing of the kind to read (the
    program has no such program name or span: an older commit)."""
    values = [read_fit(fit) for fit in fits_of(record)]
    if not values or any(v is None for v in values):
        return None
    return statistics.median(values)


def closed(fit) -> bool:
    """Whether the fit's programs add up to its busy seconds. Runs that
    overlapped on the modules line would add up to more, and no split by
    program could then be trusted."""
    return (abs(sum(fit["programs"].values()) - fit["busy_s"])
            <= CLOSURE * fit["busy_s"])


def solve_seconds(fit, program):
    """Device seconds inside the runs of one solve program; None where the
    trace has no such name or the fit is not closed."""
    return fit["programs"].get(program) if closed(fit) else None


def other_seconds(fit):
    """Device seconds of every program but the two solves, and of the ops
    outside every program; None where the trace has neither solve's name,
    since then nothing tells a solve from the rest, or is not closed."""
    programs = fit["programs"]
    if not closed(fit) or not any(name in programs for name in CALLS):
        return None
    return sum(v for name, v in programs.items() if name not in CALLS)


def idle_seconds(fit, wait, spans):
    """Idle seconds in which the device waited for `wait` (CALL or
    OPERANDS) of a solve program, or for the host while its innermost span
    was photon/<one of spans>; None where the fit has no photon/* span."""
    if not fit["spans"]:
        return None
    return sum(fit["idle"].get(name, 0.0) for name in
               [wait + program for program in CALLS]
               + [SPAN + span for span in spans])
