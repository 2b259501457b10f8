"""Coordinate descent: median per traced fit of the device-idle seconds in
which the device waits for the host to call a solve program: while the call
of the next solve the device runs is open (`re/dispatch`, `fe/dispatch`), or
before it opens while the host is still inside an earlier one (`re/dispatch`,
`fe/dispatch`, or the rest of `re/solve_call`). Not what the host was doing
during a gap: what the device waited for (span_reduce.py has the rule).

The profiler is on while this is read, and it slows the host's side of a
transfer more than the device's programs: beside an untraced run, read the
idle as fit seconds less busy seconds."""
from benchmark import span_reduce

META = {"name": "idle_dispatch_s.fit", "unit": "s",
        "layer": "Coordinate descent", "moves": "fit_examples_per_s"}

NAMES = ("re/dispatch", "re/solve_call", "fe/dispatch")


def read(record):
    return span_reduce.median_per_fit(
        record, lambda fit: span_reduce.idle_seconds(
            fit, span_reduce.CALL, NAMES))
