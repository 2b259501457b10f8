"""Coordinate build and staging: median per traced fit of the device-idle
seconds in which the device waits for staged operands: after the call of the
next solve the device runs has returned (it is enqueued with all before it,
and what has not arrived is an operand: the fixed effect's design matrix
that `fe/stage` stages anew in every fit), or before that call opens while
the host's innermost span stages (the offsets gather and the x0 slice of a
bucket, the residency layer's `stage_static` and `stage_update`, `fe/stage`,
the labels' transfer, the coordinate build). span_reduce.py has the rule.

The value includes what the profiler costs: the host's re-tiling of the
design matrix before its DMA is about 0.45 s slower a fit under the profiler
in a process that loaded its programs from the compile cache, and not in one
that compiled them (PERF.md section 5). Untraced, read the idle of a fit as
its seconds less the trace's busy seconds."""
from benchmark import span_reduce

META = {"name": "idle_staging_s.fit", "unit": "s",
        "layer": "Coordinate build + staging", "moves": "fit_examples_per_s"}

NAMES = ("re/offsets", "re/x0", "re/stage_static", "re/stage_update",
         "fe/stage", "init/transfer", "build/coordinates")


def read(record):
    return span_reduce.median_per_fit(
        record, lambda fit: span_reduce.idle_seconds(
            fit, span_reduce.OPERANDS, NAMES))
