"""Coordinate descent: median per traced fit of the device seconds in the
residual exchange, the part of `other_device_s.fit` that grows with the
number of coordinates: `jit__gather_flat_offsets` (the other coordinates'
scores gathered into a bucket's block layout, once per bucket and visit) and
`jit_score_entities_scatter` (all rows, passive ones too, scored against
their entity's new coefficients, once per visit). span_reduce.py has the
device seconds by program."""
from benchmark import span_reduce

META = {"name": "exchange_device_s.fit", "unit": "s",
        "layer": "Coordinate descent", "moves": "fit_examples_per_s"}

PROGRAMS = ("jit__gather_flat_offsets", "jit_score_entities_scatter")


def read_fit(fit):
    programs = fit["programs"]
    if not span_reduce.closed(fit) or not any(p in programs
                                              for p in PROGRAMS):
        return None
    return sum(programs.get(p, 0.0) for p in PROGRAMS)


def read(record):
    return span_reduce.median_per_fit(record, read_fit)
