"""Coordinate descent: median per traced fit of the device seconds in every
program but the two solves (the offsets gather, scoring, objective,
validation, eager ops) and in ops outside every program. With
`re_solve_device_s.fit` and `fe_solve_device_s.fit` it adds up to the
trace's busy seconds per fit; all three read nothing where it does not."""
from benchmark import span_reduce

META = {"name": "other_device_s.fit", "unit": "s",
        "layer": "Coordinate descent", "moves": "fit_examples_per_s"}


def read(record):
    return span_reduce.median_per_fit(record, span_reduce.other_seconds)
