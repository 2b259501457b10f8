"""Solvers: median per traced fit of the device seconds inside the runs of
`jit_re_bucket_solve`, the vmapped per-entity solve of one bucket
(span_reduce.py: the ops' union inside each run of that program)."""
from benchmark import span_reduce

META = {"name": "re_solve_device_s.fit", "unit": "s", "layer": "Solvers",
        "moves": "fit_examples_per_s"}


def read(record):
    return span_reduce.median_per_fit(
        record, lambda fit: span_reduce.solve_seconds(
            fit, span_reduce.RE_SOLVE))
