"""Solvers: median per traced fit of the device seconds inside the runs of
`jit_fe_solve`, the fixed-effect solve (span_reduce.py)."""
from benchmark import span_reduce

META = {"name": "fe_solve_device_s.fit", "unit": "s", "layer": "Solvers",
        "moves": "fit_examples_per_s"}


def read(record):
    return span_reduce.median_per_fit(
        record, lambda fit: span_reduce.solve_seconds(
            fit, span_reduce.FE_SOLVE))
