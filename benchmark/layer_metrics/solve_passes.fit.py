"""Solvers: data passes of one solve, the program's `fg_count` (+ `hv_count`).
A count: it repeats exactly."""
META = {"name": "solve_passes.fit", "unit": "passes/fit", "layer": "Solvers",
        "moves": "fit_examples_per_s"}


def read(record):
    passes = [f["record"]["passes"] for f in record["samples"].get("fits", [])
              if "passes" in f["record"]]
    return passes[-1] if passes else None
