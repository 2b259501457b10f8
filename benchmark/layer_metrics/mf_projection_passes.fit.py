"""Solvers: value+gradient passes of the factored random effect's projection
refits in one fit, the program's own count (`projection_data_passes` of
`solver_diagnostics()`, through the builder's record), summed over the
coordinate's visits. One lane of L-BFGS under the upstream's stopping rule,
so the count follows the seed (18 to 20 a refit in `game-ml20m-mf.fit`); with
`fe_solve_device_s.perUserMF.fit` it gives the seconds of one pass. A commit
that does not keep the alternation's halves apart reads nothing."""
META = {"name": "mf_projection_passes.fit", "unit": "passes/fit",
        "layer": "Solvers", "moves": "fit_examples_per_s"}


def read(record):
    passes = [f["record"]["mf_passes"]["projection"]
              for f in record["samples"].get("fits", [])
              if f["record"].get("mf_passes", {}).get("projection")]
    return sum(passes[-1]) if passes else None
