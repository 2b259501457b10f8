"""Solvers: median per traced fit of the trips of the vmapped L-BFGS loop,
summed over the runs of `jit_re_bucket_solve` (a run: one bucket of one
coordinate's visit; its trips are its slowest lane's iterations). The
program's own count, from the `photon/re/lockstep` events inside each
`bench/fit` mark (lockstep_reduce.py); with `re_solve_device_s.fit` it
gives the seconds of a trip. A count: a seed repeats it exactly."""
from benchmark import lockstep_reduce

META = {"name": "re_trips.fit", "unit": "trips/fit", "layer": "Solvers",
        "moves": "fit_examples_per_s"}


def read(record):
    return lockstep_reduce.median_per_fit(record, lockstep_reduce.trips)
