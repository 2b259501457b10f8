"""Solvers: median per traced fit of 100 x the lanes' own iterations over
lanes x trips, summed over the runs of `jit_re_bucket_solve`: the share of
the lane-trips the per-entity solve holds in which a lane is still running.
Every trip runs every lane, so 100 less it is the most that compacting
ended lanes away (ROADMAP S10 item 3) can take off the trips' work. The
program's own counts, from the `photon/re/lockstep` events inside each
`bench/fit` mark (lockstep_reduce.py)."""
from benchmark import lockstep_reduce

META = {"name": "re_lane_occupancy.fit", "unit": "%", "layer": "Solvers",
        "moves": "fit_examples_per_s"}


def read(record):
    return lockstep_reduce.median_per_fit(record,
                                          lockstep_reduce.lane_occupancy)
