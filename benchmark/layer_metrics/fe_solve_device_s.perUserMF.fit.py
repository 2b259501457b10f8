"""Solvers: median per traced fit of the device seconds inside the runs of
`jit_fe_solve` whose call the `perUserMF` coordinate's update made: the
refits of the shared projection, the second half of the factored random
effect's alternation, which run the fixed effect's program
(coordinate_reduce_fe.py places a run by the host span its call was made
in). `fe_solve_device_s.fit` less this is the fixed effect's own solve."""
from benchmark import coordinate_reduce_fe

META = {"name": "fe_solve_device_s.perUserMF.fit", "unit": "s",
        "layer": "Solvers", "moves": "fit_examples_per_s"}


def read(record):
    return coordinate_reduce_fe.fe_solve_seconds(record, "perUserMF")
