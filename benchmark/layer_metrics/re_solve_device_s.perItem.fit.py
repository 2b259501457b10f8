"""Solvers: median per traced fit of the device seconds inside the runs of
`jit_re_bucket_solve` whose call the `perItem` coordinate's update made
(coordinate_reduce.py places a run by the host span its call was made in).
With `re_solve_device_s.perUser.fit` it adds up to `re_solve_device_s.fit`;
both read nothing where it does not to 1%."""
from benchmark import coordinate_reduce

META = {"name": "re_solve_device_s.perItem.fit", "unit": "s",
        "layer": "Solvers", "moves": "fit_examples_per_s"}


def read(record):
    return coordinate_reduce.re_solve_seconds(record, "perItem")
