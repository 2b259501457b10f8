"""Solvers: median per traced fit of the device seconds inside the runs of
`jit_re_bucket_solve` whose call the `perUserMF` coordinate's update made:
the per-user solves in the latent space, the first half of the factored
random effect's alternation (coordinate_reduce.py places a run by the host
span its call was made in). With `re_solve_device_s.perUser.fit` and
`.perItem.fit` it adds up to `re_solve_device_s.fit`; all read nothing where
they do not to 1%."""
from benchmark import coordinate_reduce

META = {"name": "re_solve_device_s.perUserMF.fit", "unit": "s",
        "layer": "Solvers", "moves": "fit_examples_per_s"}


def read(record):
    return coordinate_reduce.re_solve_seconds(record, "perUserMF")
