"""Solvers: median per traced fit of 100 x (1 - running / lock-step trials),
summed over the runs of `jit_re_bucket_solve`: the share of the line
search's trial values the device evaluated that no running lane needed.
The batched search runs while ANY lane's condition holds, an ended lane's
too (on its frozen state), so near 0 says the running lanes drive the
trials (the value's resolution, ROADMAP S10 item 1) and well over half says
the ended lanes do. The program's own counts, from the
`photon/re/lockstep` events inside each `bench/fit` mark
(lockstep_reduce.py)."""
from benchmark import lockstep_reduce

META = {"name": "re_ended_trial_share.fit", "unit": "%", "layer": "Solvers",
        "moves": "fit_examples_per_s"}


def read(record):
    return lockstep_reduce.median_per_fit(record,
                                          lockstep_reduce.ended_trial_share)
