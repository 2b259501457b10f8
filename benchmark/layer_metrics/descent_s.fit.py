"""Coordinate descent: median per fit of every `{iteration}/*` span of the
program (PhaseTimings, host clock). In pipelined mode a wait lands in
whichever span it happens in, so only the sum is read."""
import statistics

META = {"name": "descent_s.fit", "unit": "s", "layer": "Coordinate descent",
        "moves": "fit_examples_per_s"}


def read(record):
    per_fit = [sum(v for k, v in f["record"]["timings"].items()
                   if k.split("/")[0].isdigit())
               for f in record["samples"].get("fits", [])
               if "timings" in f["record"]]
    return statistics.median(per_fit) if per_fit else None
