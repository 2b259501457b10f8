"""Coordinate build and staging: median per fit of the program's `build/*`
and `init/*` spans (PhaseTimings, host clock)."""
import statistics

META = {"name": "build_s.fit", "unit": "s",
        "layer": "Coordinate build + staging", "moves": "fit_examples_per_s"}


def read(record):
    per_fit = [sum(v for k, v in f["record"]["timings"].items()
                   if k.startswith(("build/", "init/")))
               for f in record["samples"].get("fits", [])
               if "timings" in f["record"]]
    return statistics.median(per_fit) if per_fit else None
