"""Whole fits, back to back, for as long as the window lasts.

Set-up runs ONE whole fit, which loads or compiles every program. The window
then starts a new fit while `elapsed < seconds`; every fit is the same work
and ends with its coefficients ready on the device. The rate is taken over
all fits completed and all the time to the end of the last one, so that a
count of a few fits is not quantised by the window's length.
"""
from __future__ import annotations

import statistics
import time

ROLE = "fit"
MARK = "bench/fit"


def warm(built, params):
    built.fit()


def run(built, params, seconds, seed, trace_dir):
    import jax
    fits, failures, tracing, traced = [], [], False, None
    if trace_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0       # marks and device ops only
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        tracing = True
    start = time.perf_counter()
    while time.perf_counter() - start < seconds:
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(MARK):
                result = built.fit()
            t1 = time.perf_counter()
            fits.append({"start": t0 - start, "end": t1 - start,
                         "record": built.record(result)})
            del result
        except Exception as e:  # a fit that raised is counted, not hidden
            failures.append(f"{type(e).__name__}: {e}"[:2000])
            print("fit failed:", failures[-1], flush=True)
            if len(failures) > params["max_failures"]:
                break
        if tracing and (time.perf_counter() - start
                        >= params["trace_max_seconds"]
                        or len(fits) + len(failures)
                        >= params["trace_max_fits"]):
            jax.profiler.stop_trace()
            tracing, traced = False, len(fits)
    if tracing:
        jax.profiler.stop_trace()
        traced = len(fits)
    return {"fits": fits, "failures": failures, "traced_fits": traced}


def summarise(built, samples):
    fits, failures = samples["fits"], samples["failures"]
    records = [f["record"] for f in fits]
    seconds = [f["end"] - f["start"] for f in fits]
    metrics, notes = {}, {"fit_seconds": [round(s, 4) for s in seconds],
                      "failures": failures}
    check = {"ok": False}
    if fits:
        metrics["fit_examples_per_s"] = (built.train_rows * len(fits)
                                         / fits[-1]["end"])
        notes["median_fit_s"] = statistics.median(seconds)
        check = built.check(records)
    notes["check"] = check
    return {"metrics": metrics, "correct": bool(check["ok"] and not failures),
            "attempted": len(fits) + len(failures), "failed": len(failures),
            "notes": notes}
