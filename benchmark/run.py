"""The benchmark's command: one cell, one process.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from BENCHMARK.json, loads the configuration and the traffic
mix it names, builds, warms up, measures, checks, and prints one JSON object
as the last line of its output. It knows no cell, configuration, mix or
layer metric by name: builders, drivers and layer metrics are found by file
name (see README.md).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT = os.path.join(HERE, "out")


def say(*parts):
    print(*parts, flush=True)


def refuse(why: str, code: int) -> int:
    """No result line: the reason goes to the errors, the code to the caller."""
    print(why, file=sys.stderr, flush=True)
    return code


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def metrics_of(spec: dict, group: str, cell: str):
    """The metrics of `group` that the cell reports: those with no
    `workloads` key and those that list the cell."""
    return [m for m in spec[group]
            if "workloads" not in m or cell in m["workloads"]]


def jsonable(x):
    import numpy as np
    if isinstance(x, np.ndarray):
        return x.tolist()
    if isinstance(x, np.generic):
        return x.item()
    return str(x)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU; its numbers are no speeds")
    args = ap.parse_args()

    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next((w for w in spec["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        raise SystemExit(f"no cell {args.workload!r} in BENCHMARK.json")
    config_entry = next(c for c in spec["configs"]
                        if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, config_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))

    sys.path.insert(0, ROOT)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        config.update(config.get("rehearsal", {}))
    try:
        from photon_ml_tpu.utils.jax_cache import (CompileTimeTracker,
                                                   enable_persistent_cache)
    except ImportError as e:
        return refuse(f"the program under test is not beside the benchmark: "
                      f"{e}", 2)
    import jax
    cache_dir = enable_persistent_cache()
    tracker = CompileTimeTracker().install()

    devices = jax.devices()
    kind = devices[0].device_kind
    if devices[0].platform != "tpu" and not args.rehearse_cpu:
        return refuse(f"platform is {devices[0].platform!r}, not 'tpu'", 3)
    if len(devices) < cell["chips"]:
        return refuse(f"the cell asks for {cell['chips']} chips, JAX finds "
                      f"{len(devices)}", 3)
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in peaks and not args.rehearse_cpu:
        return refuse(f"no peaks recorded for device kind {kind!r}: add it "
                      "to benchmark/peaks.json with its source", 3)
    used = devices[:cell["chips"]]

    driver = load_module("drivers", traffic["driver"])
    builder = load_module("builders", config["builders"][driver.ROLE])
    built = builder.build(config, args.seed, cell["chips"])
    t_built = time.perf_counter()
    driver.warm(built, traffic)
    compile_setup = {"setup_seconds": tracker.seconds,
                     "setup_count": tracker.count}
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(OUT, "trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
    setup_s = time.perf_counter() - T_START
    say(f"set-up {setup_s:.2f} s (build {t_built - T_START:.2f} s, warm-up "
        f"{setup_s - (t_built - T_START):.2f} s); compile "
        f"{tracker.seconds:.2f} s in {tracker.count} programs; cache "
        f"{cache_dir}; built {json.dumps(built.info)}")

    samples = driver.run(built, traffic, args.seconds, args.seed, trace_dir)
    window_s = time.perf_counter() - T_START - setup_s
    compiles_in_window = tracker.count - compile_setup["setup_count"]
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in used)     # before the check's own arrays
    summary = driver.summarise(built, samples)
    say(f"window {window_s:.2f} s; compiles_in_window {compiles_in_window}; "
        f"check and summary {time.perf_counter() - T_START - setup_s - window_s:.2f} s")
    say("notes", json.dumps(summary["notes"], default=jsonable))

    values = dict(summary["metrics"], setup_s=setup_s)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": memory_peak}
    line = {"correct": bool(summary["correct"] and compiles_in_window == 0),
            "attempted": summary["attempted"], "failed": summary["failed"]}
    if args.trace:
        from benchmark import trace_reduce
        trace = trace_reduce.reduce_trace(trace_dir)
        record = {"cell": cell, "config": config, "traffic": traffic,
                  "built": built.info, "samples": samples, "summary": summary,
                  "compile": dict(compile_setup,
                                  window_count=compiles_in_window),
                  "trace": trace, "peak": peaks.get(kind)}
        wanted = {m["name"] for m in metrics_of(spec, "per_layer",
                                                cell["name"])}
        for path in sorted(glob.glob(os.path.join(HERE, "layer_metrics",
                                                  "*.py"))):
            module = load_module("layer_metrics",
                                 os.path.basename(path)[:-3])
            if module.META["name"] in wanted:
                value = module.read(record)
                if value is not None:
                    values[module.META["name"]] = value
        group = "per_layer"
        if trace:
            device["busy_s"], device["window_s"] = (trace["busy_s"],
                                                    trace["window_s"])
            line["breakdown"] = {"device_ops": trace["device_ops"],
                                 "idle_gaps": trace["idle_gaps"]}
            say("trace", json.dumps({k: trace[k] for k in
                                     ("path", "lines", "marks")}))
    else:
        group = "end_to_end"
    listed = metrics_of(spec, group, cell["name"])
    line["metrics"] = {
        m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
        for m in listed if m["name"] in values}
    missing = [m["name"] for m in listed if m["name"] not in values]
    if missing:
        say(f"not read in this run: {missing}")
    line["device"] = device
    say(json.dumps(line, default=jsonable))
    return 0


if __name__ == "__main__":
    sys.exit(main())
