"""Prove a cell with ONE chip call: the cold run, sets of warm runs with the
same seeds in every set, and one traced run, each a child process in turn.
This parent never imports JAX, so each child gets the chip to itself.

    python benchmark/prove.py --workload <cell> --runs 6 --sets 2 --cold --trace-after

Every child's last line goes to <out>/<cell>.jsonl with the run's set, seed
and wall seconds added, its whole output to <out>/<cell>.log, and medians and
spreads are printed at the end. A spread is the distance between the first
and third quartile (statistics.quantiles(values, n=4)) over the median.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def spread(values):
    if len(values) < 2:
        return None
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def one_run(cell, seed, seconds, trace, tag, out_dir, extra):
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
           "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)] + extra
    t0 = time.perf_counter()
    child = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                           stderr=subprocess.PIPE, text=True, timeout=1500)
    wall = time.perf_counter() - t0
    lines = child.stdout.strip().splitlines()
    with open(os.path.join(out_dir, cell + ".log"), "a") as f:
        f.write(f"=== {tag} seed {seed} trace {trace} rc {child.returncode} "
                f"wall {wall:.1f} s\n{child.stdout}\n")
        if child.returncode != 0:
            f.write(child.stderr[-4000:] + "\n")
    row = {"tag": tag, "seed": seed, "trace": trace, "rc": child.returncode,
           "wall_s": wall}
    try:
        row.update(json.loads(lines[-1]))
    except (IndexError, ValueError):
        row["error"] = (child.stderr or child.stdout)[-600:]
    with open(os.path.join(out_dir, cell + ".jsonl"), "a") as f:
        f.write(json.dumps(row) + "\n")
    brief = {k: v["value"] for k, v in row.get("metrics", {}).items()}
    print(f"{tag} seed {seed} rc {child.returncode} wall {wall:.1f} s "
          f"correct {row.get('correct')} attempted {row.get('attempted')} "
          f"peak {row.get('device', {}).get('memory_peak_bytes')} {brief}"
          + (f" ERROR {row['error']}" if "error" in row else ""), flush=True)
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--seed0", type=int, default=2147483659)
    ap.add_argument("--cold", action="store_true",
                    help="one run first that is kept out of the sets")
    ap.add_argument("--trace-after", action="store_true")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "prove"))
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        seconds = args.seconds or json.load(f)["run_seconds"]
    os.makedirs(args.out, exist_ok=True)
    extra = ["--rehearse-cpu"] if args.rehearse_cpu else []
    seeds = [args.seed0 + 7919 * i for i in range(args.runs)]
    cell = args.workload

    if args.cold:
        one_run(cell, seeds[0], seconds, 0, "cold", args.out, extra)
    sets = [[one_run(cell, s, seconds, 0, f"set{k}", args.out, extra)
             for s in seeds] for k in range(args.sets)]
    if args.trace_after:
        one_run(cell, seeds[0], seconds, 1, "traced", args.out, extra)

    names = sorted({m for rows in sets for r in rows
                    for m in r.get("metrics", {})})
    for name in names:
        spreads, medians = [], []
        for k, rows in enumerate(sets):
            values = [r["metrics"][name]["value"] for r in rows
                      if name in r.get("metrics", {})]
            if not values:
                continue
            medians.append(statistics.median(values))
            spreads.append(spread(values))
            print(f"{cell} {name} set{k}: median {medians[-1]:.6g} spread "
                  f"{spreads[-1] if spreads[-1] is None else round(spreads[-1], 5)} "
                  f"values {[round(v, 4) for v in values]}")
        known = [s for s in spreads if s is not None]
        if known:
            print(f"{cell} {name}: wider spread {max(known):.5f}, five times "
                  f"it {5 * max(known):.4f}; set medians differ by "
                  f"{abs(medians[-1] - medians[0]) / medians[0]:.5f}")
    bad = [r for rows in sets for r in rows
           if r["rc"] != 0 or not r.get("correct")]
    print(f"{cell}: {sum(len(r) for r in sets)} runs in sets, "
          f"{len(bad)} failed or incorrect")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
