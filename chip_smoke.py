#!/usr/bin/env python3
"""Chip smoke: the two paths users wait on, end to end on the attached TPU.

    python chip_smoke.py                 # one chip: train, then serve
    python chip_smoke.py --multichip     # four chips: mesh fit vs one-device fit

Default run, one chip — the GLMix deployment the repo calls its flagship
(BASELINE.json config 4, as the benchmark's `glmix-ml20m` trains it):

  data   MovieLens-1M shape from photon_ml_tpu.data.synthetic_bench
         (1,000,209 rows, 6,040 users, global width 31, per-user width 19),
         made from --seed and written as the Avro part files cli.train
         reads, plus a 5% validation file from the same seed.
  train  python -m photon_ml_tpu.cli.train, float32, --mesh auto, FE +
         per-user RE, L2, 2 outer iterations.  Checked: exit 0, objective
         history finite and decreasing, validation AUC above the bar.
  serve  python -m photon_ml_tpu.cli.serve on the saved model; /score over
         HTTP (single rows, a batch that spans two buckets, known and
         unknown user ids) compared with NumPy float64 margins computed here
         from the saved coefficients; GET /metrics.json; SIGTERM, clean
         drain, exit 0.

One process holds the chip at a time: this script never imports JAX.  It
starts the probe, cli.train and cli.serve as children in turn, each of which
exits before the next starts, and takes the device from what they report
(`device` in cli.train's summary JSON and cli.serve's start-up line).  Data
is written by children held to the CPU.  Both product children place the
compile cache by the repo's one rule ($JAX_COMPILATION_CACHE_DIR, else
<checkout>/.jax_cache), so they share it.

It fails (non-zero exit, reason on stderr, no result line) when the platform
is not "tpu", when a phase fails, or when the rest of the repo is not beside
it.  --rehearse-cpu is the rehearsal the tests run: the same phases on the
CPU backend at a --rows of the caller's choosing; it never prints
"platform": "tpu".

The last line of stdout is one JSON object:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
from __future__ import annotations

import argparse
import json
import os
import queue
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
FULL_ROWS = 1_000_209          # MovieLens-1M; the generator's own default
D_GLOBAL, D_USER = 31, 19      # the generator's widths, intercept last
SHARD_MAP = {"global": ["globalBag"], "per_user": ["userBag"]}
TRAIN_PARTS = 8
# validation AUC of the full-size fit: the CPU rehearsal gives 0.8378 (seed
# 11, float32); a reduced --rows run is held to better-than-chance only
FULL_AUC_BAR = 0.82
REDUCED_AUC_BAR = 0.55
# float32 scoring vs float64 NumPy margins: margins are O(1..10) sums of
# <= 50 products, so float32 rounding is ~1e-6; the bound leaves room for
# the TPU's multi-pass float32 matmul
SCORE_ATOL, SCORE_RTOL = 2e-4, 2e-4
# float32 objective histories, mesh vs one device: the psum changes the
# summation order only (the benchmark's objective check is the same 1e-4)
OBJECTIVE_RTOL = 1e-4
SCORE_BATCH_ROWS = 1324        # 1024 + 300: buckets 1024 and 512
# a mesh peer's live bytes as a share of the one-device fit's: a quarter of
# the row-sharded data plus the replicated model and per-visit operands
MESH_PEER_SHARE = 0.35


class SmokeFailure(Exception):
    """A phase failed; the message is the reason printed before exit."""


def _require(cond: bool, reason: str) -> None:
    if not cond:
        raise SmokeFailure(reason)


def _say(msg: str) -> None:
    print(msg, flush=True)


# -- children ---------------------------------------------------------------

def _child_env(rehearse: bool, devices: int = 1) -> dict:
    """Environment of a child that may use the accelerator."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, [REPO, env.get("PYTHONPATH")]))
    env.pop("JAX_ENABLE_X64", None)     # float32 end to end
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return env


def _cpu_env() -> dict:
    """Environment of a child that must never touch the accelerator."""
    env = _child_env(rehearse=True)
    env.pop("XLA_FLAGS")
    return env


_PROBE = """
import json, jax
d = jax.devices()
print(json.dumps({"platform": d[0].platform, "kind": d[0].device_kind,
                  "count": len(d),
                  "memory_stats": d[0].memory_stats() is not None}))
"""


def _probe(env: dict) -> dict:
    """What JAX finds, asked of a child that exits before any phase."""
    p = subprocess.run([sys.executable, "-c", _PROBE], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    _require(p.returncode == 0,
             f"device probe failed (rc={p.returncode}): {p.stderr[-800:]}")
    return json.loads(p.stdout.strip().splitlines()[-1])


def _last_json_line(text: str, what: str) -> dict:
    lines = [l for l in text.strip().splitlines() if l.startswith("{")]
    _require(bool(lines), f"{what} printed no JSON line")
    return json.loads(lines[-1])


# -- phase: data (children held to the CPU) ---------------------------------

def _write_part(seed: int, rows: int, work: str, part: int) -> None:
    """Child body: regenerate the corpus from the seed and write one slice
    of it.  part < TRAIN_PARTS writes that training part file;
    part == TRAIN_PARTS writes the validation file and the request rows."""
    from photon_ml_tpu.data.avro_game import write_game_examples
    from photon_ml_tpu.data.index_map import IndexMap, feature_key
    from photon_ml_tpu.data.synthetic_bench import (make_movielens_like,
                                                    movielens_shards)
    ml = make_movielens_like("1m", seed=seed, n_rows=rows)
    shards = movielens_shards(ml)
    assert shards["global"].shape[1] == D_GLOBAL
    assert shards["per_user"].shape[1] == D_USER
    maps = {k: IndexMap.from_keys(
        [feature_key(f"{k}{j:04d}") for j in range(shards[k].shape[1] - 1)])
        for k in ("global", "per_user")}
    # a deterministic 95/5 split, drawn from seed + 99 as the benchmark's is
    val_mask = np.random.default_rng(seed + 99).uniform(size=rows) < 0.05
    if part < TRAIN_PARTS:
        take = np.array_split(np.flatnonzero(~val_mask), TRAIN_PARTS)[part]
        path = os.path.join(work, "train", f"part-{part:05d}.avro")
    else:
        take = np.flatnonzero(val_mask)
        path = os.path.join(work, "val", "part-00000.avro")
        req = take[:SCORE_BATCH_ROWS + 64]
        np.savez(os.path.join(work, "requests.npz"),
                 x_global=shards["global"][req],
                 x_user=shards["per_user"][req],
                 user_ids=ml.user_ids[req].astype(str))
    write_game_examples(
        path, ml.response[take],
        bags={"globalBag": (shards["global"][take], maps["global"]),
              "userBag": (shards["per_user"][take], maps["per_user"])},
        id_values={"userId": ml.user_ids[take]})


def phase_data(seed: int, rows: int, work: str) -> dict:
    for sub in ("train", "val"):
        os.makedirs(os.path.join(work, sub))
    env = _cpu_env()
    children = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--write-part", str(part),
         "--seed", str(seed), "--rows", str(rows), "--work-dir", work],
        env=env, cwd=REPO, stderr=subprocess.PIPE, text=True)
        for part in range(TRAIN_PARTS + 1)]
    for part, c in enumerate(children):
        _, err = c.communicate(timeout=900)
        _require(c.returncode == 0,
                 f"data part {part} failed (rc={c.returncode}): {err[-800:]}")
    files = [os.path.join(work, "train", f) for f in
             sorted(os.listdir(os.path.join(work, "train")))]
    _require(len(files) == TRAIN_PARTS, f"expected {TRAIN_PARTS} part files")
    return {"train_files": len(files),
            "avro_mb": round(sum(map(os.path.getsize, files)) / 1e6, 1)}


# -- phase: train -------------------------------------------------------------

def _glmix_config(seed: int) -> dict:
    """The training configuration of the benchmark's `glmix-ml20m` as
    GameTrainingConfig JSON (tests/test_chip_smoke.py holds the two equal):
    FE + per-user RE, LBFGS (100 iterations), cap 512, L2 weight 1, 2 outer
    iterations."""
    opt = {"optimizer": {"optimizer": "lbfgs", "max_iterations": 100},
           "regularization": {"type": "l2"}, "regularization_weight": 1.0}
    return {
        "task_type": "logistic_regression",
        "coordinates": {
            "fixed": {"kind": "fixed_effect", "feature_shard": "global",
                      "optimization": opt},
            "perUser": {"kind": "random_effect",
                        "random_effect_type": "userId",
                        "feature_shard": "per_user",
                        "active_data_upper_bound": 512,
                        "optimization": opt}},
        "updating_sequence": ["fixed", "perUser"],
        "num_outer_iterations": 2, "seed": seed}


def run_train(work: str, out_name: str, mesh: str, env: dict, seed: int,
              run_log: bool = False) -> dict:
    """One cli.train child on the generated files -> its summary JSON (plus
    the child's wall seconds and its stderr `mesh:` line)."""
    cfg_path = os.path.join(work, "glmix.json")
    with open(cfg_path, "w") as f:
        json.dump(_glmix_config(seed), f)
    out_dir = os.path.join(work, out_name)
    cmd = [sys.executable, "-m", "photon_ml_tpu.cli.train",
           "--train-data", os.path.join(work, "train"),
           "--validation-data", os.path.join(work, "val", "part-00000.avro"),
           "--feature-shard-map", json.dumps(SHARD_MAP),
           "--id-columns", "userId", "--config", cfg_path,
           "--evaluators", "AUC", "--mesh", mesh, "--output-dir", out_dir]
    if run_log:
        cmd += ["--run-log", os.path.join(work, out_name + ".run.jsonl")]
    t0 = time.perf_counter()
    p = subprocess.run(cmd, env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=1100)
    wall = time.perf_counter() - t0
    _require(p.returncode == 0,
             f"cli.train --mesh {mesh} failed (rc={p.returncode}): "
             f"{p.stderr[-1500:]}")
    summary = _last_json_line(p.stdout, "cli.train")
    summary["_wall_s"] = wall
    summary["_mesh_line"] = next(
        (l for l in p.stderr.splitlines() if l.startswith("mesh: ")), None)
    return summary


def check_fit(summary: dict, rows: int, want_platform: str) -> None:
    dev = summary["device"]
    _require(dev["platform"] == want_platform,
             f"cli.train ran on {dev}, wanted platform {want_platform!r}")
    hist = np.asarray(summary["objective_history"], np.float64)
    _require(len(hist) == 4, f"expected 2 outer x 2 coordinates: {hist}")
    _require(bool(np.all(np.isfinite(hist))), f"objective not finite: {hist}")
    _require(bool(np.all(np.diff(hist) < 0)),
             f"objective history not decreasing: {hist.tolist()}")
    bar = FULL_AUC_BAR if rows == FULL_ROWS else REDUCED_AUC_BAR
    auc = summary["validation"]["AUC"]
    _require(auc > bar, f"validation AUC {auc} not above the bar {bar}")
    if want_platform == "tpu":
        for m in summary["device_memory"]:
            _require(bool(m["peak_bytes_in_use"]),
                     f"memory_stats() reports no peak on the chip: {m}")


def report_fit(tag: str, summary: dict) -> None:
    decoders = {k.rsplit(".", 1)[1]: v for k, v in
                summary["telemetry"]["metrics"]["counters"].items()
                if k.startswith("avro.decode.")}
    _say(f"{tag}: wall {summary['_wall_s']:.1f} s (ingest "
         f"{summary['ingest_s']} s, fit+save {summary['wall_s']} s in-process)")
    _say(f"{tag}: compile {summary['compile_s']} s in "
         f"{summary['compile_count']} programs; cache "
         f"{summary['compile_cache']}")
    _say(f"{tag}: avro decoder files {decoders}")
    _say(f"{tag}: phase seconds {summary['phase_timings_s']}")
    _say(f"{tag}: objective history {summary['objective_history']}; "
         f"validation {summary['validation']}")
    _say(f"{tag}: device {summary['device']}; memory "
         f"{summary['device_memory']}")


# -- phase: serve -------------------------------------------------------------

def reference_margins(model_dir: str, x_global, x_user, user_ids):
    """NumPy float64 margins from the SAVED coefficients: x_global . w_fixed
    plus, for a user the model knows, the per-user coefficients scattered
    through their index projection; an unknown user contributes 0."""
    with np.load(os.path.join(model_dir, "fixed-effect", "fixed",
                              "coefficients.npz"), allow_pickle=True) as z:
        w = z["means"].astype(np.float64)
    with np.load(os.path.join(model_dir, "random-effect", "perUser",
                              "coefficients.npz"), allow_pickle=True) as z:
        coef = z["coefficients"].astype(np.float64)
        proj = z["projection"]
        lane = {str(e): i for i, e in enumerate(z["entity_ids"].tolist())}
    margins = x_global.astype(np.float64) @ w
    known = np.zeros(len(user_ids), bool)
    for r, uid in enumerate(user_ids):
        e = lane.get(str(uid))
        if e is None:
            continue
        known[r] = True
        cols = proj[e] >= 0
        margins[r] += float(
            x_user[r, proj[e][cols]].astype(np.float64) @ coef[e][cols])
    return margins, known


def _post(url: str, payload: dict) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())


def phase_serve(work: str, model_dir: str, env: dict,
                want_platform: str) -> dict:
    with np.load(os.path.join(work, "requests.npz")) as z:
        xg, xu, uids = z["x_global"], z["x_user"], z["user_ids"]
    _require(len(uids) >= SCORE_BATCH_ROWS,
             f"only {len(uids)} request rows; --rows too small")
    # every third row of the batch asks for a user the model never saw
    uids = uids.copy()
    uids[2::3] = np.char.add("never-seen-", uids[2::3])
    ref, known = reference_margins(model_dir, xg, xu, uids)
    _require(bool(known.any()) and bool((~known).any()),
             "request rows must mix known and unknown users")
    first_known = int(np.flatnonzero(known)[0])
    first_unknown = int(np.flatnonzero(~known)[0])
    requests = [("single known", [first_known]),
                ("single unknown", [first_unknown]),
                ("batch of 5", list(range(5))),
                (f"batch of {SCORE_BATCH_ROWS} (two buckets)",
                 list(range(SCORE_BATCH_ROWS)))]

    err_path = os.path.join(work, "serve.stderr")
    t0 = time.perf_counter()
    with open(err_path, "w") as err:
        proc = subprocess.Popen(
            [sys.executable, "-m", "photon_ml_tpu.cli.serve",
             "--model-dir", model_dir, "--port", "0"],
            env=env, cwd=REPO, stdout=subprocess.PIPE, stderr=err, text=True)
    lines: queue.Queue = queue.Queue()
    threading.Thread(target=lambda: [lines.put(l) for l in proc.stdout],
                     daemon=True).start()

    def next_json(timeout, what):
        deadline = time.monotonic() + timeout
        while True:
            try:
                line = lines.get(timeout=max(deadline - time.monotonic(), 0))
            except queue.Empty:
                with open(err_path) as f:
                    raise SmokeFailure(f"cli.serve: no {what} within "
                                       f"{timeout}s: {f.read()[-1500:]}")
            if line.startswith("{"):
                return json.loads(line)

    try:
        start = next_json(900, "start-up line")
        startup_s = time.perf_counter() - t0
        _require(start["device"]["platform"] == want_platform,
                 f"cli.serve runs on {start['device']}, wanted platform "
                 f"{want_platform!r}")
        url = start["serving"]
        worst = 0.0
        rows_sent = 0
        t_req = time.perf_counter()
        for label, idx in requests:
            out = _post(url + "/score", {
                "features": {"global": xg[idx].tolist(),
                             "per_user": xu[idx].tolist()},
                "ids": {"userId": uids[idx].tolist()}})
            got = np.asarray(out["scores"], np.float64)
            _require(got.shape == (len(idx),) and bool(
                np.all(np.isfinite(got))), f"{label}: bad scores {got[:5]}")
            gap = np.abs(got - ref[idx])
            ok = gap <= SCORE_ATOL + SCORE_RTOL * np.abs(ref[idx])
            _require(bool(ok.all()),
                     f"{label}: scores differ from the float64 margins by "
                     f"up to {gap.max():.3g} (tolerance {SCORE_ATOL} + "
                     f"{SCORE_RTOL}*|ref|)")
            worst = max(worst, float(gap.max()))
            rows_sent += len(idx)
            _say(f"serve: {label}: {len(idx)} rows, max |score - float64 "
                 f"margin| {gap.max():.3g}")
        request_s = time.perf_counter() - t_req
        with urllib.request.urlopen(url + "/metrics.json",
                                    timeout=60) as resp:
            metrics = json.loads(resp.read())
        _require(metrics["rows"] == rows_sent,
                 f"/metrics.json counts {metrics['rows']} rows, sent "
                 f"{rows_sent}")
        _require(metrics["requests"] == len(requests)
                 and metrics["errors"] == 0,
                 f"/metrics.json counts {metrics['requests']} requests and "
                 f"{metrics['errors']} errors, sent {len(requests)}")
        _require(0.0 < metrics["entity_hit_rate"] < 1.0,
                 "known and unknown users were sent, but the hit rate is "
                 f"{metrics['entity_hit_rate']}")
        proc.send_signal(signal.SIGTERM)
        drained = next_json(120, "drain line")
        rc = proc.wait(timeout=120)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    _require(drained.get("drained") is True and drained["aborted"] is False,
             f"cli.serve did not drain cleanly: {drained}")
    _require(rc == 0, f"cli.serve exited {rc} after SIGTERM")
    if want_platform == "tpu":
        for m in start["device_memory"]:
            _require(bool(m["peak_bytes_in_use"]),
                     f"memory_stats() reports no peak on the chip: {m}")
    _say(f"serve: start-up {startup_s:.1f} s (model load + warm-up "
         f"{start['model_load_s']} s), requests {request_s:.2f} s, "
         f"buckets {start['buckets']}")
    _say(f"serve: compile {start['compile_s']} s; cache "
         f"{start['compile_cache']}")
    _say(f"serve: device {start['device']}; memory {start['device_memory']}")
    _say(f"serve: /metrics.json rows {metrics['rows']}, requests "
         f"{metrics['requests']}, batches {metrics['batches']}, occupancy "
         f"{metrics['batch_occupancy']}, entity hit rate "
         f"{metrics['entity_hit_rate']}, latency ms {metrics['latency_ms']}")
    return {"start": start, "metrics": metrics, "max_score_gap": worst,
            "startup_s": startup_s, "request_s": request_s}


# -- the four-chip comparison -------------------------------------------------

def check_mesh_fit(mesh: dict, single: dict, work: str,
                   want_platform: str) -> None:
    """The mesh fit against the one-device fit of the same files."""
    _require(mesh["mesh"] == {"data": 4, "feature": 1},
             f"mesh reported {mesh['mesh']}, wanted data=4")
    _require(mesh["_mesh_line"] is not None
             and "over 4 devices" in mesh["_mesh_line"],
             f"no 4-device mesh line on stderr: {mesh['_mesh_line']}")
    _require(mesh["device"]["count"] == 4 and single["device"]["count"] == 1,
             f"model devices: mesh {mesh['device']}, single "
             f"{single['device']}")
    ids = [m["id"] for m in mesh["device_memory"]]
    _require(len(set(ids)) == 4, f"wanted four distinct devices, got {ids}")

    # objective histories agree (float32, summation order differs)
    h4 = np.asarray(mesh["objective_history"])
    h1 = np.asarray(single["objective_history"])
    gap = float(np.max(np.abs(h4 - h1) / np.abs(h1)))
    _require(gap <= OBJECTIVE_RTOL,
             f"objective histories differ by {gap:.3g} relative "
             f"(tolerance {OBJECTIVE_RTOL}): {h4.tolist()} vs {h1.tolist()}")
    _say(f"multichip: objective histories agree to {gap:.3g} relative "
         f"(tolerance {OBJECTIVE_RTOL})")

    # the row-sharded coordinate blocks: per device a quarter of one device's
    r4, r1 = mesh["hbm_residency"], single["hbm_residency"]
    _require(r4["per_device"] and r4["data_devices"] == 4,
             f"mesh residency accounting is not per device: {r4}")
    for name, one in r1["resident_block_bytes"].items():
        share = r4["resident_block_bytes"][name] / one
        _require(0.2 <= share <= 0.3,
                 f"{name}: per-device block is {share:.3f} of the "
                 "single-device block, wanted about a quarter")
        _say(f"multichip: {name} resident block "
             f"{r4['resident_block_bytes'][name]} B per device vs {one} B "
             f"on one device ({share:.3f})")
    t = mesh["mesh_transfer"]
    _say(f"multichip: staged cold {t['cold_bytes']} B in {t['cold_stages']} "
         f"transfers ({t['cold_bytes'] // 4} B per device), warm "
         f"{t['warm_bytes']} B in {t['warm_stages']}")

    # what is alive on each device at the end of the fit, counted from the
    # arrays' own shards (works on every backend).  Devices 1-3 hold their
    # quarter.  Device 0 also still holds WHOLE copies — the random-effect
    # coordinate builds its entity blocks, its flat shard view and the
    # validation shards on the default device before the mesh layer shards
    # them (ROADMAP S9) — so it is held only to "no more than the one-device
    # fit holds"; the bytes are printed for the record.
    live = [m["live_array_bytes"] for m in mesh["device_memory"]]
    one_live = single["device_memory"][0]["live_array_bytes"]
    _require(all(0 < b <= MESH_PEER_SHARE * one_live for b in live[1:]),
             f"devices 1-3 hold {live[1:]} live bytes, wanted at most "
             f"{MESH_PEER_SHARE} of the one-device fit's {one_live}")
    _require(0 < live[0] <= 1.05 * one_live,
             f"device 0 holds {live[0]} live bytes, more than the one-device "
             f"fit's {one_live}")
    _say(f"multichip: live array bytes per device {live}; one-device fit "
         f"{one_live} (device 0 keeps whole copies of {live[0] - live[1]} B "
         "beside its shard)")
    if want_platform == "tpu":
        peaks = [m["peak_bytes_in_use"] for m in mesh["device_memory"]]
        used = [m["bytes_in_use"] for m in mesh["device_memory"]]
        one = single["device_memory"][0]
        _require(all(peaks) and all(used) and bool(one["peak_bytes_in_use"]),
                 f"memory_stats() missing on the chip: {peaks} {used}")
        _say(f"multichip: memory_stats bytes_in_use per device {used}, peak "
             f"{peaks}; one-device fit in use {one['bytes_in_use']}, peak "
             f"{one['peak_bytes_in_use']}")
    else:
        _say("multichip: memory_stats() not reported by this backend "
             "(rehearsal)")

    # warm iterations stage no dataset bytes: every cold (static data)
    # transfer starts before the second outer iteration does
    spans = []
    with open(os.path.join(work, "model-mesh.run.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("kind") == "span":
                spans.append(rec)
    outer = sorted((s for s in spans if s["name"] == "outer_iteration"),
                   key=lambda s: s["t0_s"])
    cold = [s for s in spans if s["name"] == "mesh_stage"
            and not s["attrs"]["warm"]]
    _require(len(outer) == 2 and bool(cold),
             f"run log: {len(outer)} outer iterations, {len(cold)} cold "
             "stages")
    late = [s for s in cold if s["t0_s"] >= outer[1]["t0_s"]]
    _require(not late,
             f"{len(late)} static transfers in the warm outer iteration: "
             f"{[s['attrs'] for s in late[:3]]}")
    _say(f"multichip: all {len(cold)} static transfers precede the second "
         "outer iteration; it staged per-visit operands only")


# -- main -----------------------------------------------------------------------

def run(args) -> dict:
    rehearse = args.rehearse_cpu
    want_platform = "cpu" if rehearse else "tpu"
    devices = 4 if args.multichip else 1
    env = _child_env(rehearse, devices)

    t0 = time.perf_counter()
    probe = _probe(env)
    _say(f"probe: {probe} in {time.perf_counter() - t0:.1f} s")
    _require(probe["platform"] == want_platform,
             f"JAX finds platform {probe['platform']!r}, not "
             f"{want_platform!r}: no accelerator here")
    _require(probe["count"] == devices,
             f"this run needs {devices} device(s), JAX finds "
             f"{probe['count']}")
    if not rehearse:
        _require(probe["memory_stats"],
                 "device.memory_stats() is None on the chip")

    rows = args.rows
    if rows != FULL_ROWS:
        _say(f"rows cut to {rows} of {FULL_ROWS} (widths and all 6,040 "
             "users kept)")
    work = args.work_dir or tempfile.mkdtemp(prefix="chip_smoke_")
    os.makedirs(work, exist_ok=True)
    _require(not os.listdir(work), f"work dir {work} is not empty")
    result = {"mode": "multichip" if args.multichip else "default",
              "seed": args.seed, "rows": rows, "probe": probe}

    t0 = time.perf_counter()
    result["data"] = phase_data(args.seed, rows, work)
    result["data"]["wall_s"] = time.perf_counter() - t0
    _say(f"data: {rows} rows -> {result['data']['train_files']} Avro part "
         f"files, {result['data']['avro_mb']} MB, in "
         f"{result['data']['wall_s']:.1f} s")

    if args.multichip:
        mesh = run_train(work, "model-mesh", "auto", env, args.seed,
                         run_log=True)
        report_fit("train mesh auto", mesh)
        check_fit(mesh, rows, want_platform)
        single = run_train(work, "model-single", "none", env, args.seed)
        report_fit("train mesh none", single)
        check_fit(single, rows, want_platform)
        check_mesh_fit(mesh, single, work, want_platform)
        result.update(train_mesh=mesh, train_single=single)
        device = dict(mesh["device"])
    else:
        fit = run_train(work, "model", "auto", env, args.seed)
        report_fit("train", fit)
        check_fit(fit, rows, want_platform)
        served = phase_serve(work, os.path.join(work, "model", "best"), env,
                             want_platform)
        _require(served["start"]["device"] == fit["device"],
                 f"train and serve disagree on the device: {fit['device']} "
                 f"vs {served['start']['device']}")
        _say(f"compile seconds, train + serve: "
             f"{fit['compile_s'] + served['start']['compile_s']:.2f}")
        result.update(train=fit, serve=served)
        device = dict(fit["device"])
    _require(device["count"] == devices,
             f"the phases used {device['count']} device(s), not {devices}")

    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"chip_smoke_{result['mode']}"
                           f"{'_rehearsal' if rehearse else ''}.json"),
              "w") as f:
        json.dump(result, f, indent=1)
    if not args.work_dir:
        shutil.rmtree(work)
    return device


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=11,
                   help="data seed")
    p.add_argument("--rows", type=int, default=FULL_ROWS,
                   help="corpus rows before the 95/5 split (cutting it is "
                        "said on a printed line; widths and users stay)")
    p.add_argument("--multichip", action="store_true",
                   help="four chips: the --mesh auto fit against the --mesh "
                        "none fit, and no other phase")
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="rehearsal on the CPU backend (virtual devices for "
                        "--multichip); never reports platform tpu")
    p.add_argument("--work-dir", default=None,
                   help="empty directory for data and models (kept); "
                        "default: a temporary one, removed on success")
    p.add_argument("--write-part", type=int, default=None,
                   help=argparse.SUPPRESS)
    args = p.parse_args(argv)
    if not os.path.isfile(os.path.join(REPO, "photon_ml_tpu", "cli",
                                       "train.py")):
        print("chip_smoke: FAILED: photon_ml_tpu is not beside this script",
              file=sys.stderr)
        return 2
    if args.write_part is not None:
        _write_part(args.seed, args.rows, args.work_dir, args.write_part)
        return 0
    t0 = time.perf_counter()
    try:
        device = run(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    _say(f"total: {time.perf_counter() - t0:.1f} s")
    _say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
