"""Training CLI: the single driver replacing both reference drivers.

reference: the legacy stage-machine Driver (photon-client/.../Driver.scala:71-739)
and the GAME training driver (photon-client/.../cli/game/training/Driver.scala:50-505)
are folded into one subcommand (SURVEY §7 "What NOT to port"):

  python -m photon_ml_tpu.cli.train \
      --train-data data.npz|data.libsvm --task logistic_regression \
      --output-dir out/ [--validation-data v.npz] [--config game.json]
      [--reg-weights 0.1,1,10] [--evaluators AUC,PRECISION@K:10:userId] ...

Without --config, a single fixed-effect coordinate over the "global" shard
is trained (the legacy single-GLM pipeline: preprocess -> train lambda sweep
-> validate -> select best); with --config (GameTrainingConfig JSON), the
full GAME coordinate-descent path runs.
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import sys
import time

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="photon-ml-tpu-train",
        description="Train GLM / GAME mixed-effect models on TPU (JAX)")
    p.add_argument("--train-data", required=True,
                   help=".npz GameDataset, .libsvm file, or Avro input "
                        "(.avro file, directory of .avro files, or glob)")
    p.add_argument("--validation-data", default=None)
    p.add_argument("--feature-shard-map", default=None,
                   help="Avro inputs: JSON (inline or @file) mapping shard "
                        "name -> list of feature-bag fields to merge, e.g. "
                        "'{\"global\": [\"features\"], \"per_user\": "
                        "[\"userFeatures\"]}' (reference: readMerged "
                        "featureColumnMap); default merges the 'features' "
                        "bag into one 'global' shard")
    p.add_argument("--index-map-dir", default=None,
                   help="directory of prebuilt per-shard index maps "
                        "(python -m photon_ml_tpu.cli.index); pins this "
                        "job's Avro ingest to that frozen feature space so "
                        "separate jobs share identical feature dimensions "
                        "and key->column assignment (reference: "
                        "FeatureIndexingJob + PalDBIndexMapLoader)")
    p.add_argument("--selected-features", default=None,
                   help="Avro file of FeatureAvro {name, term} records: "
                        "restrict training to exactly these features (+ "
                        "intercept), like the legacy driver's "
                        "selected-features file (reference: GLMSuite "
                        "selectedFeaturesFile).  Single-shard Avro input "
                        "only; exclusive with --index-map-dir")
    p.add_argument("--id-columns", default=None,
                   help="Avro inputs: comma-separated random-effect id tags "
                        "to extract (top-level field or metadataMap key)")
    p.add_argument("--input-columns", default=None,
                   help="Avro inputs: JSON remapping of input column names, "
                        "e.g. '{\"response\": \"label\", \"weight\": \"w\"}' "
                        "(reference: InputColumnsNames; keys: response, "
                        "offset, weight, uid)")
    p.add_argument("--input-date-range", default=None,
                   help="restrict date-partitioned input to "
                        "'yyyyMMdd-yyyyMMdd': reads "
                        "<train-data>/daily/YYYY/MM/DD per day (reference: "
                        "GameDriver.pathsForDateRange)")
    p.add_argument("--input-days-ago", default=None,
                   help="same as --input-date-range but as 'START-END' days "
                        "ago (e.g. '90-1'); mutually exclusive with it")
    p.add_argument("--validation-date-range", default=None,
                   help="date range for the VALIDATION input's daily/ tree "
                        "(each input resolves its own range, as in the "
                        "reference)")
    p.add_argument("--validation-days-ago", default=None,
                   help="days-ago range for the validation input")
    p.add_argument("--save-feature-stats", action="store_true",
                   help="persist per-shard BasicStatisticalSummary to "
                        "<output-dir>/feature-stats/<shard>.json (reference: "
                        "Driver.calculateAndSaveFeatureShardStats)")
    p.add_argument("--task", default="logistic_regression",
                   choices=["logistic_regression", "linear_regression",
                            "poisson_regression", "smoothed_hinge_loss_linear_svm"])
    p.add_argument("--output-dir", required=True)
    p.add_argument("--config", default=None,
                   help="GameTrainingConfig JSON file (enables GAME path)")
    p.add_argument("--optimizer", default="lbfgs", choices=["lbfgs", "tron"])
    p.add_argument("--regularization", default="l2",
                   choices=["none", "l1", "l2", "elastic_net"])
    p.add_argument("--elastic-net-alpha", type=float, default=None)
    p.add_argument("--reg-weights", default="1.0",
                   help="comma-separated lambda sweep (legacy path)")
    p.add_argument("--max-iterations", type=int, default=None)
    p.add_argument("--tolerance", type=float, default=None)
    p.add_argument("--normalization", default="none",
                   choices=["none", "scale_with_standard_deviation",
                            "scale_with_max_magnitude", "standardization"])
    p.add_argument("--evaluators", default=None,
                   help="comma-separated, e.g. AUC,RMSE,PRECISION@K:10:userId")
    p.add_argument("--compute-variances", action="store_true")
    p.add_argument("--x64", action="store_true", help="float64 (parity runs)")
    p.add_argument("--verbose", action="store_true")
    p.add_argument("--mesh", default="auto",
                   help="'auto' = all local devices on the data axis, 'none' "
                        "= single device, or 'DxF' (e.g. '4x2' = 4-way data "
                        "x 2-way feature sharding; F > 1 trains dense fixed "
                        "effects on the feature-axis consensus-ADMM lane).  "
                        "On a multi-process run the device list is GLOBAL "
                        "(every host's devices, processes contiguous on the "
                        "data axis)")
    # multi-host bring-up (parallel/multihost.py): all three fall back to
    # $PHOTON_COORDINATOR / $PHOTON_NUM_PROCESSES / $PHOTON_PROCESS_ID so
    # pod launchers can export identity instead of templating argv
    p.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="multi-host runs: process 0's coordination "
                        "endpoint (jax.distributed); required when "
                        "--num-processes > 1")
    p.add_argument("--num-processes", type=int, default=None,
                   help="total processes in this run (1 = single-process, "
                        "the default); a relaunch after a lost worker "
                        "passes the SMALLER survivor count and resumes "
                        "from --checkpoint-dir")
    p.add_argument("--process-id", type=int, default=None,
                   help="this process's id in [0, num-processes); process "
                        "0 owns every durable write (checkpoints, models, "
                        "summaries)")
    p.add_argument("--data-validation", default="full",
                   choices=["full", "sample", "disabled"],
                   help="input sanity-check intensity (reference: "
                        "DataValidationType VALIDATE_FULL/SAMPLE/DISABLED)")
    p.add_argument("--no-weight-check", action="store_true",
                   help="allow rows with weights <= 0 (the cheap rejection "
                        "otherwise runs even under --data-validation "
                        "disabled, like the reference's separate checkData "
                        "flag)")
    # hyperparameter tuning (reference: GameTrainingParams tuning mode +
    # Driver.runHyperparameterTuning, cli/game/training/Driver.scala:337-373)
    p.add_argument("--tuning", default="none",
                   choices=["none", "random", "bayesian"])
    p.add_argument("--tuning-iterations", type=int, default=10)
    p.add_argument("--tuning-range", default="-3,3",
                   help="log10 lambda search range 'lo,hi' per coordinate")
    p.add_argument("--sweep-seed", type=int, default=None,
                   help="seed for the hyperparameter search (candidate "
                        "draws + GP slice sampler): a fixed seed reproduces "
                        "the candidate sequence bit-identically; default = "
                        "the training config's seed")
    p.add_argument("--warm-start", action="store_true",
                   help="initialize each grid combo / tuning refit from the "
                        "previous (best) model (reference: use-warm-start, "
                        "GameTrainingParams.scala:197)")
    p.add_argument("--event-listener", action="append", default=[],
                   help="dotted class path of an EventListener to register "
                        "(repeatable; reference: Driver.scala:108-118)")
    p.add_argument("--profile", action="store_true",
                   help="record a jax.profiler trace of the training run "
                        "into <output-dir>/profile (the TPU-native "
                        "replacement for the reference's Timed/Spark event "
                        "log; view with TensorBoard or xprof)")
    p.add_argument("--trace-out", default=None, metavar="TRACE.json",
                   help="arm the telemetry span tracer and write a Chrome-"
                        "trace/Perfetto JSON timeline of the fit (outer "
                        "iterations -> coordinate visits -> solves / chunk "
                        "staging / checkpoint writes, with fault/"
                        "quarantine/recovery events attached to their "
                        "spans); open at https://ui.perfetto.dev.  "
                        "Disarmed (the default) the instrumentation is a "
                        "module-global None check — zero overhead")
    p.add_argument("--run-log", default=None, metavar="RUN.jsonl",
                   help="JSONL run log: one line per finished span and "
                        "instant event (EventEmitter events, fault "
                        "injections, quarantine rollbacks, checkpoint "
                        "recoveries), correlated by span id with "
                        "--trace-out; arms the tracer like --trace-out")
    p.add_argument("--no-compile-cache", action="store_true",
                   help="disable the persistent XLA compilation cache (on "
                        "by default so repeat invocations skip compiles; "
                        "cache dir: $JAX_COMPILATION_CACHE_DIR, else "
                        "<checkout>/.jax_cache)")
    p.add_argument("--model-format", default="npz",
                   choices=["npz", "avro", "reference"],
                   help="best-model output format; avro writes the "
                        "reference's BayesianLinearModelAvro / "
                        "LatentFactorAvro interchange records; reference "
                        "writes the Scala reference's own directory layout "
                        "(part-*.avro + id-info) that photon-ml itself "
                        "can load")
    p.add_argument("--initial-model-dir", default=None,
                   help="warm-start every coordinate this model covers "
                        "(npz, avro, or a reference-layout directory that "
                        "actual photon-ml wrote); beyond the reference, "
                        "whose warm start is intra-sweep only")
    p.add_argument("--checkpoint-dir", default=None,
                   help="persist the model after every outer coordinate-"
                        "descent iteration and resume from the latest "
                        "record on restart; sweeps checkpoint per grid "
                        "combo (the reference restarts failed jobs from "
                        "scratch)")
    p.add_argument("--hbm-budget", default=None,
                   help="device-memory residency budget, e.g. '8GB', "
                        "'512MB', or raw bytes — PER DEVICE on a mesh "
                        "(blocks shard 1/D per chip, so aggregate fit size "
                        "scales with fleet HBM).  When the training "
                        "coordinates' device blocks can't all fit: "
                        "fixed-effect shards over budget stream in double-"
                        "buffered host->device chunks (sharded over the "
                        "mesh when one is active), and inactive "
                        "coordinates' blocks are evicted between "
                        "coordinate-descent visits (out-of-core training — "
                        "fit size bounded by host memory, not HBM; see "
                        "COMPONENTS.md 'Memory modes').  Overrides the "
                        "config file's hbm_budget_bytes")
    p.add_argument("--timing-mode", default="pipelined",
                   choices=["pipelined", "strict"],
                   help="pipelined (default): device work for the next "
                        "coordinate is enqueued while the previous one's "
                        "bookkeeping is in flight — objectives/metrics "
                        "fetched in one batched readback per outer "
                        "iteration, checkpoints written by a background "
                        "thread.  strict: sync after every update (same "
                        "math bit-for-bit; per-phase timings stay "
                        "attributable to the device work they launched)")
    p.add_argument("--fault-plan", default=None,
                   help="ARM FAULT INJECTION (testing/chaos runs only): "
                        "FaultPlan JSON (inline or @file) of named "
                        "injection sites x trigger hits/probabilities "
                        "(utils/faults.py; same format as the "
                        "PHOTON_FAULT_PLAN env var, which also works).  "
                        "With no plan the injection sites are zero-"
                        "overhead no-ops.  On SIGTERM/SIGINT the trainer "
                        "exits RESUMABLY (status 75, EX_TEMPFAIL) after "
                        "finishing the in-flight coordinate update and "
                        "making the newest checkpoint durable")
    return p


def make_mesh_from_arg(mesh_arg: str):
    """'auto' | 'none' | 'DxF' -> Mesh or None.  The default builds a mesh
    over ALL local devices — the distributed path IS the product path
    (the reference driver is always distributed: Driver.scala:50-505)."""
    if mesh_arg == "none":
        return None
    from photon_ml_tpu.parallel import make_mesh
    if mesh_arg == "auto":
        return make_mesh()
    d, _, f = mesh_arg.partition("x")
    return make_mesh(int(d), int(f) if f else 1)


def resolve_avro_paths(path: str):
    """'.avro' file, directory of .avro files, or glob -> sorted paths, or
    None when `path` is not an Avro input.  A directory or glob that yields
    NO .avro files is an explicit error, not a silent fall-through."""
    import glob as _glob
    if os.path.isdir(path):
        found = sorted(_glob.glob(os.path.join(path, "*.avro")))
        if not found:
            raise SystemExit(f"no .avro files found in directory {path!r}")
        return found
    if "*" in path or "?" in path:
        found = sorted(p for p in _glob.glob(path) if p.endswith(".avro"))
        if not found:
            raise SystemExit(f"glob {path!r} matched no .avro files")
        return found
    if path.endswith(".avro"):
        return [path]
    return None


def parse_byte_size(arg) -> int:
    """'8GB' / '512MB' / '1.5g' / '4096' -> bytes (decimal units, like
    accelerator spec sheets)."""
    if arg is None:
        return None
    s = str(arg).strip().lower()
    units = {"tb": 1e12, "t": 1e12, "gb": 1e9, "g": 1e9, "mb": 1e6,
             "m": 1e6, "kb": 1e3, "k": 1e3, "b": 1.0}
    for suffix, mult in units.items():
        if s.endswith(suffix):
            num = s[: -len(suffix)].strip()
            break
    else:
        num, mult = s, 1.0
    try:
        value = float(num) * mult
    except ValueError:
        raise SystemExit(f"--hbm-budget: cannot parse {arg!r} (expected "
                         "e.g. '8GB', '512MB', or raw bytes)")
    if value <= 0:
        raise SystemExit(f"--hbm-budget must be positive, got {arg!r}")
    return int(value)


def _load_json_arg(arg: str):
    """Shared 'inline JSON or @file' convention for CLI JSON flags."""
    if arg.startswith("@"):
        with open(arg[1:]) as f:
            return json.loads(f.read())
    return json.loads(arg)


def parse_input_columns(arg):
    """JSON column remap -> InputColumnNames (reference: InputColumnsNames
    remappable response/offset/weight/uid names)."""
    from photon_ml_tpu.data.game_data import InputColumnNames
    if arg is None:
        return InputColumnNames()
    import dataclasses as _dc
    m = _load_json_arg(arg)
    allowed = {f.name for f in _dc.fields(InputColumnNames)}
    if not isinstance(m, dict) or not all(
            isinstance(v, str) and v for v in m.values()):
        raise SystemExit("--input-columns must be a JSON object mapping "
                         "column roles to non-empty string column names")
    bad = set(m) - allowed
    if bad:
        raise SystemExit(f"--input-columns: unknown keys {sorted(bad)} "
                         f"(allowed: {sorted(allowed)})")
    return InputColumnNames(**m)


def parse_feature_shard_map(arg):
    """JSON inline or @file -> {shard: [bags]}; default single-shard merge
    of the TrainingExampleAvro 'features' bag."""
    if arg is None:
        return {"global": ["features"]}
    m = _load_json_arg(arg)
    if not isinstance(m, dict) or not all(
            isinstance(v, list) and v for v in m.values()):
        raise SystemExit("--feature-shard-map must be a JSON object mapping "
                         "shard name -> non-empty list of bag fields")
    return m


def _load_dataset(path: str, task: str, args=None, train_dataset=None,
                  date_range=None, days_ago=None, pinned_maps=None):
    """`train_dataset` pins a validation read to the TRAINING feature/entity
    spaces: separately-scanned Avro validation data would otherwise build
    its own sorted vocabularies and silently misalign columns with the
    trained coefficients.  `date_range`/`days_ago` expand the path's
    daily/YYYY/MM/DD tree (each input resolves its own range, reference:
    GameDriver.pathsForDateRange)."""
    import glob as _glob

    from photon_ml_tpu.data import build_game_dataset, read_libsvm
    from photon_ml_tpu.data.game_data import load_game_dataset
    if path.endswith(".libsvm") or path.endswith(".txt"):
        if pinned_maps is not None:
            raise SystemExit(
                "a pinned feature space (--index-map-dir / "
                "--selected-features) requires Avro training input: LIBSVM "
                "features are positional, not (name, term)-keyed")
        x, y = read_libsvm(path)
        return build_game_dataset(y, {"global": x})
    if date_range or days_ago:
        from photon_ml_tpu.data.date_range import paths_for_date_range
        day_dirs = paths_for_date_range(path, date_range, days_ago)
        # a day dir without .avro files (e.g. only a _SUCCESS marker) is
        # skipped, matching the reference's errorOnMissing=false posture;
        # only a range yielding NOTHING is an error
        avro_paths = []
        for d in day_dirs:
            avro_paths.extend(sorted(_glob.glob(os.path.join(d, "*.avro"))))
        if not avro_paths:
            raise SystemExit(
                f"no .avro files under any day directory of {path!r} "
                "for the requested date range")
    else:
        avro_paths = resolve_avro_paths(path)
    if avro_paths is not None:
        # reference: AvroDataReader.readMerged + GameConverters — the
        # primary input path of the GAME training driver
        from photon_ml_tpu.data.avro_game import read_game_examples
        shard_map = parse_feature_shard_map(
            getattr(args, "feature_shard_map", None) if args else None)
        id_cols = (getattr(args, "id_columns", None) or "") if args else ""
        if train_dataset is not None and not train_dataset.index_maps:
            # a libsvm/npz training input carries no (name,term) index maps,
            # so an Avro validation read has nothing to pin its columns to —
            # the scanned vocabulary would silently misalign with the
            # trained coefficients
            raise SystemExit(
                "Avro validation data requires the training input to carry "
                "feature index maps (train from Avro, or from an npz "
                "GameDataset saved with index maps); the training dataset "
                "has none, so validation columns cannot be aligned with the "
                "trained model's feature space")
        result = read_game_examples(
            avro_paths, shard_map,
            id_columns=[c for c in id_cols.split(",") if c],
            columns=parse_input_columns(
                getattr(args, "input_columns", None) if args else None),
            index_maps=(pinned_maps if pinned_maps is not None
                        else train_dataset.index_maps or None
                        if train_dataset is not None else None),
            entity_vocabs=(train_dataset.entity_vocabs or None
                           if train_dataset is not None else None))
        return result.dataset
    if pinned_maps is not None:
        raise SystemExit(
            "a pinned feature space (--index-map-dir / --selected-features) "
            "requires Avro training input; an npz GameDataset already "
            "carries its feature spaces")
    return load_game_dataset(path)


def _model_arrays(model):
    """Every device array of a GameModel's coordinates."""
    import jax
    return [v for m in model.coordinates.values()
            for v in jax.tree_util.tree_leaves(vars(m))
            if isinstance(v, jax.Array)]


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    # stderr stays quiet unless --verbose (configured only when no host
    # application has set up logging; basicConfig is a no-op otherwise and
    # we must not touch a host's handlers or levels)
    if not logging.getLogger().handlers:
        logging.basicConfig(
            level=logging.INFO,
            format="%(asctime)s %(message)s", stream=sys.stderr)
        # gate stderr on the HANDLER we just created: package INFO records
        # propagate past the root logger's level, so the handler level is
        # what actually keeps stderr quiet without --verbose
        for h in logging.getLogger().handlers:
            h.setLevel(logging.INFO if args.verbose else logging.WARNING)
    # persisted job log: the package logger always captures INFO into
    # <output-dir>/training.log regardless of the host/root configuration
    # (reference: PhotonLogger writes the job log next to the job output on
    # HDFS, photon-lib/.../util/PhotonLogger.scala:36-521)
    pkg_logger = logging.getLogger("photon_ml_tpu")
    prev_level = pkg_logger.level
    pkg_logger.setLevel(logging.INFO)
    os.makedirs(args.output_dir, exist_ok=True)
    # multi-process runs share one output dir: each non-primary process
    # logs to its own file so N writers never interleave one stream
    from photon_ml_tpu.parallel import multihost
    _pid = (args.process_id if args.process_id is not None
            else multihost.process_index())
    _log_name = "training.log" if _pid == 0 else f"training.proc{_pid}.log"
    _fh = logging.FileHandler(os.path.join(args.output_dir, _log_name))
    _fh.setFormatter(logging.Formatter("%(asctime)s %(levelname)s %(message)s"))
    _fh.setLevel(logging.INFO)
    pkg_logger.addHandler(_fh)
    log = logging.getLogger("photon_ml_tpu.train")
    try:
        return _run(args, log)
    finally:
        # main() is a callable API: don't leak this job's log handler into
        # the next in-process call, whatever stage raised
        pkg_logger.removeHandler(_fh)
        pkg_logger.setLevel(prev_level)
        _fh.close()


def _run(args, log) -> int:
    log.info("args: %s", vars(args))

    import jax
    if args.x64:
        jax.config.update("jax_enable_x64", True)

    # multi-host bring-up (parallel/multihost.py) — BEFORE anything touches
    # jax devices: jax.distributed can only join a cluster on a fresh
    # backend.  Identity falls back to $PHOTON_* env vars; a single-process
    # invocation with none of the flags/env set skips all of this.
    from photon_ml_tpu.parallel import multihost
    watchdog = None
    if (args.coordinator is not None or args.num_processes is not None
            or args.process_id is not None
            or os.environ.get(multihost.ENV_COORDINATOR)
            or os.environ.get(multihost.ENV_NUM_PROCESSES)):
        multihost.initialize(args.coordinator, args.num_processes,
                             args.process_id)
    if multihost.active():
        if args.validation_data or args.tuning != "none":
            raise SystemExit(
                "--validation-data/--tuning are not supported on a "
                "multi-process run yet: the validation plane scores with "
                "process-LOCAL arrays, which cannot mix with the global "
                "training placements.  Validate the saved model in a "
                "separate single-process job.")
        if args.mesh == "none":
            raise SystemExit(
                "--mesh none contradicts a multi-process run: without a "
                "global mesh each process would train its own local copy")
        watchdog = multihost.WorkerWatchdog(
            args.output_dir,
            interval_s=float(os.environ.get(
                "PHOTON_HEARTBEAT_INTERVAL", 0.5)),
            timeout_s=float(os.environ.get(
                "PHOTON_HEARTBEAT_TIMEOUT", 10.0)),
            escalate_s=float(os.environ.get(
                "PHOTON_HEARTBEAT_ESCALATE", 10.0))).start()
        multihost.set_watchdog(watchdog)
        log.info("multihost: process %d/%d, watchdog armed "
                 "(timeout %.1fs, escalate %.1fs)",
                 multihost.process_index(), multihost.process_count(),
                 watchdog.timeout_s, watchdog.escalate_s)

    # fault containment control plane (utils/faults.py): an env- or
    # flag-armed injection plan (chaos/testing runs), and SIGTERM/SIGINT
    # graceful preemption — finish the in-flight coordinate update, make
    # the newest checkpoint durable, exit with the resumable status 75
    from photon_ml_tpu.utils import faults
    fault_plan = faults.install_from_env()
    if args.fault_plan:
        fault_plan = faults.FaultPlan.from_dict(
            _load_json_arg(args.fault_plan))
        faults.install_plan(fault_plan)
        log.warning("fault plan ACTIVE from --fault-plan: %d spec(s)",
                    len(fault_plan.specs))

    # telemetry (photon_ml_tpu/telemetry): the span tracer arms only when
    # a timeline was asked for — disarmed it is a module-global None check
    # on every instrumented path.  The metrics registry is always live.
    from photon_ml_tpu import telemetry
    tracer = None
    if args.trace_out or args.run_log:
        tracer = telemetry.install(run_log=args.run_log, proc="train")
        log.info("telemetry armed: trace_out=%s run_log=%s",
                 args.trace_out, args.run_log)

    # persistent compile cache + honest compile accounting (the reference
    # pays no compile cost — JVM/Breeze interprets; a warm cache is our
    # equivalent posture, and compile_s in the summary proves it worked)
    from photon_ml_tpu.utils.jax_cache import (CompileTimeTracker,
                                               enable_persistent_cache)
    compile_tracker = CompileTimeTracker().install()
    cache_dir = None
    if not args.no_compile_cache:
        cache_dir = enable_persistent_cache()
        log.info("persistent compile cache: %s", cache_dir)

    from photon_ml_tpu.game import GameEstimator, GameTrainingConfig
    from photon_ml_tpu.game.config import (FixedEffectCoordinateConfig,
                                           GLMOptimizationConfig)
    from photon_ml_tpu.models.io import save_game_model
    from photon_ml_tpu.ops.normalization import NormalizationType
    from photon_ml_tpu.optim import (OptimizerConfig, OptimizerType,
                                     RegularizationContext, RegularizationType)

    t0 = time.time()
    pinned_maps = None
    if args.selected_features:
        # reference: the legacy driver's selected-features file (GLMSuite
        # selectedFeaturesFile) — a FeatureAvro list freezing the feature
        # space to exactly those (name, term) keys + intercept
        if args.index_map_dir:
            raise SystemExit("--selected-features and --index-map-dir are "
                             "exclusive (both pin the feature space)")
        if args.feature_shard_map:
            raise SystemExit("--selected-features applies to the default "
                             "single-shard ingest only (the legacy driver's "
                             "scope); build maps with cli.index for "
                             "multi-shard jobs")
        from photon_ml_tpu.data.avro_codec import read_container
        from photon_ml_tpu.data.index_map import IndexMap, feature_key
        keys = [feature_key(r["name"], r.get("term") or "")
                for r in read_container(args.selected_features)]
        if not keys:
            raise SystemExit(f"--selected-features {args.selected_features!r}"
                             " names no features")
        pinned_maps = {"global": IndexMap.from_keys(keys)}
        log.info("feature space restricted to %d selected features",
                 len(keys))
    if args.index_map_dir:
        # frozen shared feature space (reference: FeatureIndexingJob +
        # PalDBIndexMapLoader): jobs trained against the same prebuilt maps
        # are guaranteed identical feature dimensions and key->column
        # assignment, whatever data slice each one saw
        from photon_ml_tpu.data.index_map import IndexMapCollection
        pinned_maps = IndexMapCollection.load(args.index_map_dir).shards
        log.info("pinned feature spaces from %s: %s", args.index_map_dir,
                 {s: m.size for s, m in pinned_maps.items()})
    train = _load_dataset(args.train_data, args.task, args,
                          date_range=args.input_date_range,
                          days_ago=args.input_days_ago,
                          pinned_maps=pinned_maps)
    val = (_load_dataset(args.validation_data, args.task, args,
                         train_dataset=train,
                         date_range=args.validation_date_range,
                         days_ago=args.validation_days_ago)
           if args.validation_data else None)
    ingest_s = time.time() - t0
    log.info("loaded train: %d rows, shards %s", train.num_rows,
             {s: x.shape[1] for s, x in train.feature_shards.items()})
    print(f"loaded train: {train.num_rows} rows, shards "
          f"{ {s: x.shape[1] for s, x in train.feature_shards.items()} }",
          file=sys.stderr)

    # reference: Driver.run -> DataValidators.sanityCheckDataFrameForTraining
    # (validate against the task actually trained: the config file's
    # task_type wins over --task on the GAME path)
    from photon_ml_tpu.data.validators import validate_game_dataset
    task = args.task
    if args.config:
        with open(args.config) as f:
            task = GameTrainingConfig.from_json(f.read()).task_type
    validate_game_dataset(train, task, args.data_validation,
                          check_weights=not args.no_weight_check)
    if val is not None:
        validate_game_dataset(val, task, args.data_validation,
                              check_weights=not args.no_weight_check)

    if args.save_feature_stats and multihost.is_primary():
        # reference: cli/game/training/Driver.calculateAndSaveFeatureShardStats
        # (Driver.scala:297) — per-shard BasicStatisticalSummary persisted
        # next to the job output (process 0 only on a multi-process run:
        # every process sees the same full host dataset)
        from photon_ml_tpu.data.stats import BasicStatisticalSummary
        stats_dir = os.path.join(args.output_dir, "feature-stats")
        os.makedirs(stats_dir, exist_ok=True)
        for shard, x in train.feature_shards.items():
            summary = (BasicStatisticalSummary.from_sparse(x, train.weights)
                       if hasattr(x, "tocsr") and not isinstance(x, np.ndarray)
                       else BasicStatisticalSummary.from_features(
                           np.asarray(x), train.weights))
            payload = summary.to_dict()
            imap = (train.index_maps or {}).get(shard)
            if imap is None:
                log.info("shard %r carries no index map: JSON stats only "
                         "(FeatureSummarizationResultAvro keys features by "
                         "name/term)", shard)
            else:
                payload["feature_keys"] = [str(k) for k in imap.index_to_key]
                # the reference's own interchange format alongside the JSON
                # (FeatureSummarizationResultAvro, one record per feature;
                # ModelProcessingUtils.writeBasicStatistics)
                from photon_ml_tpu.data.avro_io import write_feature_stats_avro
                avro_dir = os.path.join(stats_dir, shard)
                os.makedirs(avro_dir, exist_ok=True)
                write_feature_stats_avro(
                    os.path.join(avro_dir, "part-00000.avro"), summary, imap)
            with open(os.path.join(stats_dir, f"{shard}.json"), "w") as f:
                json.dump(payload, f)
        log.info("feature stats saved to %s", stats_dir)

    mesh = make_mesh_from_arg(args.mesh)
    if mesh is not None:
        from photon_ml_tpu.parallel.mesh import FEATURE_AXIS
        lanes = (" (feature axis > 1: dense fixed effects use the "
                 "consensus-ADMM lane)"
                 if mesh.shape.get(FEATURE_AXIS, 1) > 1 else "")
        print(f"mesh: {dict(mesh.shape)} over {len(mesh.devices.ravel())} "
              f"devices{lanes}", file=sys.stderr)
    evaluator_specs = args.evaluators.split(",") if args.evaluators else None

    # event hooks (reference: Driver.scala:108-118 registers listeners by
    # class name; PhotonSetupEvent carries the run params)
    from photon_ml_tpu.utils.events import EventEmitter, SetupEvent
    emitter = EventEmitter() if args.event_listener else None
    if emitter is not None:
        for dotted in args.event_listener:
            emitter.register_listener_class(dotted)
        emitter.send_event(SetupEvent(params=vars(args)))

    profile_ctx = None
    if args.profile:
        profile_dir = os.path.join(args.output_dir, "profile")
        os.makedirs(profile_dir, exist_ok=True)
        profile_ctx = jax.profiler.trace(profile_dir)
        profile_ctx.__enter__()
        print(f"profiling to {profile_dir}", file=sys.stderr)

    preempt_guard = faults.GracefulPreemption()
    preempt_guard.__enter__()
    try:
        initial_model = None
        if args.initial_model_dir:
            # cross-job warm start (BEYOND the reference, whose warm start
            # is intra-sweep only): any supported layout loads here,
            # including a model directory actual photon-ml wrote.  The
            # model re-keys into THIS job's feature spaces — a
            # reference-layout model stores a compact space (zeros
            # dropped), and a different data slice scans a different
            # vocabulary, so raw coefficients would misalign.
            from photon_ml_tpu.models.io import (align_game_model_to_dataset,
                                                 load_game_model,
                                                 load_model_index_maps)
            initial_model, _ = load_game_model(args.initial_model_dir)
            try:
                initial_model = align_game_model_to_dataset(
                    initial_model,
                    load_model_index_maps(args.initial_model_dir), train)
            except ValueError as e:
                raise SystemExit(f"--initial-model-dir: {e}")
            log.info("warm-starting from %s (%s)", args.initial_model_dir,
                     list(initial_model.coordinates))
        hbm_budget = parse_byte_size(args.hbm_budget)
        if args.config:
            import dataclasses as _dc
            with open(args.config) as f:
                config = GameTrainingConfig.from_json(f.read())
            if hbm_budget is not None:
                config = _dc.replace(config, hbm_budget_bytes=hbm_budget)
            results = [GameEstimator(config, mesh=mesh, emitter=emitter).fit(
                train, val, evaluator_specs,
                initial_model=initial_model,
                checkpoint_dir=args.checkpoint_dir,
                timing_mode=args.timing_mode)]
        else:
            # legacy single-GLM path: one FE coordinate, lambda sweep, best by
            # first validation evaluator (reference: Driver stage machine +
            # ModelSelection)
            reg = RegularizationContext(RegularizationType(args.regularization),
                                        args.elastic_net_alpha)
            opt = OptimizerConfig(optimizer=OptimizerType(args.optimizer),
                                  max_iterations=args.max_iterations,
                                  tolerance=args.tolerance)
            weights = [float(w) for w in args.reg_weights.split(",")]
            grid = {"fixed": [GLMOptimizationConfig(optimizer=opt, regularization=reg,
                                                    regularization_weight=w)
                              for w in sorted(weights, reverse=True)]}
            config = GameTrainingConfig(
                task_type=args.task,
                coordinates={"fixed": FixedEffectCoordinateConfig(
                    "global", GLMOptimizationConfig(optimizer=opt, regularization=reg),
                    normalization=NormalizationType(args.normalization))},
                updating_sequence=["fixed"],
                hbm_budget_bytes=hbm_budget)
            results = GameEstimator(config, mesh=mesh, emitter=emitter).fit_grid(
                train, grid, val, evaluator_specs, warm_start=args.warm_start,
                checkpoint_dir=args.checkpoint_dir,
                initial_model=initial_model, timing_mode=args.timing_mode)

        if args.tuning != "none":
            # reference: Driver.runHyperparameterTuning — searcher seeded with
            # the grid results, evaluation = refit with the candidate lambdas
            if val is None:
                raise SystemExit("--tuning requires --validation-data")
            from photon_ml_tpu.hyperparameter import (
                GameEstimatorEvaluationFunction, GaussianProcessSearch, RandomSearch)
            fn = GameEstimatorEvaluationFunction(
                GameEstimator(config, mesh=mesh, emitter=emitter), train, val,
                evaluator_specs, scale="log", warm_start=args.warm_start,
                initial_model=initial_model)
            if args.warm_start:
                for r in results:
                    if r.validation:
                        fn.observe(r)
            lo, hi = (float(v) for v in args.tuning_range.split(","))
            ranges = [(lo, hi)] * fn.num_params
            spec0 = results[0].validation_specs[0]
            # --sweep-seed pins the WHOLE search chain (candidate draws,
            # GP estimator init, slice sampler) independently of the
            # training seed: a fixed value reproduces the candidate
            # sequence bit-identically
            sweep_seed = (args.sweep_seed if args.sweep_seed is not None
                          else config.seed)
            if args.tuning == "bayesian":
                search = GaussianProcessSearch(ranges, fn, spec0.evaluator,
                                               seed=sweep_seed)
            else:
                search = RandomSearch(ranges, fn, seed=sweep_seed)
            prior = [r for r in results if r.validation]
            results = results + search.find(args.tuning_iterations, prior)

        from photon_ml_tpu.game.estimator import select_best_result
        best = select_best_result(results)
        os.makedirs(args.output_dir, exist_ok=True)
        if multihost.is_primary():
            # process 0 owns every durable artifact (photonlint PH014);
            # peers trained the SAME model — GSPMD reductions leave the
            # coefficients replicated — so one writer loses nothing
            save_game_model(best.model,
                            os.path.join(args.output_dir, "best"),
                            config=best.config,
                            index_maps=train.index_maps or None,
                            format=args.model_format)
        # per-coordinate inner-solver accounting (SolveResult already
        # carried iterations + ConvergenceReason; the fit summary now
        # surfaces them instead of dropping them on the floor)
        solver_diag = best.descent.solver_diagnostics()
        from photon_ml_tpu.utils.devices import device_memory, device_summary
        summary = {
            "task": args.task,
            # the device the fit ran on, read off the trained model's own
            # arrays (a caller that started this process as a child — one
            # process per chip — cannot ask jax itself)
            "device": device_summary(_model_arrays(best.model)),
            "device_memory": device_memory(),
            "train_rows": train.num_rows,
            "ingest_s": round(ingest_s, 2),
            "num_configs": len(results),
            "objective_history": [float(v) for v in best.objective_history],
            "final_objective": best.objective_history[-1],
            "validation": best.validation,
            "solver_iterations_total": best.descent.total_iterations(),
            "solver_diagnostics": solver_diag,
            # per random-effect coordinate: active / passive / discarded
            # rows, capped entities, padded cells, bucket shapes
            "coordinate_build": best.coordinate_build,
            # fault containment accounting: quarantine events (rollbacks /
            # tightened retries / freezes), coordinates left frozen, how
            # the checkpoint was recovered at resume, and — on chaos runs —
            # the injection plan's per-site fire counts
            "containment_events": best.descent.containment_events,
            "frozen_coordinates": best.descent.frozen_coordinates,
            "checkpoint_recovery": best.checkpoint_recovery,
            "fault_report": (fault_plan.report() if fault_plan is not None
                             else None),
            "wall_s": round(time.time() - t0, 2),
            "timing_mode": args.timing_mode,
            # HBM residency accounting (None budget = unbounded/resident;
            # PER-DEVICE semantics on a mesh — accounting carries
            # per_device/data_devices)
            "hbm_budget_bytes": hbm_budget,
            "hbm_residency": getattr(best, "residency", None),
            # multi-chip accounting: mesh axes + cold/warm staged bytes
            # (mesh_transfer proves a warm iteration moves only
            # coefficients/offsets, never the dataset)
            "mesh": dict(mesh.shape) if mesh is not None else None,
            "mesh_transfer": getattr(best, "mesh_transfer", None),
            # multi-host accounting: identity + whether the mesh spans
            # processes (mesh_transfer bytes above are PER-PROCESS there)
            "multihost": ({"num_processes": multihost.process_count(),
                           "process_id": multihost.process_index()}
                          if multihost.active() else None),
            "host_blocked_s": round(
                getattr(getattr(best.descent, "timings", None),
                        "host_blocked_total", lambda: 0.0)(), 3),
            # the contiguous phase spans of the best fit (build, init,
            # per-coordinate solve/score, validation, checkpoint)
            "phase_timings_s": {
                name: round(t, 3) for name, t in
                getattr(best.descent, "timings", {}).items()},
            "compile_s": round(compile_tracker.seconds, 2),
            "compile_count": compile_tracker.count,
            "compile_cache": cache_dir,
            # the unified telemetry surface: registry counters/gauges/
            # histograms (stream/mesh/checkpoint/quarantine/retrace
            # accounting) + tracer record counts when armed
            "telemetry": telemetry.snapshot(),
            "trace_out": args.trace_out,
            "output": os.path.join(args.output_dir, "best"),
        }
        if multihost.is_primary():
            with open(os.path.join(args.output_dir,
                                   "training-summary.json"), "w") as f:
                json.dump(summary, f, indent=2)
        log.info("summary: %s", summary)
        for coord, d in solver_diag.items():
            log.info("solver %-16s solves=%d iterations=%d reasons=%s "
                     "caps=%s", coord, d["solves"], d["iterations"],
                     d["reasons"], d["iteration_caps"])
            if "stream" in d:
                st = d["stream"]
                log.info("stream %-16s staged=%.1f MB chunks=%d "
                         "local_epochs=%d examples=%d "
                         "examples/staged-byte=%.4f", coord,
                         st["total_bytes"] / 1e6, st["chunks_staged"],
                         st["local_epochs"], st["examples_processed"],
                         st["examples_per_staged_byte"])
        if mesh is not None and summary["mesh_transfer"] is not None:
            acct = summary["hbm_residency"] or {}
            log.info(
                "mesh %s: staged %.1f MB cold / %.1f MB warm; per-device "
                "peak %.1f MB (budget %s)", dict(mesh.shape),
                summary["mesh_transfer"]["cold_bytes"] / 1e6,
                summary["mesh_transfer"]["warm_bytes"] / 1e6,
                acct.get("peak_tracked_bytes", 0) / 1e6,
                ("%.1f MB" % (acct["budget_bytes"] / 1e6)
                 if acct.get("budget_bytes") else "unbounded"))
        for name, t in getattr(best.descent, "timings", {}).items():
            log.info("phase %s: %.3fs", name, t)
        print(json.dumps(summary))
        return 0
    except faults.Preempted as e:
        # graceful preemption (SIGTERM/SIGINT): the in-flight coordinate
        # update finished and the newest checkpoint record is durable —
        # report resumability and exit with the DISTINCT status 75
        # (EX_TEMPFAIL) so schedulers relaunch the same command
        payload = {
            "preempted": True,
            "completed_iterations": e.completed_iterations,
            "resumable": e.checkpointed,
            "checkpoint_dir": e.checkpoint_dir,
            "exit_status": faults.EXIT_PREEMPTED,
            "lost_worker": (watchdog.lost_process
                            if watchdog is not None else None),
            "wall_s": round(time.time() - t0, 2),
        }
        log.warning("preempted: %s", e)
        if multihost.is_primary():
            with open(os.path.join(args.output_dir,
                                   "training-summary.json"), "w") as f:
                json.dump(payload, f, indent=2)
        print(json.dumps(payload))
        return faults.EXIT_PREEMPTED
    except Exception:
        # a peer died mid-collective: gloo/XLA surface that as an opaque
        # RuntimeError in the MAIN thread within milliseconds — typically
        # BEFORE the watchdog's heartbeat timeout has elapsed — so poll
        # the peer heartbeats synchronously to tell a dead peer apart
        # from a genuine local crash.  With a confirmed loss this process
        # is a SURVIVOR — exit with the resumable status 75 (checkpoint
        # state is durable + manifest-consistent), not a crash.
        lost = watchdog.confirm_lost() if watchdog is not None else None
        if lost is not None:
            log.error("multihost: collective failed after losing worker "
                      "%d — exiting resumably (status %d)",
                      lost, faults.EXIT_PREEMPTED, exc_info=True)
            print(json.dumps({
                "preempted": True, "resumable": True,
                "lost_worker": lost,
                "exit_status": faults.EXIT_PREEMPTED}))
            return faults.EXIT_PREEMPTED
        raise
    finally:
        preempt_guard.__exit__(None, None, None)
        if profile_ctx is not None:
            profile_ctx.__exit__(None, None, None)
        if tracer is not None:
            # export on EVERY path (success, preemption, failure): a
            # timeline of the run that died is the one you want most
            telemetry.shutdown()
            if args.trace_out:
                try:
                    info = telemetry.write_chrome_trace(args.trace_out)
                    log.info("chrome trace written: %s", info)
                    print(f"trace written to {args.trace_out} "
                          f"({info['events']} events) — open at "
                          "https://ui.perfetto.dev", file=sys.stderr)
                except Exception:
                    log.exception("trace export failed")
        # listeners flush buffered events in close() — run even when
        # training/validation/tuning raises
        if emitter is not None:
            emitter.clear_listeners()
        # multihost teardown LAST (stops the watchdog, leaves
        # jax.distributed, resets identity) so an in-process caller can
        # run again; idempotent no-op on single-process runs
        multihost.shutdown()


if __name__ == "__main__":
    raise SystemExit(main())
