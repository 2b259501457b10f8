"""Block coordinate descent over GAME coordinates — the outer training loop.

reference: CoordinateDescent (photon-lib/.../algorithm/CoordinateDescent.scala:40-385):
per iteration, per coordinate: partial score = full score - own score ->
updateModel with residual offsets -> rescore -> update running objective ->
optional per-coordinate validation -> track the best FULL model by the first
validation evaluator (line 294-335).

TPU design (SURVEY §2.14 P3): every coordinate's scores live as one dense
[n] device array in canonical row order, so the reference's uid-keyed
full-outer-join score algebra (DataScores +/-, CoordinateDataScores.scala:38-61)
is literally `total - own` / `partial + new` here.  A third of the
reference's loop body is persist/unpersist choreography (RDDLike); none of
that exists — arrays are device-resident for the whole fit.
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import telemetry
from photon_ml_tpu.data.game_data import GameDataset
from photon_ml_tpu.evaluation.evaluators import Evaluator, MultiEvaluator
from photon_ml_tpu.game import quarantine as quarantine_mod
from photon_ml_tpu.game.coordinates import Coordinate
from photon_ml_tpu.models.game import GameModel
from photon_ml_tpu.ops import TASK_LOSSES
from photon_ml_tpu.optim.types import LOCKSTEP
from photon_ml_tpu.telemetry.timings import PhaseTimings, clock
from photon_ml_tpu.utils import faults
from photon_ml_tpu.utils import durable

logger = logging.getLogger("photon_ml_tpu")


@dataclasses.dataclass
class ValidationSpec:
    """A validation evaluator, optionally grouped by an entity-index column
    (reference: MultiEvaluator id columns)."""

    evaluator: Evaluator | MultiEvaluator
    group_column: Optional[str] = None

    @property
    def name(self) -> str:
        return self.evaluator.name

    def evaluate(self, dataset: GameDataset, scores) -> float:
        s = np.asarray(scores)
        if dataset.offsets is not None:
            s = s + dataset.offsets  # score+offset, Evaluator.scala:35-45
        if self.group_column is not None:
            return self.evaluator.evaluate_grouped(
                dataset.entity_indices[self.group_column], s,
                dataset.response, dataset.weights)
        return self.evaluator(s, dataset.response, dataset.weights)


@functools.partial(jax.jit, static_argnames=("loss",))
def _data_term(total_scores, base_offsets, labels, weights, *, loss):
    """Weighted data-loss sum as ONE compiled program (a single device
    round-trip per objective evaluation, which happens coords x iters times
    per fit).  Module-level so the trace cache hits across fits of the same
    shapes — per-fit closures would recompile on every grid combo."""
    z = total_scores + base_offsets
    l = loss.loss(z, labels)
    return jnp.sum(l if weights is None else weights * l)


def _sync(*arrays) -> float:  # photonlint: flush-point
    """Wait until every array is computed, returning the seconds the host
    was blocked (callers feed PhaseTimings.add_blocked).  Dispatch is
    asynchronous, so every STRICT-mode timing span that launches device
    work ends with one — the span then covers the work it launched.
    Pipelined mode skips these entirely — that is the point."""
    t0 = clock()
    jax.block_until_ready([a for a in arrays if a is not None])
    return clock() - t0


@dataclasses.dataclass
class TrackerSummary:
    """Host-side per-solve record (reference: OptimizationStatesTracker
    records per-iteration state + wall clock, OptimizationStatesTracker
    .scala:32-102; here iterations are summed over vmapped entities).

    `reasons` counts ConvergenceReason outcomes across the solve's lanes
    (one entry for a scalar FE solve, per-entity counts for a vmapped RE
    solve, both sub-solves merged for a factored-MF alternation);
    `iteration_cap`/`tolerance` record the inexactness budget the solve ran
    under (None = strict full solve); `containment` records a quarantine
    outcome for the visit (None = healthy solve; "rolled_back" /
    "retry_ok" / "frozen", game/quarantine.py)."""

    iterations: int
    wall_s: float
    reasons: Dict[str, int] = dataclasses.field(default_factory=dict)
    iteration_cap: Optional[int] = None
    tolerance: Optional[float] = None
    containment: Optional[str] = None
    # mesh transfer bytes staged during THIS visit ({"cold": b, "warm": b},
    # parallel/mesh_residency.py TransferStats delta): cold = static
    # coordinate data (first visit / post-eviction re-stream), warm =
    # per-visit operands (offsets, x0).  A warm steady-state mesh visit
    # must stage ZERO cold bytes (tests/test_mesh_residency.py::
    # test_warm_iterations_stage_zero_cold_bytes).  None on non-mesh fits.
    staged_bytes: Optional[Dict[str, int]] = None
    # fresh XLA traces observed during THIS visit (telemetry's compile
    # watch, the runtime counterpart of photonlint PH002): a warm fit must
    # show 0 everywhere.  None when the tracer is disarmed (the counter
    # only advances while the compile watch is armed).
    retraces: Optional[int] = None
    # chunk-stream accounting delta for THIS visit (streamed FE
    # coordinates only, StreamStats snapshot diff): staged bytes/chunks,
    # local epochs, examples processed, and the derived
    # examples_per_staged_byte — the stochastic lane's win is this ratio
    # going up by ~the local epoch count.  None on resident coordinates.
    stream: Optional[Dict[str, object]] = None
    # LBFGS/OWLQN solves only (SolveResult.fg_count / .ls_trials /
    # .lockstep).  `data_passes`: full value+gradient passes, two reads of
    # the features each.  An entity coordinate's visit runs one lock step a
    # bucket, each as long as its slowest lane, one after the other: the
    # SUM over its runs of each run's most passes of any lane.
    # `ls_trials`: the line search's trial values the device evaluated, for
    # an entity coordinate the sum of its runs' `lockstep_trials`, which
    # counts the trials the batched search ran for lanes that had ended.
    # 1 - data_passes / (1 + ls_trials) is the share of evaluations served
    # from cached margins without reading the features (0 where every
    # trial is a full pass: L1, box).
    data_passes: Optional[int] = None
    ls_trials: Optional[int] = None
    # an entity coordinate's visit only: its runs' lock-step counts,
    # {LOCKSTEP column: [one int a run, in bucket order]}
    # (optim/types.py LOCKSTEP says what each counts)
    lockstep: Optional[Dict[str, List[int]]] = None
    # a factored coordinate's visit only: `data_passes` is the sum of its
    # two halves, these are the halves.  The per-entity solves in the latent
    # space (summed over their runs) and the one-lane refit of the shared
    # projection read different operands, so their passes cost differently.
    # `latent_lockstep` is `lockstep` of the latent half's runs
    latent_data_passes: Optional[int] = None
    projection_data_passes: Optional[int] = None
    latent_lockstep: Optional[Dict[str, List[int]]] = None


def _reason_counts(reason) -> Dict[str, int]:
    """{ConvergenceReason name: lane count} from a scalar or [E] array."""
    from photon_ml_tpu.optim.types import ConvergenceReason
    if reason is None:
        return {}
    arr = np.atleast_1d(np.asarray(reason))
    out: Dict[str, int] = {}
    for code, count in zip(*np.unique(arr, return_counts=True)):
        try:
            name = ConvergenceReason(int(code)).name
        except ValueError:
            name = str(int(code))
        out[name] = out.get(name, 0) + int(count)
    return out


def _lock_step_of(tracker: object):
    """The lock-step rows of a visit's batched per-entity solves
    (SolveResult.lockstep, a device array), or None: a fixed effect, TRON,
    a frozen coordinate."""
    part = getattr(tracker, "random_effect_result", tracker)
    return getattr(part, "lockstep", None)


def _summarize_tracker(tracker: object, wall_s: float, budget=None,
                       lockstep=None) -> TrackerSummary:
    """`lockstep`: the tracker's lock-step rows as the flush's batched read
    fetched them; read here (a sync) where it did not."""
    # a factored-MF tracker carries one SolveResult per half of the
    # alternation; merge both instead of dropping them on the floor
    parts = [t for t in (getattr(tracker, "random_effect_result", None),
                         getattr(tracker, "latent_result", None))
             if t is not None]
    if not parts and getattr(tracker, "iterations", None) is not None:
        parts = [tracker]
    count = sum(int(np.sum(np.asarray(t.iterations))) for t in parts)
    reasons: Dict[str, int] = {}
    for t in parts:
        for name, c in _reason_counts(getattr(t, "reason", None)).items():
            reasons[name] = reasons.get(name, 0) + c
    cap, tol = (None, None) if budget is None else budget
    summary = TrackerSummary(iterations=count, wall_s=wall_s, reasons=reasons,
                             iteration_cap=cap, tolerance=tol)
    if lockstep is None:
        lockstep = _lock_step_of(tracker)
    runs = (None if lockstep is None else
            {k: [int(v) for v in column]
             for k, column in zip(LOCKSTEP, np.asarray(lockstep).T)})
    counted = [t for t in parts if getattr(t, "ls_trials", None) is not None]
    if counted:
        # the halves of a factored alternation run one after the other, and
        # so do an entity coordinate's runs, one a bucket
        passes, trials = [], []
        for t in counted:
            if t is parts[0] and runs is not None:
                passes.append(sum(runs["data_passes"]))
                trials.append(sum(runs["lockstep_trials"]))
            else:       # one lane
                passes.append(int(np.max(np.asarray(t.fg_count), initial=0)))
                trials.append(int(np.max(np.asarray(t.ls_trials),
                                         initial=0)))
        summary.data_passes, summary.ls_trials = sum(passes), sum(trials)
        if len(parts) == 2 and len(counted) == 2:
            summary.latent_data_passes, summary.projection_data_passes = \
                passes
    if len(parts) == 2:
        summary.latent_lockstep = runs
    else:
        summary.lockstep = runs
    return summary


def _mark_lock_step(coordinate: str, visit: int,
                    summary: TrackerSummary) -> None:
    """One zero-length `photon/re/lockstep` profiler event a run of the
    visit's per-entity solve, the run's counts as its arguments: on the
    profiler's clock, inside the fit it belongs to.  A flag check a run
    while no profiler is on."""
    runs = summary.lockstep or summary.latent_lockstep
    for k, row in enumerate(zip(*(runs or {}).values())):
        telemetry.mark("re/lockstep", coordinate=coordinate, visit=visit,
                       run=k, **dict(zip(runs, row)))


@dataclasses.dataclass
class CoordinateDescentResult:
    model: GameModel                       # final full model
    best_model: GameModel                  # best by first validation evaluator
    objective_history: List[float]         # after each coordinate update
    validation_history: Dict[str, List[float]]
    # contiguous phase spans: "init/transfer", "init/score",
    # "{it}/{coord}/solve|objective|validation", "{it}/checkpoint" (+ the
    # estimator adds "build/coordinates"); their sum accounts for the whole
    # fit wall clock
    timings: Dict[str, float]
    # "it/coord" -> compact host-side solve summary (iterations, wall clock);
    # a full SolveResult per solve would pin [E, d]-sized device arrays for
    # the lifetime of every GameResult in a sweep
    # (reference: OptimizationStatesTracker per update)
    trackers: Dict[str, "TrackerSummary"] = dataclasses.field(default_factory=dict)
    # quarantine containment log (game/quarantine.py QuarantineMonitor
    # events: rollbacks, tightened-budget retries, freezes) — empty on a
    # healthy fit
    containment_events: List[dict] = dataclasses.field(default_factory=list)
    frozen_coordinates: List[str] = dataclasses.field(default_factory=list)

    def total_iterations(self) -> int:
        """Sum of inner optimizer iterations across all solves (vmapped RE
        trackers contribute their per-entity counts)."""
        return sum(t.iterations for t in self.trackers.values())

    def solver_diagnostics(self) -> Dict[str, dict]:
        """Per-coordinate solver totals for the fit summary: solve count,
        inner iterations actually used, ConvergenceReason outcome counts,
        the budget trajectory (iteration caps per visit, None entries =
        strict full solves), for LBFGS/OWLQN the data passes the device ran
        and the line-search trials per visit (TrackerSummary.data_passes /
        .ls_trials), for an entity coordinate its runs' lock-step counts
        per visit (`lockstep`, the factored one's `latent_lockstep`),
        host-blocked seconds attributed to the
        coordinate's spans, and — when the telemetry compile watch was
        armed — fresh traces per coordinate.  reference: the per-update
        OptimizationStatesTracker logs the GAME driver prints."""
        out: Dict[str, dict] = {}
        for key, t in sorted(self.trackers.items(),
                             key=lambda kv: (int(kv[0].split("/")[0]),
                                             kv[0])):
            coord = key.split("/", 1)[1]
            d = out.setdefault(coord, {"solves": 0, "iterations": 0,
                                       "reasons": {}, "iteration_caps": [],
                                       "containment": {},
                                       "host_blocked_s": 0.0})
            d["solves"] += 1
            d["iterations"] += t.iterations
            d["iteration_caps"].append(t.iteration_cap)
            if t.ls_trials is not None:
                d.setdefault("data_passes", []).append(t.data_passes)
                d.setdefault("ls_trials", []).append(t.ls_trials)
            if t.lockstep is not None:
                d.setdefault("lockstep", []).append(t.lockstep)
            if t.projection_data_passes is not None:
                d.setdefault("latent_data_passes", []).append(
                    t.latent_data_passes)
                d.setdefault("projection_data_passes", []).append(
                    t.projection_data_passes)
            if t.latent_lockstep is not None:
                d.setdefault("latent_lockstep", []).append(
                    t.latent_lockstep)
            if t.containment is not None:
                d["containment"][t.containment] = \
                    d["containment"].get(t.containment, 0) + 1
            if t.retraces is not None:
                d["retraces"] = d.get("retraces", 0) + t.retraces
            for name, c in t.reasons.items():
                d["reasons"][name] = d["reasons"].get(name, 0) + c
            if t.staged_bytes is not None:
                sb = d.setdefault("staged_bytes",
                                  {"cold": 0, "warm": 0})
                sb["cold"] += t.staged_bytes.get("cold", 0)
                sb["warm"] += t.staged_bytes.get("warm", 0)
            if t.stream is not None:
                st = d.setdefault("stream", {
                    "passes": 0, "chunks_staged": 0, "total_bytes": 0,
                    "local_epochs": 0, "examples_processed": 0})
                for k in st:
                    st[k] += t.stream.get(k, 0)
        # host-blocked attribution: span labels are "{it}/{coord}/{phase}"
        blocked = getattr(self.timings, "host_blocked", None) or {}
        for label, seconds in blocked.items():
            parts = label.split("/")
            if len(parts) == 3 and parts[1] in out:
                out[parts[1]]["host_blocked_s"] += seconds
        for d in out.values():
            d["host_blocked_s"] = round(d["host_blocked_s"], 4)
            if "stream" in d:
                st = d["stream"]
                st["examples_per_staged_byte"] = (
                    st["examples_processed"] / st["total_bytes"]
                    if st["total_bytes"] else 0.0)
        return out


@dataclasses.dataclass
class CheckpointState:
    """One resumable record (no reference equivalent — a failed Spark
    driver restarts the job from scratch, SURVEY §5.3).  `recovery`
    documents HOW the record was recovered: {"fallback": bool,
    "resumed_from_iteration": k, "pruned": [paths]} — fallback=True means
    the primary state.json was missing/corrupt/unverifiable and the record
    came from the newest iter-*/manifest-verified directory."""

    completed_iterations: int
    initial_models: Dict[str, object]
    objective_history: List[float]
    validation_history: Dict[str, List[float]]
    best_models: Optional[Dict[str, object]]    # None = same as latest
    best_metric: Optional[float]
    recovery: Optional[dict] = None


# -- crash-safe checkpoint plumbing ------------------------------------------
#
# Write discipline (everything inside the checkpoint directory):
#   iter-KKKK/<model files>         save_game_model layout
#   iter-KKKK/record.json           the FULL state record, self-contained
#                                   (relative dir references) — the fallback
#                                   unit when state.json is torn
#   iter-KKKK/manifest.json         per-file sizes + sha256, written LAST
#                                   via tmp+rename (+fsync): a directory
#                                   with a verifying manifest is COMPLETE
#   best-KKKK/...                   same manifest discipline
#   state.json                      atomic pointer to the newest record
#                                   (tmp -> fsync -> rename -> dir fsync)
#
# Retention is TWO records: the newest and its predecessor, so a record
# whose files turn out corrupt at resume still has a verified fallback.
# Resume order: state.json (manifest-verified) -> newest iter-* directory
# whose manifest verifies -> fresh start; stale *.tmp files and orphaned
# partial directories (no/failing manifest, unreferenced) are pruned.

# the atomic write+fsync discipline lives in utils/durable.py (shared
# with models/io.py; photonlint PH005 enforces that durable modules only
# write through it) — the local underscore names are kept because the
# crash tests and this module's call sites predate the extraction
_fsync_file = durable.fsync_file
_fsync_dir = durable.fsync_dir
_file_sha256 = durable.file_sha256
_write_manifest = durable.write_manifest


def verify_checkpoint_dir(dirpath: str) -> Tuple[Optional[bool], str]:
    """-> (ok, reason).  ok=True: manifest present and every listed file
    matches size + checksum.  ok=False: torn/corrupt.  ok=None: no
    manifest (a legacy pre-manifest record, or a partial write that died
    before the manifest landed — the caller decides by reference)."""
    import json
    mpath = os.path.join(dirpath, "manifest.json")
    if not os.path.isdir(dirpath):
        return False, "missing directory"
    if not os.path.exists(mpath):
        return None, "no manifest"
    try:
        with open(mpath) as f:
            manifest = json.load(f)
        for rel, want in manifest["files"].items():
            p = os.path.join(dirpath, rel)
            if not os.path.exists(p):
                return False, f"missing file {rel}"
            if os.path.getsize(p) != want["bytes"]:
                return False, f"size mismatch for {rel}"
            if _file_sha256(p) != want["sha256"]:
                return False, f"checksum mismatch for {rel}"
    except (OSError, ValueError, KeyError, TypeError) as e:
        return False, f"unreadable manifest ({e})"
    return True, "ok"


def _write_checkpoint(directory: str, iteration: int, model: GameModel,
                      objective_history: List[float],
                      validation_history: Dict[str, List[float]],
                      best_model: GameModel,
                      best_metric: Optional[float],
                      fingerprint: Optional[str]) -> None:
    """Persist the latest model + the best-so-far model + a state record
    after an outer iteration.

    Layout: {dir}/iter-{k:04d}/ and {dir}/best-{k:04d}/ (save_game_model
    format, each sealed by a per-file size+sha256 manifest.json written
    LAST) + {dir}/state.json (replaced ATOMICALLY after an fsync, and
    LAST, so a crash mid-save leaves the previous record intact).  Each
    iter directory also embeds its full state record (record.json) so a
    torn state.json can fall back to the newest VERIFIED record.  The two
    newest records are retained (fallback depth); older superseded model
    directories are pruned."""
    import json
    import shutil

    from photon_ml_tpu.models.io import save_game_model
    from photon_ml_tpu.parallel import multihost

    # process 0 owns every durable artifact (multi-process callers pass
    # checkpoint_dir=None off-primary, so this is defense in depth)
    if not multihost.is_primary():
        return

    faults.fire("checkpoint.write", iteration=iteration)
    try:
        with open(os.path.join(directory, "state.json")) as f:
            prev = json.load(f)
    except (OSError, ValueError):
        prev = None

    path = os.path.join(directory, f"iter-{iteration:04d}")
    save_game_model(model, path)
    # the best-so-far model is only meaningful when validation tracking is
    # active; without it the final model IS the result
    best_path = None
    if best_metric is not None:
        if (prev is not None and prev.get("best_metric") == best_metric
                and prev.get("best_model_dir")
                and os.path.isdir(prev["best_model_dir"])):
            # best unchanged since the previous record: point at the
            # existing directory instead of re-serializing the model
            best_path = prev["best_model_dir"]
        else:
            best_path = os.path.join(directory, f"best-{iteration:04d}")
            save_game_model(best_model, best_path)
            _write_manifest(best_path)
    state = {"completed_iterations": iteration + 1,
             "model_dir": path,
             "best_model_dir": best_path,
             "best_metric": best_metric,
             "config_fingerprint": fingerprint,
             "objective_history": objective_history,
             "validation_history": validation_history}
    # self-contained fallback record: directory references by BASENAME so
    # the record stays valid wherever the checkpoint directory lives
    record = dict(state,
                  model_dir=os.path.basename(path),
                  best_model_dir=(os.path.basename(best_path)
                                  if best_path else None))
    durable.atomic_write_json(os.path.join(path, "record.json"), record,
                              indent=1, fsync=False)  # manifest fsyncs it
    _write_manifest(path)  # seals the iter dir (covers record.json)

    # retention of TWO records: remember the predecessor so resume can fall
    # back past a record whose files turn out corrupt
    state["previous"] = (
        {k: prev.get(k) for k in ("completed_iterations", "model_dir",
                                  "best_model_dir")}
        if prev is not None else None)
    # a "kill" injected at the before_replace hook is the canonical torn
    # checkpoint: the new record is complete + sealed, state.json still
    # points at the old one, and state.json.tmp is left for resume to prune
    durable.atomic_write_json(
        os.path.join(directory, "state.json"), state, indent=1,
        before_replace=lambda: faults.fire("checkpoint.fsync",
                                           iteration=iteration))
    # prune the dirs the GRANDPARENT record referenced (two newest records
    # are retained); a foreign/corrupt state.json may point anywhere, so
    # only delete paths contained in the checkpoint directory
    grand = (prev or {}).get("previous") or {}
    keep = {p for p in (path, best_path, (prev or {}).get("model_dir"),
                        (prev or {}).get("best_model_dir")) if p}
    root = os.path.realpath(directory)
    for key in ("model_dir", "best_model_dir"):
        old = grand.get(key)
        if not old or old in keep or not os.path.isdir(old):
            continue
        real = os.path.realpath(old)
        if os.path.commonpath([root, real]) != root or real == root:
            logger.warning(
                "checkpoint state referenced %s outside the checkpoint "
                "directory %s; refusing to prune it", old, directory)
            continue
        shutil.rmtree(real, ignore_errors=True)
    telemetry.counter("checkpoint.written").inc()
    logger.info("checkpoint: iteration %d saved to %s", iteration, path)


class AsyncCheckpointer:
    """Background checkpoint writer: iteration *k*'s models serialize while
    iteration *k+1* trains (the reference has no checkpointing at all, and
    the strict-mode path here blocks the whole loop on every write).

    Semantics:
      - writes run on ONE worker thread through the same `_write_checkpoint`,
        so the atomic write-state-last + prune discipline is untouched and
        records land in submission order;
      - keep-latest coalescing: a snapshot superseded before its write
        STARTS is dropped (only the newest record is ever resumed from, so
        a skipped intermediate costs nothing on resume — this is what keeps
        the trainer from ever waiting on a slow disk);
      - durability: after `shutdown()` (called at fit end) the LAST
        submitted iteration is on disk; mid-fit, the newest record is
        whichever submission last finished — a crash resumes from there and
        retrains the rest;
      - a worker failure (disk full, ...) surfaces at the next submit() or
        at shutdown(), never silently.
    """

    def __init__(self, directory: str):
        import threading

        from photon_ml_tpu.utils import locktrace

        self.directory = directory
        self._cv = locktrace.tracked(threading.Condition(),
                                     "AsyncCheckpointer._cv")
        self._pending: Optional[tuple] = None
        self._busy = False
        self._closed = False
        self._error: Optional[BaseException] = None
        self.written = 0
        self.coalesced = 0
        self._thread = threading.Thread(
            target=self._run, name="photon-async-checkpoint", daemon=True)
        self._thread.start()

    def submit(self, iteration: int, model: GameModel,
               objective_history: List[float],
               validation_history: Dict[str, List[float]],
               best_model: GameModel, best_metric: Optional[float],
               fingerprint: Optional[str]) -> None:
        """Enqueue one snapshot (histories are copied here; model objects
        are immutable and their device buffers are never donated — see the
        copy-on-alias guards in game/coordinates.py)."""
        snap = (iteration, model, list(objective_history),
                {k: list(v) for k, v in validation_history.items()},
                best_model, best_metric, fingerprint)
        with self._cv:
            if self._error is not None:
                err, self._error = self._error, None
                raise RuntimeError(
                    "async checkpoint write failed in the background "
                    "writer") from err
            if self._closed:
                raise RuntimeError("AsyncCheckpointer already shut down")
            if self._pending is not None:
                self.coalesced += 1
                telemetry.counter("checkpoint.coalesced").inc()
            self._pending = snap
            self._cv.notify_all()

    def _run(self) -> None:
        while True:
            with self._cv:
                while self._pending is None and not self._closed:
                    self._cv.wait()
                if self._pending is None:
                    return
                snap, self._pending = self._pending, None
                self._busy = True
            try:
                # the span runs on THIS background thread: checkpoint
                # serialization gets its own track in the trace
                with telemetry.span("checkpoint_write", iteration=snap[0]):
                    _write_checkpoint(self.directory, *snap)
                with self._cv:
                    self.written += 1
            except BaseException as e:  # surfaced at submit/shutdown
                with self._cv:
                    self._error = e
            finally:
                with self._cv:
                    self._busy = False
                    self._cv.notify_all()

    def shutdown(self, raise_errors: bool = True) -> None:
        """Drain the queue (the final snapshot always writes), stop the
        worker, and re-raise any worker failure IMMEDIATELY — the final
        fit-end record is part of the fit's durability contract, so a
        failed write surfaces here (original exception as __cause__),
        never silently.  Idempotent: a second call is a no-op."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()
            while self._pending is not None or self._busy:
                self._cv.wait()
        self._thread.join()
        if raise_errors and self._error is not None:
            err, self._error = self._error, None
            raise RuntimeError(
                "async checkpoint write failed: the final fit-end record "
                "did not persist") from err


def _prune_stale_tmp(directory: str) -> List[str]:
    """Remove *.tmp files a kill-during-write left behind (state.json.tmp,
    manifest.json.tmp, ...) — a stale tmp must never make the directory
    look foreign or half-written on resume."""
    pruned = []
    if not os.path.isdir(directory):
        return pruned
    for root, _, names in os.walk(directory):
        for fn in names:
            if fn.endswith(".tmp"):
                p = os.path.join(root, fn)
                try:
                    # every resuming process sweeps: race-tolerant
                    os.remove(p)  # photonlint: all-process
                    pruned.append(p)
                except OSError:
                    pass
    if pruned:
        logger.warning("checkpoint at %s: pruned %d stale tmp file(s) left "
                       "by an interrupted write: %s", directory, len(pruned),
                       pruned)
    return pruned


def _checkpoint_record_dirs(directory: str):
    """iter-*/best-* subdirectories, newest first."""
    out = []
    for fn in os.listdir(directory):
        if fn.startswith(("iter-", "best-")):
            p = os.path.join(directory, fn)
            if os.path.isdir(p):
                out.append(p)
    return sorted(out, reverse=True)


def _prune_orphan_dirs(directory: str, keep: set) -> List[str]:
    """Remove iter-*/best-* directories that are partial writes: not
    referenced by the record being resumed and lacking a VERIFYING
    manifest.  Verified-but-unreferenced directories (e.g. a record sealed
    right before a kill-at-fsync) are kept — they are complete and will be
    overwritten by the re-run of their iteration."""
    import shutil
    pruned = []
    for p in _checkpoint_record_dirs(directory):
        if os.path.realpath(p) in keep:
            continue
        ok, reason = verify_checkpoint_dir(p)
        if ok is True:
            continue
        # every resuming process sweeps: ignore_errors absorbs the race
        shutil.rmtree(p, ignore_errors=True)  # photonlint: all-process
        pruned.append(p)
        logger.warning("checkpoint at %s: pruned orphaned partial write %s "
                       "(%s)", directory, p, reason)
    return pruned


def _state_to_checkpoint(directory: str, state: dict, relative: bool,
                         recovery: dict) -> Optional[CheckpointState]:
    """Load the models a (top-level or embedded) state record references.
    `relative` resolves model/best dirs against the checkpoint directory
    (embedded record.json stores basenames)."""
    import zipfile

    from photon_ml_tpu.models.io import load_game_model

    def resolve(p):
        return os.path.join(directory, p) if relative else p

    try:
        model, _ = load_game_model(resolve(state["model_dir"]))
        best = None
        if state.get("best_model_dir"):
            best_dir = resolve(state["best_model_dir"])
            ok, reason = verify_checkpoint_dir(best_dir)
            if ok is False:
                logger.warning(
                    "checkpoint best-model directory %s failed verification "
                    "(%s); resuming without best-model restoration",
                    best_dir, reason)
            else:
                best_model, _ = load_game_model(best_dir)
                best = dict(best_model.coordinates)
        return CheckpointState(
            completed_iterations=int(state["completed_iterations"]),
            initial_models=dict(model.coordinates),
            objective_history=list(state["objective_history"]),
            validation_history={k: list(v) for k, v in
                                state.get("validation_history", {}).items()},
            best_models=best,
            best_metric=state.get("best_metric"),
            recovery=recovery)
    except (OSError, ValueError, KeyError, zipfile.BadZipFile) as e:
        logger.warning("checkpoint record in %s unreadable (%s)",
                       directory, e)
        return None


def _note_recovery(recovery: dict) -> None:
    """Publish a successful checkpoint recovery: counters always, a run-log
    event when the tracer is armed (correlated by span id with whatever
    stage triggered the resume)."""
    telemetry.counter("checkpoint.recoveries").inc()
    if recovery.get("fallback"):
        telemetry.counter("checkpoint.recovery_fallbacks").inc()
    telemetry.event(
        "checkpoint_recovery", fallback=recovery.get("fallback"),
        resumed_from_iteration=recovery.get("resumed_from_iteration"),
        pruned=len(recovery.get("pruned") or ()))


def _fingerprint_mismatch(state: dict, fingerprint: Optional[str],
                          directory: str) -> bool:
    recorded = state.get("config_fingerprint")
    if fingerprint is not None and recorded is not None \
            and recorded != fingerprint:
        logger.warning(
            "checkpoint at %s was written under a different training "
            "configuration (fingerprint %s != %s); starting fresh",
            directory, recorded, fingerprint)
        return True
    return False


def read_checkpoint(directory: str,
                    fingerprint: Optional[str] = None
                    ) -> Optional[CheckpointState]:
    """The resume half of the checkpoint flow, fault-contained:

      1. prune stale *.tmp files left by a kill-during-write;
      2. resume from state.json IF its model directories verify against
         their size+checksum manifests (a legacy record without manifests
         is accepted with a warning);
      3. otherwise FALL BACK to the newest iter-* directory whose manifest
         verifies, using its embedded self-contained record.json — and
         prune orphaned partial writes (no/failing manifest, unreferenced);
      4. otherwise: no checkpoint (better to retrain than to crash the job
         permanently).

    `model.load` is an injection site (utils/faults.py) so resume failures
    are testable.  `fingerprint` guards against resuming under a CHANGED
    configuration: a record written with a different coordinate/
    optimization config (outer iteration count excluded — raising it is
    the legitimate resume use) is rejected with a warning rather than
    silently returning a model trained under different settings."""
    import json

    state_path = os.path.join(directory, "state.json")
    if not os.path.isdir(directory):
        return None
    pruned = _prune_stale_tmp(directory)

    state = None
    try:
        with open(state_path) as f:
            state = json.load(f)
    except OSError:
        state = None  # no checkpoint yet (or unreadable): try fallback
    except ValueError as e:
        logger.warning("checkpoint at %s unreadable (%s); trying verified "
                       "fallback", directory, e)
        state = None

    if state is not None:
        if _fingerprint_mismatch(state, fingerprint, directory):
            return None
        ok, reason = verify_checkpoint_dir(state.get("model_dir") or "")
        if ok is None:
            logger.info("checkpoint at %s carries no manifest (legacy "
                        "record); resuming unverified", directory)
        if ok is not False:
            result = _state_to_checkpoint(
                directory, state, relative=False,
                recovery={"fallback": False, "pruned": pruned,
                          "resumed_from_iteration":
                              int(state.get("completed_iterations", 0)) - 1})
            if result is not None:
                keep = {os.path.realpath(p) for p in
                        (state.get("model_dir"), state.get("best_model_dir"),
                         *(((state.get("previous") or {}).get(k)) for k in
                           ("model_dir", "best_model_dir")))
                        if p}
                result.recovery["pruned"] += _prune_orphan_dirs(directory,
                                                                keep)
                _note_recovery(result.recovery)
                return result
            logger.warning("checkpoint at %s: primary record unusable; "
                           "trying verified fallback", directory)
        else:
            logger.warning(
                "checkpoint at %s: model directory %s failed manifest "
                "verification (%s); trying verified fallback", directory,
                state.get("model_dir"), reason)

    # fallback: newest iter-* directory with a verifying manifest + an
    # embedded record
    for p in _checkpoint_record_dirs(directory):
        if not os.path.basename(p).startswith("iter-"):
            continue
        ok, _ = verify_checkpoint_dir(p)
        if ok is not True:
            continue
        record_path = os.path.join(p, "record.json")
        try:
            with open(record_path) as f:
                record = json.load(f)
        except (OSError, ValueError):
            continue  # pre-record layout: cannot self-resume
        if _fingerprint_mismatch(record, fingerprint, directory):
            return None
        result = _state_to_checkpoint(
            directory, record, relative=True,
            recovery={"fallback": True, "pruned": pruned,
                      "resumed_from_iteration":
                          int(record.get("completed_iterations", 0)) - 1})
        if result is None:
            continue
        keep = {os.path.realpath(p)}
        if record.get("best_model_dir"):
            keep.add(os.path.realpath(
                os.path.join(directory, record["best_model_dir"])))
        result.recovery["pruned"] += _prune_orphan_dirs(directory, keep)
        logger.warning(
            "checkpoint at %s: fell back to verified record %s "
            "(completed_iterations=%d)", directory, os.path.basename(p),
            result.completed_iterations)
        _note_recovery(result.recovery)
        return result

    if state is not None or _checkpoint_record_dirs(directory):
        logger.warning("checkpoint at %s has no verifiable record; "
                       "starting fresh", directory)
        _prune_orphan_dirs(directory, set())
    return None


def run_coordinate_descent(
    coordinates: Dict[str, Coordinate],
    updating_sequence: Sequence[str],
    num_iterations: int,
    dataset: GameDataset,
    task_type: str,
    validation_dataset: Optional[GameDataset] = None,
    validation_specs: Sequence[ValidationSpec] = (),
    initial_models: Optional[Dict[str, object]] = None,
    checkpoint_dir: Optional[str] = None,
    resume: Optional[CheckpointState] = None,
    checkpoint_fingerprint: Optional[str] = None,
    timings: Optional[PhaseTimings] = None,
    timing_mode: str = "pipelined",
    residency=None,
    solver_schedules: Optional[Dict[str, object]] = None,
) -> CoordinateDescentResult:
    """reference: CoordinateDescent.run/optimize (scala:57-385).

    `checkpoint_dir` persists the latest + best-so-far models and a state
    record after every OUTER iteration; `resume` (a CheckpointState from
    read_checkpoint) continues from such a record — a capability the
    reference does NOT have (driver failure there restarts the job from
    scratch, SURVEY §5.3).  Use GameEstimator.fit(checkpoint_dir=...) for
    the integrated save-and-resume flow.

    `timing_mode` (Snap ML-style pipelining, arXiv:1803.06333):
      - "pipelined" (default): coordinate *k+1*'s device work is enqueued
        while *k*'s bookkeeping is in flight.  Objectives and validation
        metrics stay DEVICE scalars, fetched in one batched
        `jax.device_get` per outer iteration; checkpoints serialize on a
        background thread (AsyncCheckpointer).  Math is identical to
        strict mode — same programs, same order — so histories and final
        coefficients match bit-for-bit.
      - "strict": every update syncs before the next begins (the
        pre-pipelining behavior).  Use when per-phase PhaseTimings spans
        must stay attributable to the device work they launched.

    `residency` (a game.residency.ResidencyManager) rotates device
    residency under an HBM budget: after a coordinate's update + score +
    objective (and validation rescore, which reads the VALIDATION dataset's
    shards, not the training blocks), its device blocks are evicted and the
    next visit re-streams them from the host copies.  The flat [n] residual
    score vectors stay device-resident throughout.  Without a budget the
    manager only keeps byte accounting and the loop is unchanged.

    `solver_schedules` ({coordinate name -> optim.schedule.SolverSchedule
    or None}) runs inner solves INEXACTLY: early outer iterations get small
    iteration caps + loose tolerances, tightening geometrically, with the
    final outer iteration always at the full configured budget.  Budgets
    ride into the compiled solvers as traced operands (zero recompiles
    across the schedule), and the budget each solve ran under lands in the
    trackers.  Scheduling is pure arithmetic in (outer iteration,
    num_iterations), so checkpoint resume reproduces the trajectory.
    """
    if timing_mode not in ("pipelined", "strict"):
        raise ValueError(f"timing_mode must be 'pipelined' or 'strict', "
                         f"got {timing_mode!r}")
    pipelined = timing_mode == "pipelined"
    loss = TASK_LOSSES[task_type]
    # mesh transfer accounting (parallel/mesh_residency.py): per-visit
    # staged-bytes deltas (cold static data vs warm offsets/x0) land in the
    # tracker summaries, making the mesh path's no-retransfer property
    # observable per update.  Counters are host-side ints — snapshotting
    # them never syncs the device.
    _mesh_snap = None
    _mh_mesh = None  # the mesh, when this run spans PROCESSES (multi-host)
    if any(getattr(getattr(c, "mesh", None), "size", 1) > 1
           for c in coordinates.values()):
        from photon_ml_tpu.parallel.mesh_residency import transfer_snapshot
        _mesh_snap = transfer_snapshot
        from photon_ml_tpu.parallel import multihost
        if multihost.active():
            _mh_mesh = next(m for m in (getattr(c, "mesh", None)
                                        for c in coordinates.values())
                            if getattr(m, "size", 1) > 1)
    if checkpoint_dir is not None:
        from photon_ml_tpu.parallel import multihost as _mh
        if not _mh.is_primary():
            # multi-writer guard: every process runs this loop in lockstep,
            # but exactly one may own the checkpoint directory (N processes
            # racing the same state.json replace + manifest seal would
            # corrupt it); non-primary processes train checkpoint-free and
            # resume from process 0's records on relaunch
            logger.info("multihost: process %d skips checkpoint writes "
                        "(process 0 owns %s)", _mh.process_index(),
                        checkpoint_dir)
            checkpoint_dir = None

    def _dataset_rows(name):
        """One of the dataset's [n] vectors -> its device copy, the one the
        dataset holds for every fit (`GameDataset.device_vector`); on a
        multi-process mesh the copy must be GLOBAL (data-sharded, assembled
        from per-process blocks) — a local placement cannot feed a jit
        whose other operands span peer processes' devices."""
        if _mh_mesh is not None:
            from photon_ml_tpu.parallel import multihost
            return multihost.global_rows(
                _mh_mesh, np.asarray(getattr(dataset, name)))
        return dataset.device_vector(name)

    def _zero_rows(n):
        if _mh_mesh is not None:
            from photon_ml_tpu.parallel import multihost
            return multihost.global_zeros(_mh_mesh, n)
        return jnp.zeros(n)

    def _staged_delta(before):
        if before is None:
            return None
        after = _mesh_snap()
        return {"cold": after["cold_bytes"] - before["cold_bytes"],
                "warm": after["warm_bytes"] - before["warm_bytes"]}

    def _stream_delta(coord, before):
        """Per-visit StreamStats delta for a streamed coordinate (None
        otherwise): the chunk-stream work/bytes THIS visit moved, plus
        the derived examples_per_staged_byte ratio."""
        snap_fn = getattr(coord, "stream_snapshot", None)
        after = snap_fn() if callable(snap_fn) else None
        if after is None:
            return None
        before = before or {}
        delta = {k: after.get(k, 0) - before.get(k, 0)
                 for k in ("passes", "chunks_staged", "total_bytes",
                           "local_epochs", "examples_processed",
                           "retries")}
        delta["examples_per_staged_byte"] = (
            delta["examples_processed"] / delta["total_bytes"]
            if delta["total_bytes"] else 0.0)
        return delta
    spans = PhaseTimings() if timings is None else timings
    with spans.span("init/transfer"):
        labels = _dataset_rows("response")
        weights = (None if dataset.weights is None
                   else _dataset_rows("weights"))
        base_offsets = (_zero_rows(dataset.num_rows)
                        if dataset.offsets is None
                        else _dataset_rows("offsets"))
        spans.add_blocked("init/transfer",
                          _sync(labels, weights, base_offsets))

    # per-coordinate regularization terms as DEVICE scalars, recomputed
    # ONLY for the updated coordinate and folded into the data term so each
    # objective evaluation costs ONE device readback (the reference
    # recomputes every term per update via join+reduce,
    # CoordinateDescent.scala:243-254; a float() per term would block the
    # host on the device once each)
    reg_terms: Dict[str, object] = {}

    def objective_device(total_scores):
        """Full regularized objective as a DEVICE scalar — strict mode
        float()s it immediately, pipelined mode defers the readback to the
        outer-iteration boundary flush."""
        return (_data_term(total_scores, base_offsets, labels,
                           weights, loss=loss)
                + sum(reg_terms.values()))

    # init (reference: CoordinateDescent.run line 57-96); a resume record
    # overrides the initial models and restores histories + best tracking
    start_iteration = 0
    if resume is not None and resume.completed_iterations > num_iterations:
        logger.warning(
            "checkpoint covers %d outer iterations but this fit requests "
            "only %d; ignoring the checkpoint (delete it to silence this)",
            resume.completed_iterations, num_iterations)
        resume = None
    if resume is not None:
        start_iteration = min(resume.completed_iterations, num_iterations)
        if initial_models:
            logger.warning("resuming from a checkpoint: the provided "
                           "initial/warm-start models are superseded by the "
                           "checkpointed models")
        initial_models = resume.initial_models
    # factored coordinates starting from their cold default model warm-init
    # their latent factors from a sibling plain-RE solution at their FIRST
    # visit (by then the sibling has already been fit this iteration);
    # provided/resumed models are never overridden
    cold_factored: set = set()
    with spans.span("init/score"):
        zeros = _zero_rows(dataset.num_rows)
        models, scores = {}, {}
        for name in updating_sequence:
            provided = (initial_models or {}).get(name)
            if provided is None:
                if hasattr(coordinates[name], "warm_start_latent"):
                    cold_factored.add(name)
                # default initial models are zero-coefficient by
                # construction (reference: Coordinate.initializeModel), so
                # their scores are exactly zero — no device work.  The
                # regularization term is zero too EXCEPT for factored
                # coordinates, whose initial Gaussian projection carries a
                # latent-problem penalty
                models[name] = coordinates[name].initial_model()
                scores[name] = zeros
                cfg = getattr(coordinates[name], "config", None)
                reg_terms[name] = (
                    coordinates[name].regularization_term(models[name])
                    if getattr(cfg, "latent_optimization", None) is not None
                    else 0.0)
            else:
                if residency is not None:
                    residency.before_update(name)
                models[name] = provided
                scores[name] = coordinates[name].score(provided)
                reg_terms[name] = coordinates[name].regularization_term(
                    provided)
                if residency is not None:
                    # warm-start scoring touched this coordinate's blocks;
                    # under budget pressure they drop until its first visit
                    residency.after_update(name)
        total = sum(scores.values(), zeros)
        if not pipelined:
            spans.add_blocked("init/score", _sync(total))

    objective_history: List[float] = list(
        resume.objective_history if resume is not None else [])
    validation_history: Dict[str, List[float]] = {
        s.name: list((resume.validation_history if resume is not None
                      else {}).get(s.name, [])) for s in validation_specs}
    trackers: Dict[str, TrackerSummary] = {}
    best_model = GameModel(dict(models), task_type)
    best_metric: Optional[float] = None
    if resume is not None and resume.best_metric is not None:
        best_metric = resume.best_metric
        if resume.best_models is not None:
            best_model = GameModel(dict(resume.best_models), task_type)

    # per-coordinate validation scores, updated incrementally (only the
    # changed coordinate is rescored — same algebra as the training side)
    do_validation = validation_dataset is not None and validation_specs
    val_scores_by_coord = {}
    val_labels_dev = val_weights_dev = val_offsets_dev = None
    if do_validation:
        with spans.span("init/validation_score"):
            # the validation plane stays process-LOCAL on a multi-process
            # run: score_dataset scores without the mesh (full per-process
            # copies), so its arrays must not mix with global placements
            val_zeros = jnp.zeros(validation_dataset.num_rows)
            val_scores_by_coord = {
                name: (val_zeros
                       if (initial_models or {}).get(name) is None
                       else models[name].score_dataset(validation_dataset))
                for name in updating_sequence}
            if pipelined:
                # device copies for the jitted metric kernels (the host
                # evaluators read the numpy arrays off the dataset instead)
                val_labels_dev = validation_dataset.device_vector("response")
                val_weights_dev = validation_dataset.device_vector("weights")
                val_offsets_dev = validation_dataset.device_vector("offsets")
            else:
                spans.add_blocked("init/validation_score",
                                  _sync(*val_scores_by_coord.values()))

    def evaluate_spec_device(spec: ValidationSpec, val_total):
        """Device-scalar metric for one spec, or None when the spec has no
        device path (grouped or custom metrics -> host fallback)."""
        if spec.group_column is not None:
            return None
        device_eval = getattr(spec.evaluator, "evaluate_on_device", None)
        if device_eval is None:
            return None
        s = (val_total if val_offsets_dev is None
             else val_total + val_offsets_dev)
        return device_eval(s, val_labels_dev, val_weights_dev)

    # pipelined mode: per-update records awaiting the boundary readback
    # (device scalars + a models snapshot for deferred best tracking)
    pending: List[dict] = []
    # non-finite solve quarantine (game/quarantine.py): the device-side
    # where-guard already rolled back any NaN/Inf solve the moment it
    # happened; the monitor applies the host-side policy (one tightened
    # retry, else freeze) when the health flags land
    monitor = quarantine_mod.QuarantineMonitor()

    def _host_rollback(name: str, prev_model) -> None:
        """Rare path: finite coefficients but a non-finite objective (data
        term overflow).  The device-side guard passed the model through,
        so roll the coordinate back on the host and recompute its score."""
        nonlocal total
        coord = coordinates[name]
        if residency is not None:
            residency.before_update(name)
        models[name] = prev_model
        sc = coord.score(prev_model)
        total = (total - scores[name]) + sc
        scores[name] = sc
        reg_terms[name] = coord.regularization_term(prev_model)
        if residency is not None:
            residency.after_update(name)

    def _quarantine_rerun(it: int, name: str) -> bool:  # photonlint: flush-point
        """The ONE tightened-budget retry after a rollback, run at the
        point the divergence is discovered (the outer-iteration boundary
        in pipelined mode).  Its small health readback is fine — this is
        the rare containment path, not the hot loop."""
        nonlocal total
        from photon_ml_tpu.optim.schedule import QuarantineRetrySchedule
        coord = coordinates[name]
        if residency is not None:
            residency.before_update(name)
        partial = total - scores[name]
        new_model, _tracker = coord.update(
            models[name], base_offsets + partial,
            schedule=QuarantineRetrySchedule(), outer_iteration=it,
            num_outer_iterations=num_iterations)
        guarded, flag = quarantine_mod.guard(new_model, models[name])
        sc = coord.score(guarded)
        new_total = partial + sc
        old_reg = reg_terms[name]
        reg_terms[name] = coord.regularization_term(guarded)
        obj_dev = objective_device(new_total)
        ok_dev = quarantine_mod.combine_health(flag, obj_dev)
        ok_v, obj_v = jax.device_get([ok_dev, obj_dev])
        ok = bool(ok_v)
        if ok:
            models[name] = guarded
            scores[name] = sc
            total = new_total
            monitor.on_retry_result(it, name, True, float(obj_v))
        else:
            reg_terms[name] = old_reg
            monitor.on_retry_result(it, name, False)
        if residency is not None:
            residency.after_update(name)
        return ok

    def _contain(it: int, name: str) -> str:
        """Apply the quarantine policy once an unhealthy flag lands on the
        host; returns the containment label for the visit's tracker."""
        decision = monitor.on_divergence(it, name)
        if decision == "retry":
            return "retry_ok" if _quarantine_rerun(it, name) else "frozen"
        return "frozen"

    def flush_pending() -> None:  # photonlint: flush-point
        """ONE batched device_get for every objective + metric + HEALTH
        scalar of the outer iteration and every entity coordinate's
        lock-step counts (a row of scalars a run), then the deferred host
        bookkeeping (history appends, tracker summaries and their
        `photon/re/lockstep` marks, best-model tracking, logging,
        quarantine containment)."""
        nonlocal best_metric, best_model
        if not pending:
            return
        fetched = jax.device_get(
            [[p["objective"], p["health"], list(p["metrics"].values()),
              _lock_step_of(p["tracker"])] for p in pending])
        divergent = []
        for p, (obj, health, metric_vals, lockstep) in zip(pending, fetched):
            obj = float(obj)
            healthy = bool(health)
            key = f"{p['it']}/{p['name']}"
            if not healthy:
                if not math.isfinite(obj):
                    # finite coefficients, non-finite objective: host-side
                    # rollback, and log the pre-update objective instead
                    _host_rollback(p["name"], p["prev_model"])
                    obj = float(objective_device(total))
                divergent.append(p)
            objective_history.append(obj)
            trackers[key] = _summarize_tracker(
                p["tracker"], spans[p["solve_key"]], p["budget"], lockstep)
            _mark_lock_step(p["name"], p["it"], trackers[key])
            trackers[key].containment = ("rolled_back" if not healthy
                                         else p["containment"])
            trackers[key].staged_bytes = p["staged"]
            trackers[key].stream = p["stream"]
            trackers[key].retraces = p["retraces"]
            logger.info("iter %d coordinate %-16s objective=%.8g (%.2fs)",
                        p["it"], p["name"], obj, spans[p["solve_key"]])
            for k, (spec, v) in enumerate(zip(validation_specs, metric_vals)):
                v = float(v)
                validation_history[spec.name].append(v)
                logger.info("  validation %-24s = %.6g", spec.name, v)
                if k == 0:  # best FULL model by first evaluator (ref 294-335)
                    if healthy and (best_metric is None or
                                    spec.evaluator.better_than(v,
                                                               best_metric)):
                        best_metric = v
                        best_model = GameModel(dict(p["models"]), task_type)
        pending.clear()
        # containment AFTER the iteration's bookkeeping: the retry runs at
        # the boundary, not inside any one entry's slot in the history
        for p in divergent:
            label = _contain(p["it"], p["name"])
            trackers[f"{p['it']}/{p['name']}"].containment = label

    checkpointer: Optional[AsyncCheckpointer] = None

    def _preempt(completed: int):
        """Graceful-preemption exit: the in-flight coordinate update is
        finished, make the newest checkpoint record durable, then raise
        the distinct resumable signal (cli.train maps it to exit 75)."""
        nonlocal checkpointer
        logger.warning("graceful preemption: stopping after %d completed "
                       "outer iteration(s)", completed)
        if checkpointer is not None:
            with spans.span("checkpoint/join"):
                checkpointer.shutdown(raise_errors=True)
            checkpointer = None
        raise faults.Preempted(
            completed, checkpoint_dir is not None and completed > 0,
            checkpoint_dir)

    loop_ok = False
    try:
        for it in range(start_iteration, num_iterations):
            # hierarchy level 1 of the trace: outer_iteration ->
            # coordinate_visit -> solve/objective/validation spans.
            # push/pop instead of `with` keeps the loop body un-reindented;
            # an exception path (Preempted, a fatal staging error) leaves
            # them open and Tracer.finish() heals them at export.
            _it_span = telemetry.push("outer_iteration", iteration=it)
            for name in updating_sequence:
                _visit_span = telemetry.push("coordinate_visit",
                                             coordinate=name, iteration=it)
                _retr0 = (telemetry.retrace_count() if telemetry.armed()
                          else None)
                solve_key = f"{it}/{name}/solve"
                coord = coordinates[name]
                frozen = monitor.is_frozen(name)
                prev_model = models[name]
                mesh_before = _mesh_snap() if _mesh_snap else None
                stream_before = getattr(coord, "stream_snapshot",
                                        lambda: None)()
                sched = (solver_schedules or {}).get(name)
                budget_diag = None
                tracker = None
                health_flag = None
                if sched is not None and not frozen:
                    base = coordinates[name].config.optimization \
                        .optimizer.resolved()
                    budget_diag = sched.plan(it, num_iterations,
                                             base.max_iterations,
                                             base.tolerance)
                with spans.span(solve_key, name="solve", coordinate=name,
                                iteration=it):
                    if frozen:
                        # quarantined after repeated divergence: the
                        # coordinate keeps its last good coefficients and
                        # the rest of the descent continues
                        pass
                    else:
                        if residency is not None:
                            residency.before_update(name)
                        if name in cold_factored:
                            # first visit of a cold factored coordinate:
                            # seed the latent factors from the sibling
                            # plain-RE solution (updated earlier in this
                            # sequence pass)
                            cold_factored.discard(name)
                            warm = coord.warm_start_latent(models[name],
                                                           models)
                            if warm is not None:
                                models[name] = warm
                                prev_model = warm
                        # partial = full - own (reference line 186-193)
                        partial = total - scores[name]
                        new_model, tracker = coord.update(
                            models[name], base_offsets + partial,
                            schedule=sched, outer_iteration=it,
                            num_outer_iterations=num_iterations)
                        if faults.fire("solve.poison", coordinate=name,
                                       iteration=it) == "poison":
                            new_model = quarantine_mod.poison_model(
                                new_model)
                        # device-side containment: a non-finite solve rolls
                        # back to the last good coefficients RIGHT HERE, so
                        # downstream coordinates never see poisoned scores;
                        # the flag rides the batched boundary fetch
                        models[name], health_flag = quarantine_mod.guard(
                            new_model, prev_model)
                        scores[name] = coord.score(models[name])
                        total = partial + scores[name]
                    if not pipelined:
                        spans.add_blocked(solve_key, _sync(total))
                if not pipelined:
                    # tracker summaries read device iteration counts — a
                    # per-update sync pipelined mode defers to the flush
                    trackers[f"{it}/{name}"] = _summarize_tracker(
                        tracker, spans[solve_key], budget_diag)
                    _mark_lock_step(name, it, trackers[f"{it}/{name}"])
                    if frozen:
                        trackers[f"{it}/{name}"].containment = "frozen"

                obj_key = f"{it}/{name}/objective"
                with spans.span(obj_key, name="objective", coordinate=name,
                                iteration=it):
                    if not frozen:
                        reg_terms[name] = coord.regularization_term(
                            models[name])
                    obj_dev = objective_device(total)
                    health_dev = (True if health_flag is None else
                                  quarantine_mod.combine_health(health_flag,
                                                                obj_dev))
                    if not pipelined:
                        with spans.blocked(obj_key):
                            obj = float(obj_dev)
                if not pipelined:
                    healthy = (health_dev is True
                               # strict timing mode syncs per update BY
                               # DESIGN — it exists to measure what
                               # pipelining saves
                               or bool(jax.device_get(health_dev)))  # photonlint: disable=PH001
                    if not healthy:
                        if not math.isfinite(obj):
                            _host_rollback(name, prev_model)
                            obj = float(objective_device(total))
                        label = _contain(it, name)
                        trackers[f"{it}/{name}"].containment = label
                    objective_history.append(obj)
                    logger.info("iter %d coordinate %-16s objective=%.8g "
                                "(%.2fs)", it, name, obj, spans[solve_key])

                metrics: Dict[str, object] = {}
                if do_validation:
                    val_key = f"{it}/{name}/validation"
                    with spans.span(val_key, name="validation",
                                    coordinate=name, iteration=it):
                        val_scores_by_coord[name] = \
                            models[name].score_dataset(validation_dataset)
                        val_scores = sum(val_scores_by_coord.values(),
                                         jnp.zeros(validation_dataset.num_rows))
                        if pipelined:
                            for spec in validation_specs:
                                v = evaluate_spec_device(spec, val_scores)
                                if v is None:
                                    # no device kernel (grouped/custom):
                                    # host fallback, one timed [n] transfer
                                    with spans.blocked(val_key):
                                        s_np = np.asarray(val_scores)
                                    v = spec.evaluate(validation_dataset, s_np)
                                metrics[spec.name] = v
                        else:
                            with spans.blocked(val_key):
                                s_np = np.asarray(val_scores)
                            vals = [spec.evaluate(validation_dataset, s_np)
                                    for spec in validation_specs]
                    if not pipelined:
                        for k, (spec, v) in enumerate(
                                zip(validation_specs, vals)):
                            validation_history[spec.name].append(v)
                            logger.info("  validation %-24s = %.6g",
                                        spec.name, v)
                            if k == 0:  # best FULL model by first evaluator
                                if best_metric is None or \
                                        spec.evaluator.better_than(v, best_metric):
                                    best_metric = v
                                    best_model = GameModel(dict(models),
                                                           task_type)
                if residency is not None:
                    # update + own-score + objective (and the validation
                    # rescore, which reads the VALIDATION dataset's shards)
                    # are all dispatched: under budget pressure this
                    # coordinate's training blocks drop now and re-stream
                    # on its next visit.  Dropping Python references is
                    # queue-safe — XLA keeps buffers alive until in-flight
                    # consumers finish.
                    residency.after_update(name)
                staged = _staged_delta(mesh_before)
                stream_d = _stream_delta(coord, stream_before)
                # fresh traces during this visit (tracing happens at
                # dispatch time, so the count is settled HERE even in
                # pipelined mode — nothing below launches device work)
                retraces = (telemetry.retrace_count() - _retr0
                            if _retr0 is not None else None)
                if not pipelined:
                    if staged is not None:
                        trackers[f"{it}/{name}"].staged_bytes = staged
                    trackers[f"{it}/{name}"].retraces = retraces
                    trackers[f"{it}/{name}"].stream = stream_d
                if pipelined:
                    pending.append({"it": it, "name": name,
                                    "solve_key": solve_key,
                                    "objective": obj_dev, "metrics": metrics,
                                    "models": dict(models),
                                    "tracker": tracker,
                                    "budget": budget_diag,
                                    "health": health_dev,
                                    "prev_model": prev_model,
                                    "staged": staged,
                                    "stream": stream_d,
                                    "retraces": retraces,
                                    "containment": ("frozen" if frozen
                                                    else None)})
                telemetry.pop(_visit_span)

                if faults.preemption_requested() \
                        and name != updating_sequence[-1]:
                    # the in-flight coordinate update is DONE; settle the
                    # iteration's device scalars, then exit resumably (the
                    # newest durable record covers the completed
                    # iterations — this partial iteration retrains)
                    if pipelined:
                        with spans.span(f"{it}/flush", host_blocked=True,
                                        name="flush", iteration=it):
                            flush_pending()
                    _preempt(it)

            if pipelined:
                # outer-iteration boundary: the ONE host sync of the
                # iteration (Snap ML-style pipelining: everything above was
                # enqueued without waiting)
                with spans.span(f"{it}/flush", host_blocked=True,
                                name="flush", iteration=it):
                    flush_pending()

            if checkpoint_dir is not None:
                with spans.span(f"{it}/checkpoint", name="checkpoint",
                                iteration=it):
                    ckpt_model = GameModel(dict(models), task_type)
                    if pipelined:
                        if checkpointer is None:
                            checkpointer = AsyncCheckpointer(checkpoint_dir)
                        checkpointer.submit(it, ckpt_model,
                                            objective_history,
                                            validation_history,
                                            best_model, best_metric,
                                            checkpoint_fingerprint)
                    else:
                        _write_checkpoint(checkpoint_dir, it, ckpt_model,
                                          objective_history,
                                          validation_history,
                                          best_model, best_metric,
                                          checkpoint_fingerprint)

            telemetry.pop(_it_span)
            if faults.preemption_requested():
                # iteration boundary: this iteration's record is submitted
                # (pipelined) or already on disk (strict) — drain and exit
                _preempt(it + 1)
        loop_ok = True
    finally:
        if checkpointer is not None:
            # drain + stop the writer; on the success path a worker failure
            # must surface (durability is part of the fit's contract), on
            # an exception path it must not mask the original error
            with spans.span("checkpoint/join"):
                checkpointer.shutdown(raise_errors=loop_ok)

    if (do_validation and resume is not None
            and start_iteration >= num_iterations
            and any(not validation_history[s.name] for s in validation_specs)):
        # resumed past the last iteration (the checkpoint already covers the
        # whole fit) and the record lacks metrics for some spec (e.g. the
        # original fit ran without validation): evaluate the restored model
        # once for those specs — callers like select_best_result need them.
        # Specs whose restored history is already complete are left alone.
        val_scores = sum(val_scores_by_coord.values(),
                         jnp.zeros(validation_dataset.num_rows))
        for k, spec in enumerate(validation_specs):
            if validation_history[spec.name]:
                continue
            v = spec.evaluate(validation_dataset, val_scores)
            validation_history[spec.name].append(v)
            if k == 0 and (best_metric is None
                           or spec.evaluator.better_than(v, best_metric)):
                best_metric = v
                best_model = GameModel(dict(models), task_type)

    # host-blocked accounting into the registry (the PH001 rule's runtime
    # counterpart): host floats only, no device reads
    _wall = spans.total()
    _hb = spans.host_blocked_total()
    telemetry.gauge("train.host_blocked_s").set(round(_hb, 4))
    telemetry.gauge("train.host_blocked_frac").set(
        round(_hb / _wall, 6) if _wall > 0 else 0.0)

    final = GameModel(dict(models), task_type)
    if validation_dataset is None or not validation_specs:
        best_model = final
    return CoordinateDescentResult(
        model=final, best_model=best_model,
        objective_history=objective_history,
        validation_history=validation_history, timings=spans,
        trackers=trackers,
        containment_events=monitor.events,
        frozen_coordinates=monitor.frozen)
