"""Multi-process (multi-host) runtime: bring-up, per-process data placement,
and lost-worker containment for meshes that span machines.

The mesh path so far ran on the devices of ONE process; this module is the
top level of the Snap ML hierarchy (PAPERS.md: arXiv 1803.06333 device ->
host -> cluster) — the role Spark itself played for the reference (executor
bring-up, partition locality, lost-executor handling).  Three concerns live
here, deliberately OUTSIDE jax so importing this module never initializes a
backend:

  * **Process identity** (`process_count`/`process_index`/`is_primary`):
    resolved from `initialize()` state, falling back to the
    ``PHOTON_NUM_PROCESSES`` / ``PHOTON_PROCESS_ID`` environment (pod
    launchers export these before python starts).  `utils/durable.py`
    consults `is_primary()` so only process 0 performs durable writes —
    N processes racing one ``state.json`` atomic replace is the multi-writer
    hazard this kills.

  * **Host-local placement** (`put_global`, `global_rows`, `global_zeros`,
    `host_gather`, `process_row_range`): global sharded arrays are assembled
    with `jax.make_array_from_single_device_arrays` from each process's OWN
    row block, so staging moves ZERO bytes across hosts — every process
    transfers only the shards its devices own (the locality the reference
    got from RDD partitioning).  `local_nbytes` reports the per-process
    (addressable, deduplicated) byte footprint the residency layer accounts.

  * **Lost-worker containment** (`WorkerWatchdog`): every process heartbeats
    a per-process file under the shared run directory and watches its peers.
    A peer silent past the timeout means a SIGKILLed/partitioned worker; the
    survivors first request graceful preemption (finish the in-flight
    coordinate update, make the newest checkpoint durable — the PR 5
    discipline one level up) and, if the training loop is wedged inside a
    collective that will never complete, hard-exit with the SAME resumable
    status ``EXIT_PREEMPTED`` (75).  Durable state is checkpoint-consistent
    at every instant (atomic manifest writes), so a relaunch at a smaller
    ``--num-processes`` re-chunks over the survivors and resumes from the
    manifest-verified record.
"""
from __future__ import annotations

import json
import logging
import os
import sys
import threading
import time
from typing import Callable, Dict, Optional

import numpy as np

logger = logging.getLogger("photon_ml_tpu")

#: env-var fallbacks for pod launchers (flags win when passed explicitly)
ENV_COORDINATOR = "PHOTON_COORDINATOR"
ENV_NUM_PROCESSES = "PHOTON_NUM_PROCESSES"
ENV_PROCESS_ID = "PHOTON_PROCESS_ID"

_LOCK = threading.Lock()
_STATE: Dict[str, object] = {
    "initialized": False,     # jax.distributed joined (num_processes > 1)
    "declared": False,        # identity declared (covers num_processes == 1)
    "coordinator": None,
    "num_processes": 1,
    "process_id": 0,
    "watchdog": None,
}


class MultihostInitError(RuntimeError):
    """Bring-up failed or was re-attempted with different parameters; the
    message names the coordinator address and process id so a hanging pod
    log says WHICH worker could not join."""


class WorkerLost(RuntimeError):
    """A peer process missed its heartbeat deadline (SIGKILL, OOM,
    partition).  Carries the lost process id."""

    def __init__(self, process_id: int, silent_s: float):
        super().__init__(
            f"worker process {process_id} lost: no heartbeat for "
            f"{silent_s:.1f}s — surviving processes exit resumably "
            "(status 75) so a relaunch can re-chunk over the survivors")
        self.process_id = process_id
        self.silent_s = silent_s


def _env_int(name: str) -> Optional[int]:
    v = os.environ.get(name)
    return int(v) if v not in (None, "") else None


def process_count() -> int:
    """Processes in this run — WITHOUT touching jax (importable from the
    durable-write layer, lint tooling, and data prep before backend init)."""
    if _STATE["declared"]:
        return int(_STATE["num_processes"])  # type: ignore[arg-type]
    return _env_int(ENV_NUM_PROCESSES) or 1


def process_index() -> int:
    if _STATE["declared"]:
        return int(_STATE["process_id"])  # type: ignore[arg-type]
    return _env_int(ENV_PROCESS_ID) or 0


def is_primary() -> bool:
    """True on the one process that owns durable writes (checkpoints,
    models, summaries)."""
    return process_index() == 0


def active() -> bool:
    """True when this run spans more than one process."""
    return process_count() > 1


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               *, timeout_s: float = 120.0) -> None:
    """Join (or declare) a multi-process run.  Idempotent: a second call
    with the same parameters is a no-op; different parameters raise
    (silently re-initializing jax.distributed would strand the first
    mesh's arrays).

    All parameters fall back to ``PHOTON_COORDINATOR`` /
    ``PHOTON_NUM_PROCESSES`` / ``PHOTON_PROCESS_ID``; with
    ``num_processes <= 1`` the identity is declared locally and
    jax.distributed is NOT started (the relaunch-over-survivors path).
    """
    coordinator_address = coordinator_address or os.environ.get(
        ENV_COORDINATOR) or None
    if num_processes is None:
        num_processes = _env_int(ENV_NUM_PROCESSES)
    if process_id is None:
        process_id = _env_int(ENV_PROCESS_ID)
    num_processes = int(num_processes or 1)
    process_id = int(process_id or 0)

    with _LOCK:
        if _STATE["declared"]:
            same = (_STATE["coordinator"] == coordinator_address
                    and _STATE["num_processes"] == num_processes
                    and _STATE["process_id"] == process_id)
            if same:
                return  # idempotent double-init
            raise MultihostInitError(
                f"multihost already initialized as process "
                f"{_STATE['process_id']}/{_STATE['num_processes']} "
                f"(coordinator {_STATE['coordinator']!r}); refusing "
                f"re-init as process {process_id}/{num_processes} "
                f"(coordinator {coordinator_address!r})")
        if num_processes <= 1:
            _STATE.update(declared=True, initialized=False,
                          coordinator=coordinator_address,
                          num_processes=1, process_id=0)
            return
        if coordinator_address is None:
            raise MultihostInitError(
                f"num_processes={num_processes} requires a coordinator "
                "address (--coordinator HOST:PORT or "
                f"${ENV_COORDINATOR}) naming process 0's endpoint")
        if not (0 <= process_id < num_processes):
            raise MultihostInitError(
                f"process_id {process_id} out of range for "
                f"num_processes={num_processes} (coordinator "
                f"{coordinator_address!r})")

        import jax
        try:
            # CPU collectives need an explicit cross-process backend; gloo
            # is the one compiled into jaxlib.  TPU ignores this knob.
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except (AttributeError, ValueError):  # pragma: no cover - old jaxlib
            pass
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id,
                initialization_timeout=int(timeout_s))
        except Exception as e:
            raise MultihostInitError(
                f"process {process_id}/{num_processes} failed to join the "
                f"run at coordinator {coordinator_address!r} within "
                f"{timeout_s:.0f}s: {e}") from e
        _STATE.update(declared=True, initialized=True,
                      coordinator=coordinator_address,
                      num_processes=num_processes, process_id=process_id)
        logger.info("multihost: process %d/%d joined run at %s",
                    process_id, num_processes, coordinator_address)


def shutdown() -> None:
    """Tear down the run: stop the watchdog, leave jax.distributed (when
    this process joined it), reset identity.  Idempotent; safe to call
    from a finally block whether or not initialize() ever ran."""
    with _LOCK:
        wd = _STATE.get("watchdog")
        lost = (wd is not None
                and getattr(wd, "lost_process", None) is not None)
        if wd is not None:
            wd.stop()  # type: ignore[union-attr]
            _STATE["watchdog"] = None
        if _STATE["initialized"] and lost:
            # jax.distributed.shutdown() runs a barrier over ALL tasks,
            # which can never complete with a dead peer.  Worse, the XLA
            # coordination client's C++ DESTRUCTOR runs the same barrier
            # at interpreter exit and FATAL-aborts this process (SIGABRT,
            # losing the resumable exit status) — there is no local-only
            # disconnect.  So a survivor cannot leave through normal
            # interpreter teardown at all: flush everything and _exit
            # with the resumable status, same as the watchdog's wedged-
            # collective escalation path.  Durable state is already
            # checkpoint-consistent (atomic manifest writes).
            from photon_ml_tpu.utils import faults
            logger.warning(
                "multihost: lost worker %s — the coordination-service "
                "shutdown barrier cannot complete without the dead peer, "
                "hard-exiting resumably (status %d)",
                wd.lost_process, faults.EXIT_PREEMPTED)
            logging.shutdown()
            try:
                sys.stdout.flush()
                sys.stderr.flush()
            except Exception:  # pragma: no cover
                pass
            os._exit(faults.EXIT_PREEMPTED)
        elif _STATE["initialized"]:
            import jax
            try:
                jax.distributed.shutdown()
            except Exception:  # pragma: no cover - peer already gone
                logger.warning("jax.distributed.shutdown failed "
                               "(peer already gone?)", exc_info=True)
        _STATE.update(declared=False, initialized=False, coordinator=None,
                      num_processes=1, process_id=0)


def set_watchdog(watchdog: Optional["WorkerWatchdog"]) -> None:
    """Register the run's watchdog so shutdown() stops it."""
    _STATE["watchdog"] = watchdog


# -- per-process placement ----------------------------------------------------

def process_row_range(n: int, *, count: Optional[int] = None,
                      index: Optional[int] = None) -> range:
    """This process's contiguous block of a length-`n` leading axis: the
    1/P of rows it stages (balanced to within one row when P does not
    divide n)."""
    p = count if count is not None else process_count()
    i = index if index is not None else process_index()
    return range((n * i) // p, (n * (i + 1)) // p)


def put_global(mesh, host, sharding):
    """Place a FULL host array as a global array under `sharding`, moving
    only the shards THIS process's devices own.

    Single-process: a plain device_put.  Multi-process: each addressable
    shard is sliced from the host array and device_put per device, then
    `jax.make_array_from_single_device_arrays` assembles the global array —
    zero cross-host data movement at staging time.  Every process must hold
    (at least) the rows its devices own; processes holding only their
    `process_row_range` slice pass it through `global_rows(...,
    local_rows=...)` instead."""
    import jax
    if not active():
        return jax.device_put(host, sharding)
    host = np.asarray(host)
    shape = host.shape
    arrays = []
    for dev, idx in sharding.addressable_devices_indices_map(shape).items():
        arrays.append(jax.device_put(host[idx], dev))
    return jax.make_array_from_single_device_arrays(shape, sharding, arrays)


def put_global_block(mesh, block, sharding, shape, row_start: int = 0):
    """Assemble a global `shape` array under `sharding` from a host `block`
    holding only global rows [row_start, row_start + len(block)) — the
    process-slice staging primitive: each host fetches just the row block
    its devices own (ChunkPlan.process_block / GameDataset.process_slice)
    and this places it with zero cross-host movement.  Every addressable
    shard must lie inside the block."""
    import jax
    block = np.asarray(block)
    if not active():
        if row_start != 0 or block.shape[0] != shape[0]:
            raise ValueError(
                f"single-process put_global_block requires the full array "
                f"(got rows [{row_start}, {row_start + block.shape[0]}) of "
                f"{shape[0]})")
        return jax.device_put(block, sharding)
    shape = tuple(shape)
    arrays = []
    for dev, idx in sharding.addressable_devices_indices_map(shape).items():
        sl = idx[0] if idx else slice(None)
        lo = (sl.start or 0) - row_start
        hi = (shape[0] if sl.stop is None else sl.stop) - row_start
        if lo < 0 or hi > block.shape[0]:
            raise ValueError(
                f"process {process_index()} holds global rows "
                f"[{row_start}, {row_start + block.shape[0]}) but device "
                f"{dev} owns [{sl.start or 0}, {sl.stop}) — the block does "
                "not cover this process's shards")
        rest = tuple(idx[1:])
        arrays.append(jax.device_put(block[(slice(lo, hi),) + rest], dev))
    return jax.make_array_from_single_device_arrays(shape, sharding, arrays)


def global_rows(mesh, host):
    """[n, ...] host array -> global array row-sharded over the mesh "data"
    axis.  The multi-process-safe replacement for a bare jnp.asarray: a
    local placement cannot feed a jit whose other operands span peer
    processes' devices."""
    import jax
    from photon_ml_tpu.parallel.mesh import data_sharding
    host = np.asarray(host)
    host = host.astype(jax.dtypes.canonicalize_dtype(host.dtype), copy=False)
    return put_global(mesh, host, data_sharding(mesh, host.ndim))


def global_zeros(mesh, n: int, dtype=None):
    """Data-sharded [n] zeros on the global mesh (the multi-process
    jnp.zeros: zero-filled shards are built per process, nothing moves)."""
    import jax
    dtype = dtype or jax.dtypes.canonicalize_dtype(np.float64)
    return global_rows(mesh, np.zeros(n, dtype=dtype))


def host_gather(arr) -> np.ndarray:
    """Global array -> full host numpy copy on EVERY process.

    Fully-addressable (single-process or replicated) arrays read back
    directly; a cross-process sharded array is first all-gathered to the
    replicated layout by a tiny jitted identity (a collective: every
    process must call this at the same point, which holds — the callers
    are the lockstep evaluator paths)."""
    import jax
    if getattr(arr, "is_fully_addressable", True):
        return np.asarray(arr)
    from photon_ml_tpu.parallel.mesh import replicated
    sh = arr.sharding
    rep = jax.jit(lambda a: a, out_shardings=replicated(sh.mesh))(arr)
    return np.asarray(rep)


def local_nbytes(arr) -> int:
    """Logical bytes THIS process owns of a (possibly global) array:
    addressable shards, deduplicated by global index so a replicated array
    counts once (matching single-host `.nbytes` accounting, and making the
    residency layer's cold/warm byte gates per-process)."""
    if not active() or not hasattr(arr, "addressable_shards"):
        return int(arr.nbytes)
    seen: Dict[tuple, int] = {}
    for s in arr.addressable_shards:
        key = tuple((sl.start, sl.stop, sl.step)
                    if isinstance(sl, slice) else sl for sl in s.index)
        seen[key] = int(s.data.nbytes)
    return sum(seen.values())


# -- lost-worker containment --------------------------------------------------

class WorkerWatchdog:
    """Per-process heartbeat files + peer staleness detection over the
    SHARED run directory (the same filesystem the checkpoints live on).

    Every `interval_s` the daemon thread (1) rewrites this process's
    ``heartbeats/proc-<i>.json`` and (2) checks each peer's file.  A peer
    whose heartbeat is older than `timeout_s` (and not marked done) is
    LOST: `on_lost` fires once — the default requests graceful preemption,
    so the training loop exits 75 at the next coordinate boundary with the
    newest checkpoint durable — and if the process is still alive
    `escalate_s` later (wedged inside a collective whose peer is gone, the
    common case under SIGKILL), the watchdog hard-exits with the same
    resumable status 75.  Both exits leave checkpoint-consistent durable
    state: every checkpoint write is atomic + manifest-sealed."""

    def __init__(self, directory: str, *,
                 num_processes: Optional[int] = None,
                 process_id: Optional[int] = None,
                 interval_s: float = 0.5, timeout_s: float = 10.0,
                 escalate_s: float = 10.0,
                 on_lost: Optional[Callable[[int], None]] = None):
        self.directory = os.path.join(directory, "heartbeats")
        self.num_processes = (num_processes if num_processes is not None
                              else process_count())
        self.process_id = (process_id if process_id is not None
                           else process_index())
        self.interval_s = float(interval_s)
        self.timeout_s = float(timeout_s)
        self.escalate_s = float(escalate_s)
        self._on_lost = on_lost
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._started_at = 0.0
        # set once by whichever thread detects the loss first (the
        # watchdog sweep or the main thread's confirm_lost) via the
        # locked _publish_loss; read lock-free afterwards (monotonic
        # None -> value publish)
        self._loss_lock = threading.Lock()
        self._lost_at: Optional[float] = None  # photonlint: guarded-by=atomic
        self.lost_process: Optional[int] = None  # photonlint: guarded-by=atomic

    def _publish_loss(self, lost: "WorkerLost") -> bool:
        """First-writer-wins publication of a detected loss; True when
        THIS caller performed the publish (and owns its side effects)."""
        with self._loss_lock:
            if self.lost_process is not None:
                return False
            self._lost_at = time.time()
            self.lost_process = lost.process_id
        logger.error("multihost: %s", lost)
        return True

    def _path(self, pid: int) -> str:
        return os.path.join(self.directory, f"proc-{pid}.json")

    def _beat(self, done: bool = False) -> None:
        from photon_ml_tpu.utils import durable
        durable.atomic_write_json(  # photonlint: all-process
            self._path(self.process_id),
            {"process_id": self.process_id, "pid": os.getpid(),
             "time": time.time(), "done": done},
            fsync=False, all_process=True)

    def start(self) -> "WorkerWatchdog":
        if self.num_processes <= 1:
            return self  # nothing to watch
        os.makedirs(self.directory, exist_ok=True)
        self._started_at = time.time()
        self._beat()
        self._thread = threading.Thread(
            target=self._run, name="photon-multihost-watchdog", daemon=True)
        self._thread.start()
        return self

    def stop(self) -> None:
        """Clean exit: mark this process done (so peers finishing later do
        not mistake our silence for a crash) and stop the thread."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=self.interval_s * 4 + 1.0)
            self._thread = None
        if self.num_processes > 1 and self._started_at:
            try:
                self._beat(done=True)
            except OSError:  # pragma: no cover - run dir removed under us
                pass

    def confirm_lost(self, wait_s: Optional[float] = None) -> Optional[int]:
        """Synchronously decide whether a peer is dead.

        A failed collective surfaces in the MAIN thread within
        milliseconds of a peer's death — often before a single heartbeat
        interval has elapsed — so an exception handler cannot just read
        ``lost_process``.  Poll the peer heartbeats for up to ``wait_s``
        (default: timeout_s plus slack): a dead peer goes silent past
        timeout_s and its process id is returned; a live one keeps
        beating and None is returned once the window closes.
        """
        if self.num_processes <= 1:
            return None
        wait_s = (self.timeout_s + 2.0 * self.interval_s + 1.0
                  if wait_s is None else float(wait_s))
        deadline = time.time() + wait_s
        while self.lost_process is None:
            lost = self.check_peers()
            if lost is not None:
                # publish it ourselves: the background thread may have
                # been stopped already, or just not swept yet
                self._publish_loss(lost)
                break
            if time.time() >= deadline:
                break
            time.sleep(min(self.interval_s, 0.25))
        return self.lost_process

    # one watchdog sweep; split out for deterministic unit testing
    def check_peers(self, now: Optional[float] = None) -> Optional[WorkerLost]:
        now = time.time() if now is None else now
        for pid in range(self.num_processes):
            if pid == self.process_id:
                continue
            try:
                with open(self._path(pid)) as f:
                    beat = json.load(f)
            except (OSError, ValueError):
                # not written yet (startup) or torn mid-replace: covered by
                # the startup grace below / next sweep
                beat = None
            if beat is None:
                silent = now - self._started_at
            elif beat.get("done"):
                continue
            else:
                silent = now - float(beat.get("time", 0.0))
            if silent > self.timeout_s:
                return WorkerLost(pid, silent)
        return None

    def _run(self) -> None:
        from photon_ml_tpu import telemetry
        from photon_ml_tpu.utils import faults
        while not self._stop.wait(self.interval_s):
            try:
                self._beat()
            except OSError:  # pragma: no cover - disk full / dir gone
                logger.warning("multihost watchdog: heartbeat write failed",
                               exc_info=True)
            lost = self.check_peers()
            if lost is None:
                continue
            if self._publish_loss(lost):
                telemetry.counter("multihost.worker_lost").inc()
                if self._on_lost is not None:
                    self._on_lost(lost.process_id)
                else:
                    # graceful path: the training loop notices at the next
                    # coordinate boundary, seals the newest checkpoint, and
                    # exits 75 through the normal Preempted flow
                    faults.request_preemption()
            elif time.time() - self._lost_at > self.escalate_s:
                # the loop never reached a boundary: it is blocked inside a
                # collective whose peer is dead.  Durable state is already
                # checkpoint-consistent (atomic manifest writes), so exit
                # with the SAME resumable status the graceful path uses.
                logger.error(
                    "multihost: still alive %.1fs after losing worker %s — "
                    "assuming a wedged collective, hard-exiting resumably "
                    "(status %d)", time.time() - self._lost_at,
                    self.lost_process, faults.EXIT_PREEMPTED)
                logging.shutdown()
                os._exit(faults.EXIT_PREEMPTED)
