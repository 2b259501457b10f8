"""Out-of-core chunk streaming: host shards -> double-buffered device chunks.

The resident training path requires every coordinate's data on the
accelerator for the whole fit, which a corpus larger than one chip's HBM cannot
give.  Snap ML
(arXiv:1803.06333) and "Large-Scale Stochastic Learning using GPUs"
(arXiv:1702.07005) both recover near-resident throughput on datasets larger
than device memory with hierarchical memory management + pipelined
host<->accelerator chunk transfer.  This module is that layer:

  - `ChunkPlan` row-partitions a flat batch into power-of-two-sized chunks
    (via the ONE shape-bucketing rule, utils.math.ceil_pow2, shared with
    training prep and the serving micro-batcher) so the whole stream
    compiles at most two XLA programs: the full-chunk shape and the
    pow-2-padded tail shape.
  - `Prefetcher` double-buffers: a background thread stages chunk i+1
    (slice + pad + device transfer) while chunk i computes, with bounded
    lookahead so at most `depth` (default 2) chunks are device-resident.
  - `StreamStats` is the transfer-size accounting used where
    device.memory_stats() is unavailable (the CPU backend): peak resident
    chunk count/bytes and total bytes staged.

Nothing here is jax-traced: chunk STAGING is host work by design, and every
compiled consumer (ops/chunked.py) is keyed only on the chunk shape — chunk
COUNT never appears in a cache key, so growing the dataset re-uses every
program (tested by tests/test_streaming.py's compile-count regression).
"""
from __future__ import annotations

import dataclasses
import queue
import random
import threading
import time
from typing import Callable, Dict, Iterator, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import telemetry
from photon_ml_tpu.utils import faults, locktrace
from photon_ml_tpu.utils.math import ceil_pow2

# never plan chunks smaller than this: per-chunk dispatch overhead would
# dominate
MIN_CHUNK_ROWS = 256

# staging retry policy: a flaky host read / device transfer must not kill an
# hours-long fit.  Transient failures (faults.is_transient: OSError,
# timeouts, injected TransientFault, ...) retry up to STAGE_MAX_ATTEMPTS
# with jittered exponential backoff; everything else — and always
# KeyboardInterrupt/SystemExit — propagates immediately.
STAGE_MAX_ATTEMPTS = 3
STAGE_BACKOFF_S = 0.05
STAGE_BACKOFF_JITTER = 0.5


class ChunkStagingError(RuntimeError):
    """A chunk failed to stage after exhausting its retry budget (or hit a
    fatal, non-retryable error).  The message names the chunk; the original
    failure rides as __cause__."""

    def __init__(self, message: str, chunk_index: int):
        super().__init__(message)
        self.chunk_index = chunk_index


@dataclasses.dataclass(frozen=True)
class ChunkSpec:
    """One row range [start, stop) padded to `padded_rows` (a power of two).
    Padding rows carry zero features / SAFE labels / zero weights and are
    excluded by the chunk mask."""

    index: int
    start: int
    stop: int
    padded_rows: int

    @property
    def rows(self) -> int:
        return self.stop - self.start


@dataclasses.dataclass(frozen=True)
class ChunkPlan:
    """Static row partition of an [n, ...] batch into pow-2-sized chunks.

    All full chunks share one shape; the tail is padded to its own power of
    two, so a plan compiles at most TWO programs per consumer kernel
    regardless of how many chunks (i.e. how many rows) it covers."""

    num_rows: int
    chunk_rows: int                  # pow2 size of the full chunks
    chunks: Tuple[ChunkSpec, ...]

    @property
    def num_chunks(self) -> int:
        return len(self.chunks)

    @property
    def chunk_shapes(self) -> Tuple[int, ...]:
        """Distinct padded sizes, ascending (<= 2 by construction)."""
        return tuple(sorted({c.padded_rows for c in self.chunks}))

    def chunk_bytes(self, bytes_per_row: int) -> int:
        """Device bytes of ONE full chunk (the double-buffer unit)."""
        return self.chunk_rows * bytes_per_row

    def process_block(self, spec: ChunkSpec, *, num_shards: int,
                      shard_lo: int, shard_hi: int) -> Tuple[int, int]:
        """The process-slice view of one chunk: padded-row offsets [lo, hi)
        of `spec` owned by data-axis shards [shard_lo, shard_hi) of
        `num_shards`.  On a multi-process mesh each process's devices hold
        a contiguous block of the data axis (parallel/mesh.py make_mesh),
        so its share of every chunk is the contiguous padded-row block
        returned here — the host then fetches/pads/transfers ONLY those
        rows (1/P of the stream per process, zero cross-host movement)."""
        if spec.padded_rows % num_shards:
            raise ValueError(
                f"chunk {spec.index} pads to {spec.padded_rows} rows, not a "
                f"multiple of {num_shards} data-axis shards; build the plan "
                "with row_multiple=num_shards")
        per = spec.padded_rows // num_shards
        return shard_lo * per, shard_hi * per

    @staticmethod
    def build(num_rows: int, *, chunk_rows: Optional[int] = None,
              hbm_budget_bytes: Optional[int] = None,
              bytes_per_row: Optional[int] = None,
              row_multiple: int = 1) -> "ChunkPlan":
        """Partition `num_rows` rows.

        Either pass `chunk_rows` (rounded up to a power of two) or a device
        budget: the chunk is then the largest power of two such that TWO
        chunks (current + prefetched) fit in `hbm_budget_bytes` given
        `bytes_per_row`.  A chunk covering every row degenerates to a
        single-chunk plan — the streamed oracle then matches the resident
        one bit-for-bit (tests rely on this).

        `row_multiple` additionally rounds every padded chunk size up to a
        multiple (the mesh data-axis size, so each staged chunk shards
        evenly over the devices).  The ≤2-compiled-shapes property is
        preserved: full chunks share one rounded size, the tail gets its
        own.
        """
        if num_rows < 1:
            raise ValueError(f"num_rows must be >= 1, got {num_rows}")
        if row_multiple < 1:
            raise ValueError(f"row_multiple must be >= 1, got {row_multiple}")
        if chunk_rows is None:
            if hbm_budget_bytes is None or bytes_per_row is None:
                raise ValueError("pass chunk_rows, or hbm_budget_bytes with "
                                 "bytes_per_row")
            per_chunk = max(hbm_budget_bytes // (2 * max(bytes_per_row, 1)), 1)
            chunk_rows = ceil_pow2(per_chunk)
            if chunk_rows > per_chunk:        # ceil overshot the budget
                chunk_rows //= 2
        mult = int(row_multiple)
        ceil_mult = lambda v: -(-int(v) // mult) * mult
        chunk_rows = int(ceil_pow2(max(int(chunk_rows), MIN_CHUNK_ROWS)))
        chunk_rows = min(chunk_rows, int(ceil_pow2(num_rows)))
        chunk_rows = ceil_mult(chunk_rows)
        chunks = []
        start = 0
        while start < num_rows:
            stop = min(start + chunk_rows, num_rows)
            rows = stop - start
            padded = (chunk_rows if rows == chunk_rows
                      else min(ceil_mult(ceil_pow2(rows)), chunk_rows))
            chunks.append(ChunkSpec(index=len(chunks), start=start, stop=stop,
                                    padded_rows=padded))
            start = stop
        return ChunkPlan(num_rows=num_rows, chunk_rows=chunk_rows,
                         chunks=tuple(chunks))


def pad_rows_host(a: np.ndarray, rows: int, fill) -> np.ndarray:
    """Host-side row pad of a [r, ...] slice to [rows, ...] with `fill`."""
    r = a.shape[0]
    if r == rows:
        return a
    out = np.full((rows,) + a.shape[1:], fill, a.dtype)
    out[:r] = a
    return out


class StreamStats:
    """Transfer-size accounting for one streaming consumer: the
    `memory_stats()` stand-in on backends that lack it (the CPU backend
    returns None).  `peak_resident_chunks` counts chunks simultaneously alive on
    device (staged or being consumed) — the double-buffer invariant is that
    it never exceeds the Prefetcher depth."""

    def __init__(self):
        self._lock = locktrace.tracked(threading.Lock(),
                                       "StreamStats._lock")
        self.total_bytes = 0
        self.chunks_staged = 0
        self.passes = 0
        self.resident_chunks = 0
        self.resident_bytes = 0
        self.peak_resident_chunks = 0
        self.peak_resident_bytes = 0
        # retry accounting: transient staging failures absorbed (retries)
        # and chunks that exhausted the retry budget (gave_up)
        self.retries = 0
        self.gave_up = 0
        # work-per-staged-byte accounting: chunk-epochs executed on
        # resident chunks (1 per chunk for a plain oracle pass, K per
        # chunk when the stochastic lane pins the chunk for K local
        # epochs) and examples processed (real rows x epochs).  The ratio
        # examples_processed / total_bytes is THE out-of-core efficiency
        # number (tests/test_stochastic.py holds the stochastic lane to
        # 1.5x the strict lane's).
        self.local_epochs = 0
        self.examples_processed = 0

    def note_retry(self) -> None:
        with self._lock:
            self.retries += 1
        telemetry.counter("stream.retries").inc()

    def note_gave_up(self) -> None:
        with self._lock:
            self.gave_up += 1
        telemetry.counter("stream.gave_up").inc()

    def note_staged(self, nbytes: int) -> None:
        with self._lock:
            self.total_bytes += nbytes
            self.chunks_staged += 1
            self.resident_chunks += 1
            self.resident_bytes += nbytes
            self.peak_resident_chunks = max(self.peak_resident_chunks,
                                            self.resident_chunks)
            self.peak_resident_bytes = max(self.peak_resident_bytes,
                                           self.resident_bytes)
        # process-global mirror (telemetry.snapshot() aggregates every
        # Prefetcher; per-instance numbers stay on this object)
        telemetry.counter("stream.staged_bytes").inc(nbytes)
        telemetry.counter("stream.chunks_staged").inc()

    def note_released(self, nbytes: int) -> None:
        with self._lock:
            self.resident_chunks -= 1
            self.resident_bytes -= nbytes

    def note_pass(self) -> None:
        with self._lock:
            self.passes += 1

    def note_processed(self, rows: int, epochs: int = 1) -> None:
        """`epochs` chunk-epochs of consumer work on one resident chunk
        covering `rows` real (unpadded) rows."""
        with self._lock:
            self.local_epochs += epochs
            self.examples_processed += rows * epochs
        telemetry.counter("stream.local_epochs").inc(epochs)
        telemetry.counter("stream.examples").inc(rows * epochs)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            snap = {"total_bytes": self.total_bytes,
                    "chunks_staged": self.chunks_staged,
                    "passes": self.passes,
                    "peak_resident_chunks": self.peak_resident_chunks,
                    "peak_resident_bytes": self.peak_resident_bytes,
                    "retries": self.retries,
                    "gave_up": self.gave_up,
                    "local_epochs": self.local_epochs,
                    "examples_processed": self.examples_processed}
        snap["examples_per_staged_byte"] = (
            snap["examples_processed"] / snap["total_bytes"]
            if snap["total_bytes"] else 0.0)
        # metrics mirror: the ratio as a gauge so operators see
        # work-per-staged-byte without dividing counters themselves
        telemetry.gauge("stream.examples_per_staged_byte").set(
            snap["examples_per_staged_byte"])
        return snap


def _tree_device_put(host_tree):
    """Host pytree -> device, via jnp.asarray so dtypes canonicalize exactly
    as the resident path's transfers do (float64 host arrays become float32
    under the default config, float64 under x64)."""
    import jax
    return jax.tree_util.tree_map(
        lambda a: a if a is None else jnp.asarray(a), host_tree,
        is_leaf=lambda a: a is None)


def _tree_nbytes(dev_tree) -> int:
    """Bytes THIS process staged for a device chunk tree: on a
    multi-process mesh each chunk is a global array of which this process
    transferred only its addressable shards, so the accounting (and the
    warm-bytes gates built on it) stays per-process."""
    import jax

    from photon_ml_tpu.parallel import multihost
    return sum(multihost.local_nbytes(leaf)
               for leaf in jax.tree_util.tree_leaves(dev_tree)
               if leaf is not None)


_DONE = object()


class Prefetcher:
    """Double-buffered host->device chunk pipeline over one ChunkPlan.

    `fetch(spec)` returns the chunk's HOST pytree (sliced + padded numpy
    arrays); a background thread runs fetch + device transfer for chunk
    i+1 while the consumer computes on chunk i.  Lookahead is bounded by a
    semaphore so at most `depth` chunks are device-resident at once —
    depth=2 is the classic double buffer.  Each `stream()` call is one full
    pass (one value/gradient evaluation); the thread dies with the pass.

    Failure containment: TRANSIENT staging errors (faults.is_transient —
    OSError/timeouts/injected TransientFault) retry up to `max_attempts`
    with jittered exponential backoff (StreamStats counts the retries);
    a chunk that exhausts its budget raises ChunkStagingError naming the
    chunk in the consumer.  Fatal errors skip the retry loop entirely, and
    KeyboardInterrupt/SystemExit re-raise AS THEMSELVES in the consumer —
    an operator interrupt must never be laundered into a staging error."""

    def __init__(self, plan: ChunkPlan, fetch: Callable[[ChunkSpec], object],
                 depth: int = 2, stats: Optional[StreamStats] = None,
                 max_attempts: int = STAGE_MAX_ATTEMPTS,
                 backoff_s: float = STAGE_BACKOFF_S,
                 transfer: Optional[Callable[[object], object]] = None):
        if depth < 2:
            # the producer stages chunk k only after the consumer has taken
            # chunk k-depth+1, so depth 1 would deadlock before chunk 0
            raise ValueError(f"depth must be >= 2, got {depth}")
        if max_attempts < 1:
            raise ValueError(f"max_attempts must be >= 1, got {max_attempts}")
        self.plan = plan
        self.fetch = fetch
        self.depth = depth
        self.max_attempts = max_attempts
        self.backoff_s = backoff_s
        self.stats = stats if stats is not None else StreamStats()
        # host pytree -> device placement, called as transfer(host, spec);
        # the default is an unsharded jnp.asarray transfer — mesh consumers
        # (ops/chunked.py) pass a data-sharded device_put so each chunk
        # lands split over the mesh (and, multi-process, assembled from
        # this process's row block alone)
        self._transfer = (transfer if transfer is not None
                          else lambda host, spec: _tree_device_put(host))

    def _stage_with_retry(self, spec: ChunkSpec, jitter: random.Random):
        """fetch + device transfer for one chunk, absorbing transient
        failures up to the attempt budget."""
        attempt = 0
        while True:
            attempt += 1
            try:
                faults.fire("stage.fetch", chunk=spec.index)
                host = self.fetch(spec)
                faults.fire("stage.transfer", chunk=spec.index)
                return self._transfer(host, spec)
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:
                if not faults.is_transient(e):
                    self.stats.note_gave_up()
                    raise ChunkStagingError(
                        f"chunk staging failed for chunk {spec.index} of "
                        f"{self.plan.num_chunks} (fatal "
                        f"{type(e).__name__}, not retryable)",
                        spec.index) from e
                if attempt >= self.max_attempts:
                    self.stats.note_gave_up()
                    raise ChunkStagingError(
                        f"chunk staging failed for chunk {spec.index} of "
                        f"{self.plan.num_chunks} after {attempt} "
                        f"attempt(s)", spec.index) from e
                self.stats.note_retry()
                telemetry.event("stage_retry", chunk=spec.index,
                                attempt=attempt,
                                error=f"{type(e).__name__}: {e}")
                # exponential backoff with jitter so concurrent streams
                # don't re-hammer a struggling source in lockstep
                delay = (self.backoff_s * (2 ** (attempt - 1))
                         * (1.0 + STAGE_BACKOFF_JITTER * jitter.random()))
                time.sleep(delay)

    def stream(self, pin_epochs: int = 1
               ) -> Iterator[Tuple[ChunkSpec, object]]:
        """One full pass over the plan's chunks.

        `pin_epochs` declares how many local epochs the CONSUMER will run
        on each yielded chunk before asking for the next one (the
        stochastic lane, optim/stochastic.py).  The chunk is staged ONCE
        and stays pinned on device for all of them — it never round-trips
        back through the queue — while the producer keeps prefetching the
        next chunk behind it (the double-buffer bound is unchanged: at
        most `depth` chunks resident).  StreamStats accounts the extra
        work: `local_epochs` += pin_epochs and `examples_processed` +=
        rows * pin_epochs per chunk, which is what moves
        examples_per_staged_byte."""
        if pin_epochs < 1:
            raise ValueError(f"pin_epochs must be >= 1, got {pin_epochs}")
        self.stats.note_pass()
        lookahead = threading.Semaphore(self.depth - 1)
        q: "queue.Queue" = queue.Queue()
        cancel = threading.Event()
        # deterministic per-pass jitter (seeded by the pass ordinal) keeps
        # retry timing reproducible for a given plan + failure sequence
        jitter = random.Random(self.stats.passes)

        def producer():
            spec = None
            try:
                for spec in self.plan.chunks:
                    # token acquired BEFORE staging: the device never holds
                    # more than `depth` chunks, counting the one the
                    # consumer is computing on
                    while not lookahead.acquire(timeout=0.1):
                        if cancel.is_set():
                            return
                    if cancel.is_set():
                        return
                    # span on the PREFETCH thread: staging gets its own
                    # track in the trace, overlapping the consumer's solve
                    with telemetry.span("stage", chunk=spec.index,
                                        rows=spec.rows):
                        dev = self._stage_with_retry(spec, jitter)
                    self.stats.note_staged(_tree_nbytes(dev))
                    q.put((spec, dev))
                q.put(_DONE)
            except (KeyboardInterrupt, SystemExit) as e:
                # NOT a staging failure: re-raise distinctly in the
                # consumer (the operator interrupted / the process is
                # exiting), never wrapped into a RuntimeError
                q.put(("interrupt", e))
            except ChunkStagingError as e:  # already named + chained
                q.put(e)
            except BaseException as e:  # unexpected: name the chunk anyway
                idx = spec.index if spec is not None else -1
                err = ChunkStagingError(
                    f"chunk staging failed for chunk {idx} of "
                    f"{self.plan.num_chunks}", max(idx, 0))
                err.__cause__ = e
                q.put(err)

        thread = threading.Thread(target=producer, daemon=True,
                                  name="photon-chunk-prefetch")
        thread.start()
        prev_bytes = 0
        try:
            while True:
                item = q.get()
                if item is _DONE:
                    return
                if isinstance(item, tuple) and len(item) == 2 \
                        and item[0] == "interrupt":
                    raise item[1]
                if isinstance(item, BaseException):
                    raise item
                spec, dev = item
                if prev_bytes:
                    # the consumer asked for chunk i+1 => it has dispatched
                    # all work on chunk i and dropped its reference
                    self.stats.note_released(prev_bytes)
                prev_bytes = _tree_nbytes(dev)
                lookahead.release()
                self.stats.note_processed(spec.rows, pin_epochs)
                yield spec, dev
                dev = None
        finally:
            cancel.set()
            if prev_bytes:
                self.stats.note_released(prev_bytes)
