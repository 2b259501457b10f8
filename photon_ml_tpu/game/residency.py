"""HBM residency budget: decide what lives on device, evict what doesn't fit.

The resident trainer pins EVERY coordinate's device blocks (FE feature
shards, RE EntityBlocks) for the whole fit, so a corpus whose coordinates exceed one chip's HBM
cannot train (ROADMAP R0: no chip record of the budgeted path yet).  With a budget (GameTrainingConfig.hbm_budget_bytes /
--hbm-budget) this manager applies the hierarchy Snap ML's memory manager
describes (arXiv:1803.06333):

  1. FLAT [n] vectors (residual scores, labels, weights, offsets) ALWAYS
     stay device-resident: they are touched by every coordinate every
     update and are ~d times smaller than any feature block.
  2. A fixed-effect shard whose resident footprint busts the budget runs
     STREAMED (ChunkedGLMObjective: host shard, two chunks of HBM).
     Streaming's per-iteration staging cost is what the stochastic lane
     (optim/stochastic.py) amortizes: with a SolverSchedule whose
     stochastic_passes > 0, each staged chunk does a full epoch's worth
     of local solver work before eviction, so the auto-stream decision's
     downside shrinks by the local epoch count — the per-coordinate
     `stream` snapshots in `accounting()` (examples_per_staged_byte)
     make that trade observable per fit.
  3. When the remaining resident coordinates still exceed the budget, the
     descent loop rotates residency: after a coordinate's update+score its
     device blocks are EVICTED and re-streamed on its next visit (host
     copies kept by the out-of-core build, keep_host_blocks).

The eviction MECHANISM lives in the tiered entity store
(photon_ml_tpu/store/handles.py): every coordinate registers its
evictable device blocks as a BlockStore handle at construction, and the
rotation's fetch/evict transitions run through the store — the one
eviction entry point shared with mesh staging and serving, with the
`store.fetch` fault site + shared retry discipline on every re-stage and
the unified store.* telemetry counters.  This manager keeps the POLICY:
per-device budget math, the evict-inactive decision, and the peak
accounting below.

On a device mesh the budget is PER DEVICE: coordinate blocks shard their
leading axis over the mesh "data" axis, so each device holds 1/D of every
block and the manager accounts block bytes divided by D (flat [n] vectors
are counted undivided — conservative, they may stay replicated).  Fit size
then scales with AGGREGATE fleet HBM: the same budget admits D times the
data on a D-chip mesh.

The manager also keeps the transfer-size accounting (`peak_tracked_bytes`,
per-device when a mesh is present) that stands in for
device.memory_stats() on backends without it — the peak-memory tests
(tests/test_streaming.py, tests/test_mesh_residency.py) consume it.
"""
from __future__ import annotations

import dataclasses
import logging
import math
from typing import Dict, Optional

from photon_ml_tpu.store.handles import BlockStore

logger = logging.getLogger("photon_ml_tpu")


@dataclasses.dataclass
class CoordinateFootprint:
    name: str
    block_bytes: int            # evictable device blocks (FE shard / RE blocks)
    streamed: bool              # FE chunk streaming (blocks never resident)
    chunk_bytes: int = 0        # 2-chunk double-buffer cost when streamed


class ResidencyManager:
    """Tracks per-coordinate device footprints against the budget and runs
    the eviction rotation inside run_coordinate_descent — through the
    tiered store's block handles.

    `coordinates` is the built Coordinate map — each coordinate exposes
    `device_block_bytes()`, `evict_device_blocks()` and (for streamed FE)
    `streaming_buffer_bytes()`.  Eviction only happens when the budget
    cannot hold every non-streamed coordinate at once; otherwise the
    manager is accounting-only and the fit behaves exactly as before."""

    def __init__(self, coordinates: Dict[str, object],
                 budget_bytes: Optional[int],
                 flat_vector_bytes: int = 0,
                 mesh=None):
        self.budget_bytes = budget_bytes
        self.flat_vector_bytes = flat_vector_bytes
        # per-device accounting divisor: blocks shard their leading axis
        # over the mesh "data" axis, so each device carries 1/D of every
        # block; the budget is interpreted PER DEVICE
        self.data_devices = 1
        if mesh is not None:
            from photon_ml_tpu.parallel.mesh import DATA_AXIS
            self.data_devices = max(int(mesh.shape.get(DATA_AXIS, 1)), 1)
        per_dev = lambda b: int(math.ceil(b / self.data_devices))
        self.footprints: Dict[str, CoordinateFootprint] = {}
        self.store = BlockStore()
        # streamed coordinates' chunk-stream accounting, surfaced through
        # accounting() so the cli summary sees
        # work-per-staged-byte next to the byte peaks
        self._stream_snapshots = {}
        for name, coord in coordinates.items():
            snap_fn = getattr(coord, "stream_snapshot", None)
            if getattr(coord, "streamed", False) and callable(snap_fn):
                self._stream_snapshots[name] = snap_fn
            streamed = bool(getattr(coord, "streamed", False))
            block_bytes = (0 if streamed
                           else per_dev(int(coord.device_block_bytes())))
            self.footprints[name] = CoordinateFootprint(
                name=name, block_bytes=block_bytes, streamed=streamed,
                chunk_bytes=(per_dev(int(coord.streaming_buffer_bytes()))
                             if streamed else 0))
            self.store.register(name, evict=coord.evict_device_blocks,
                                block_bytes=block_bytes, streamed=streamed)
        self.resident_block_total = sum(f.block_bytes
                                        for f in self.footprints.values())
        # a streamed coordinate's double buffer is live during ITS update,
        # concurrently with every still-resident coordinate — so the
        # no-eviction peak is blocks + flat + the largest chunk buffer
        # (updates are sequential, so max not sum)
        stream_peak = max((f.chunk_bytes for f in self.footprints.values()
                           if f.streamed), default=0)
        self.evict_inactive = (
            budget_bytes is not None
            and (self.resident_block_total + flat_vector_bytes + stream_peak
                 > budget_bytes)
            and any(not f.streamed for f in self.footprints.values()))
        # accounting: what is resident right now / the worst moment so far
        self._resident: Dict[str, int] = {}
        self.peak_tracked_bytes = 0
        self.evictions = 0
        if self.evict_inactive:
            logger.info(
                "hbm budget %.0f MB%s < resident coordinate blocks %.0f MB "
                "(+%.0f MB flat vectors): rotating residency — inactive "
                "coordinates evict after their update and re-stream on the "
                "next visit", budget_bytes / 1e6,
                (" per device (%d-way data mesh)" % self.data_devices
                 if self.data_devices > 1 else ""),
                self.resident_block_total / 1e6, flat_vector_bytes / 1e6)

    # -- descent-loop hooks ---------------------------------------------------
    def before_update(self, name: str) -> None:
        """Coordinate `name` is about to update: its blocks re-stream on
        first touch — count them resident from here.  An evicted
        coordinate's re-fetch goes through the store (store.fetch site,
        retry discipline, store.* counters)."""
        f = self.footprints[name]
        self.store.touch(name)
        self._resident[name] = (f.chunk_bytes if f.streamed
                                else f.block_bytes)
        current = (sum(self._resident.values()) + self.flat_vector_bytes)
        self.peak_tracked_bytes = max(self.peak_tracked_bytes, current)

    def after_update(self, name: str) -> None:
        """Coordinate `name` finished update+score (+objective): under
        budget pressure its device blocks are dropped NOW through the
        store's eviction entry point; the next visit's lazy accessors
        re-stream them."""
        f = self.footprints[name]
        if f.streamed:
            # chunks are released by the prefetcher as the pass drains;
            # account the double buffer as gone once the update returns
            self._resident.pop(name, None)
            return
        if not self.evict_inactive:
            return
        self.store.evict(name)
        self._resident.pop(name, None)
        self.evictions += 1

    # -- reporting ------------------------------------------------------------
    def accounting(self) -> dict:
        """Byte accounting for training summaries: the
        stand-in for device.memory_stats() where that API is missing."""
        return {
            "budget_bytes": self.budget_bytes,
            "per_device": self.data_devices > 1,
            "data_devices": self.data_devices,
            "flat_vector_bytes": self.flat_vector_bytes,
            "resident_block_bytes": {
                n: f.block_bytes for n, f in self.footprints.items()
                if not f.streamed},
            "streamed_chunk_bytes": {
                n: f.chunk_bytes for n, f in self.footprints.items()
                if f.streamed},
            "resident_block_total": self.resident_block_total,
            "evict_inactive": self.evict_inactive,
            "evictions": self.evictions,
            "peak_tracked_bytes": self.peak_tracked_bytes,
            "under_budget": (self.budget_bytes is None
                             or self.peak_tracked_bytes <= self.budget_bytes),
            "store": self.store.snapshot(),
            "stream": {name: fn()
                       for name, fn in self._stream_snapshots.items()},
        }
