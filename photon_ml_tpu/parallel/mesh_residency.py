"""Mesh-resident coordinate data: pad + shard static arrays over the mesh ONCE.

Before this layer every mesh-path coordinate visit re-padded and re-
`device_put` its ENTIRE batch (fixed-effect objectives through
`shard_objective`, entity blocks through the ad-hoc `_MESH_BLOCK_CACHE` in
parallel/random_effect.py, scoring inputs through `pad_and_shard_rows`) —
steady-state multi-chip training re-transferred the whole dataset every
coordinate-descent visit.  The distributed coordinate descent literature
(PAPERS.md: arXiv 1611.02101; Snap ML, arXiv 1803.06333) gets its scaling
precisely by keeping partitions device-local and moving only coefficients
and residuals; this module is that discipline for the GSPMD mesh path:

  * `MeshResidency` memoizes each coordinate's STATIC arrays (feature
    blocks, labels, masks, weights, normalization contexts) padded to a
    mesh multiple and sharded over the "data" axis, keyed per coordinate
    with explicit per-coordinate invalidation (the HBM residency manager's
    eviction hook).  A warm outer iteration then stages only the per-visit
    operands — residual offsets, x0 — between host and devices.
  * `TransferStats` counts every staged byte, split COLD (static data,
    staged once per residency) vs WARM (per-visit operands), so the
    no-retransfer property is observable: tests/test_mesh_residency.py
    gates "zero cold bytes across warm outer iterations" on it.  Beside
    both it counts HOST bytes, those whose source was a host array.  A
    source that is already a device array of the mesh costs none: on a
    mesh whose data axis is ONE device the sharded layout is the
    single-device one, `device_put` hands back the source's own buffer,
    and a coordinate stages the copies its dataset holds across fits
    (`GameDataset.device_shard`, `.device_vector`) at no transfer.  Only
    where the data axis spans devices does a dense host shard go host ->
    sharded devices, with no full single-device copy on the way.
  * staging runs under the same transient/fatal fault classification as
    the streaming Prefetcher: the `mesh.stage` injection site
    (utils/faults.py) fires before each transfer, transient failures retry
    with jittered exponential backoff, fatal ones propagate.

Keys are tuples — typically ``(coordinate_name, id(coordinate))`` plus an
optional sub-key (an entity bucket's lane start, "latent", "kron") — and
`invalidate(prefix)` drops every entry whose key starts with the prefix:
evicting one coordinate no longer drops every other coordinate's staged
blocks.  (The deprecated `clear_mesh_block_cache` global-flush alias is
RETIRED: invalidation routes through the tiered store's residency
registry.)

This module is a TENANT of the tiered entity store
(photon_ml_tpu/store/): the keyed registry semantics — identity
staleness, bounded FIFO, prefix invalidation — live in
`store.handles.ResidencyRegistry`, and every transfer runs under the
store's shared `with_retries` discipline.  What stays here is the
mesh-specific staging (pad + shard + sharding specs) and the cold/warm
byte split.
"""
from __future__ import annotations

import functools
import random
import threading
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import telemetry
from photon_ml_tpu.parallel.mesh import (
    DATA_AXIS, FEATURE_AXIS, data_sharding, feature_sharding, grid_sharding,
    replicated,
)
from photon_ml_tpu.store.base import with_retries
from photon_ml_tpu.store.handles import ResidencyRegistry
from photon_ml_tpu.utils import locktrace


class MeshStagingError(RuntimeError):
    """A mesh transfer failed after exhausting its retry budget (or hit a
    fatal, non-retryable error).  The message names the residency key; the
    original failure rides as __cause__."""


class TransferStats:
    """Byte accounting for mesh staging: the observable form of the
    no-retransfer property.  COLD bytes are static coordinate data (feature
    blocks, labels, masks) staged once per residency; WARM bytes are the
    per-visit operands (residual offsets, x0) that legitimately move every
    update.  HOST bytes are those of either kind whose SOURCE was a host
    array, the ones that crossed the host link: a stage whose source is
    already a device array of the mesh (a one-device mesh reads the
    dataset's own copy where it lies) counts cold or warm and no host
    bytes.  Thread-safe: scoring may stage from worker threads."""

    def __init__(self):
        self._lock = locktrace.tracked(threading.Lock(),
                                       "TransferStats._lock")
        self.cold_bytes = 0
        self.warm_bytes = 0
        self.host_bytes = 0
        self.cold_stages = 0
        self.warm_stages = 0
        self.invalidations = 0
        self.evictions = 0          # FIFO capacity evictions, not eviction-API
        self.retries = 0

    def note_stage(self, nbytes: int, warm: bool,
                   host_nbytes: int = 0) -> None:
        with self._lock:
            if warm:
                self.warm_bytes += nbytes
                self.warm_stages += 1
            else:
                self.cold_bytes += nbytes
                self.cold_stages += 1
            self.host_bytes += host_nbytes
        # registry mirror: telemetry.snapshot() carries the cold/warm split
        # without reaching into the residency singleton
        kind = "warm" if warm else "cold"
        telemetry.counter(f"mesh.{kind}_bytes").inc(nbytes)
        telemetry.counter(f"mesh.{kind}_stages").inc()
        telemetry.counter("mesh.host_bytes").inc(host_nbytes)

    def note_invalidation(self, count: int = 1) -> None:
        with self._lock:
            self.invalidations += count
        telemetry.counter("mesh.invalidations").inc(count)

    def note_eviction(self) -> None:
        with self._lock:
            self.evictions += 1
        telemetry.counter("mesh.evictions").inc()

    def note_retry(self) -> None:
        with self._lock:
            self.retries += 1
        telemetry.counter("mesh.retries").inc()

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return {"cold_bytes": self.cold_bytes,
                    "warm_bytes": self.warm_bytes,
                    "host_bytes": self.host_bytes,
                    "cold_stages": self.cold_stages,
                    "warm_stages": self.warm_stages,
                    "invalidations": self.invalidations,
                    "evictions": self.evictions,
                    "retries": self.retries}

    @staticmethod
    def delta(before: Dict[str, int], after: Dict[str, int]) -> Dict[str, int]:
        return {k: after[k] - before.get(k, 0) for k in after}


def _canonical_np(a: np.ndarray) -> np.ndarray:
    """Host array in the dtype a plain jnp.asarray transfer would yield
    (float64 -> float32 without x64), so staging from host numpy matches
    the resident path's numerics exactly."""
    want = jax.dtypes.canonicalize_dtype(a.dtype)
    return a if a.dtype == want else np.asarray(a, dtype=want)


def _pad_axis0(a, rem: int, fill):
    """Append `rem` fill-rows.  Host numpy pads on the host (one sharded
    device_put follows — no intermediate unsharded device copy); device
    arrays pad with jnp."""
    if rem == 0:
        return a
    if isinstance(a, np.ndarray):
        out = np.empty((a.shape[0] + rem,) + a.shape[1:], a.dtype)
        out[: a.shape[0]] = a
        out[a.shape[0]:] = fill
        return out
    a = jnp.asarray(a)
    if not getattr(a, "is_fully_addressable", True):
        # cross-process global array (multi-host residual offsets): pad
        # inside a cached jitted program — eager concatenate with a
        # locally-created fill block would mix local and global placements
        return _global_padder(rem, a.ndim, float(fill))(a)
    sh = getattr(a, "sharding", None)
    if (getattr(sh, "mesh", None) is not None
            and sh.mesh.shape.get(FEATURE_AXIS, 1) > 1):
        # concatenate of row-sharded operands miscompiles on feature-wide
        # meshes (see parallel.mesh.concat_rows_safe); pad in the
        # replicated layout — the following _put_leaf reshards anyway
        a = jax.device_put(a, replicated(sh.mesh))
    return jnp.concatenate([a, jnp.full((rem,) + a.shape[1:], fill, a.dtype)])


@functools.lru_cache(maxsize=None)
def _global_padder(rem: int, ndim: int, fill: float):
    pads = ((0, rem),) + ((0, 0),) * (ndim - 1)
    return jax.jit(lambda x: jnp.pad(x, pads, constant_values=fill))


def _put_leaf(mesh, leaf, spec: str):
    if leaf is None:
        return None
    if isinstance(leaf, np.ndarray):
        leaf = _canonical_np(leaf)
    if spec == "replicated" or np.ndim(leaf) == 0:
        sharding = replicated(mesh)
    elif spec == "feature":
        sharding = feature_sharding(mesh, np.ndim(leaf))
    elif spec == "grid":
        sharding = grid_sharding(mesh, np.ndim(leaf))
    else:
        sharding = data_sharding(mesh, np.ndim(leaf))
    from photon_ml_tpu.parallel import multihost
    if multihost.active():
        if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
            # already a global array (residual offsets computed on the
            # mesh): resharding stays device-side — a host round-trip
            # could not even read it back per-process
            if leaf.sharding == sharding:
                return leaf
            return jax.jit(lambda a: a, out_shardings=sharding)(leaf)
        # mesh spans processes: assemble the global array from per-device
        # host slices (jax.make_array_from_single_device_arrays) — each
        # process transfers ONLY the shards its devices own, zero
        # cross-host movement at staging time.  Local jax arrays (padding
        # leftovers, device-derived sources) are fully addressable and
        # read back to host first.
        return multihost.put_global(mesh, np.asarray(leaf), sharding)
    return jax.device_put(leaf, sharding)


def _leaf_nbytes(staged) -> int:
    """Bytes accounted for one staged leaf: global `.nbytes` single-
    process; on a multi-process mesh the PER-PROCESS share (addressable
    shards, deduplicated — parallel/multihost.py), so the cold/warm gates
    stay per-process as each host stages only its 1/P of rows."""
    from photon_ml_tpu.parallel import multihost
    return multihost.local_nbytes(staged)


def _stage_tree(mesh, tree, fill, spec: str):
    """Pad (data-spec leaves, leading axis to a mesh multiple) + shard one
    array or FeatureMatrix pytree.  Returns (staged, nbytes, host_nbytes):
    the last is the part of nbytes whose source leaf was no device array."""
    from photon_ml_tpu.ops import features as fops
    if tree is None:
        return None, 0, 0
    if isinstance(tree, (np.ndarray, jnp.ndarray, jax.Array)) \
            or not hasattr(tree, "tree_flatten"):
        a = tree if hasattr(tree, "shape") else np.asarray(tree)
        from_host = not isinstance(a, jax.Array)
        if spec in ("data", "grid"):
            rem = (-a.shape[0]) % mesh.shape[DATA_AXIS]
            a = _pad_axis0(a, rem, fill)
        staged = _put_leaf(mesh, a, spec)
        nbytes = _leaf_nbytes(staged)
        return staged, nbytes, nbytes if from_host else 0
    # FeatureMatrix pytree (PaddedSparse / KroneckerDesign): pad via the
    # shared pad_rows, then shard every array leaf on its leading axis.
    # Row-shaped pytrees carry a .shape; others (NormalizationContext
    # stats, [d]-shaped) have no row axis to pad — just place the leaves.
    padded = tree
    if hasattr(tree, "shape"):
        if isinstance(tree, fops.PaddedSparse) and mesh.size > 1:
            # the VMEM table gather's flat streams are one device's: rows
            # shard over a mesh as the XLA forms' `[n, k]`
            tree = tree.xla_forms()
        rem = (-tree.shape[0]) % mesh.shape[DATA_AXIS]
        padded = fops.pad_rows(tree, rem)
    staged = jax.tree_util.tree_map(lambda l: _put_leaf(mesh, l, spec),
                                    padded)
    sizes = [(_leaf_nbytes(s), isinstance(l, jax.Array)) for l, s in
             zip(jax.tree_util.tree_leaves(padded),
                 jax.tree_util.tree_leaves(staged))]
    return (staged, sum(b for b, _ in sizes),
            sum(b for b, on_device in sizes if not on_device))


def _mesh_fingerprint(mesh) -> tuple:
    return (tuple(mesh.shape.items()),
            tuple(d.id for d in mesh.devices.flat))


def _as_tuple(key) -> tuple:
    return key if isinstance(key, tuple) else (key,)


class MeshResidency:
    """Keyed registry of padded + sharded STATIC coordinate arrays — a
    tenant of the tiered store's ResidencyRegistry.

    An entry is keyed ``(coordinate key, field, mesh fingerprint)`` and
    pins the SOURCE array it was staged from: a call with a different
    source object (the coordinate rebuilt / re-streamed its blocks)
    re-stages in place — per-coordinate staleness, no global flush.
    Bounded FIFO: an entry pins sharded device memory, so the registry
    caps entries and ages out the oldest."""

    def __init__(self, max_entries: int = 256):
        self.max_entries = max_entries
        self.stats = TransferStats()
        self._registry = ResidencyRegistry(
            max_entries=max_entries,
            on_eviction=self.stats.note_eviction,
            on_invalidation=self.stats.note_invalidation,
            prefix_key=lambda k: k[0])
        self._jitter = random.Random(0)

    # -- staging --------------------------------------------------------------
    def _transfer_with_retry(self, mesh, host_or_build, fill, spec,
                             key, field, warm: bool):
        """One staged transfer under the store's shared transient/fatal
        retry discipline; `host_or_build` is the array or a zero-arg
        callable producing it (deferred so a retry re-reads the source)."""

        def stage():
            with telemetry.span("mesh_stage", key=str(key), field=field,
                                warm=warm):
                src = (host_or_build() if callable(host_or_build)
                       else host_or_build)
                staged, nbytes, host_nbytes = _stage_tree(mesh, src, fill,
                                                          spec)
            self.stats.note_stage(nbytes, warm=warm, host_nbytes=host_nbytes)
            return staged, nbytes

        return with_retries(
            stage, site="mesh.stage", what=f"{key!r}/{field}",
            on_retry=self.stats.note_retry, jitter=self._jitter,
            error_cls=MeshStagingError, key=str(key), field=field)

    def stage_static(self, key, field: str, mesh, source, fill=0.0, *,
                     build: Optional[Callable[[], object]] = None,
                     spec: str = "data"):
        """Memoized pad+shard of one static array (or FeatureMatrix /
        normalization pytree).  `source` anchors identity — a later call
        with the same source object returns the cached sharded copy with
        ZERO transfer; a different source re-stages (and counts an
        invalidation).  `build` optionally derives the actual staged host
        array from the source (e.g. a reshape view), deferred so cache
        hits never build it."""
        if source is None:
            return None
        full_key = (_as_tuple(key), field, _mesh_fingerprint(mesh))
        staged, replacing = self._registry.lookup(full_key, source)
        if staged is not None:
            return staged
        staged, _ = self._transfer_with_retry(
            mesh, build if build is not None else source, fill, spec,
            key, field, warm=False)
        if replacing:
            self.stats.note_invalidation()
        self._registry.commit(full_key, source, staged)
        return staged

    def stage_derived(self, key, field: str, mesh, source,
                      build: Callable[[], object], *,
                      site: str = "admm.stage"):
        """Memoized DEVICE-derived residency: run `build()` (device
        compute, e.g. the ADMM lane's per-shard Gram eigendecomposition)
        once per (key, field, mesh) and pin the result, anchored on the
        staged `source` array's identity — when the source re-stages (the
        coordinate re-built its blocks), the derived entry re-derives and
        counts an invalidation, exactly like stage_static.

        The derivation runs under the store's transient/fatal retry
        discipline at the given fault site (default "admm.stage", the ADMM
        lane's only host-boundary site — the consensus step itself does no
        host-visible I/O); its bytes count COLD, since a derived aggregate
        is static coordinate data that must never re-materialize across
        warm visits."""
        full_key = (_as_tuple(key), field, _mesh_fingerprint(mesh))
        staged, replacing = self._registry.lookup(full_key, source)
        if staged is not None:
            return staged

        def derive():
            with telemetry.span("mesh_stage", key=str(key), field=field,
                                warm=False):
                out = build()
                # surface async device failures inside the retry scope
                jax.block_until_ready(out)
            nbytes = sum(_leaf_nbytes(l)
                         for l in jax.tree_util.tree_leaves(out))
            self.stats.note_stage(nbytes, warm=False)
            return out

        staged = with_retries(
            derive, site=site, what=f"{key!r}/{field}",
            on_retry=self.stats.note_retry, jitter=self._jitter,
            error_cls=MeshStagingError, key=str(key), field=field)
        if replacing:
            self.stats.note_invalidation()
        self._registry.commit(full_key, source, staged)
        return staged

    def stage_update(self, mesh, array, fill=0.0, *, spec: str = "data",
                     key="update", field: str = "operand"):
        """Per-visit operand staging (residual offsets, x0): never
        memoized, counted WARM.  These are the only bytes a steady-state
        mesh iteration should move."""
        if array is None:
            return None
        staged, _ = self._transfer_with_retry(mesh, array, fill, spec,
                                              key, field, warm=True)
        return staged

    # -- invalidation ---------------------------------------------------------
    def invalidate(self, key) -> int:
        """Drop every entry whose coordinate key starts with `key` (all
        fields, all meshes).  The residency manager's per-coordinate
        eviction hook — other coordinates' staged blocks are untouched."""
        return self._registry.invalidate(_as_tuple(key))

    def clear(self) -> int:
        return self._registry.clear()

    def num_entries(self) -> int:
        return self._registry.num_entries()

    def keys(self) -> Tuple[tuple, ...]:
        return self._registry.keys()


# -- process-global default registry ------------------------------------------
# One registry serves every estimator in the process (entries are keyed by
# coordinate identity + mesh, so fits never collide); module-level so the
# descent loop and the CLI summary read one TransferStats.

_DEFAULT: Optional[MeshResidency] = None
_DEFAULT_LOCK = threading.Lock()


def default_residency() -> MeshResidency:
    # double-checked: scoring worker threads and the training loop race
    # the first stage; a bare check-then-act would build TWO registries
    # and split the TransferStats the transfer tests read [PH013]
    global _DEFAULT
    if _DEFAULT is None:
        with _DEFAULT_LOCK:
            if _DEFAULT is None:
                _DEFAULT = MeshResidency()
    return _DEFAULT


def transfer_snapshot() -> Dict[str, int]:
    """Current global transfer counters (monotonic; consumers diff
    snapshots via TransferStats.delta)."""
    return default_residency().stats.snapshot()


def invalidate(key) -> int:
    return default_residency().invalidate(key)


def clear() -> int:
    return default_residency().clear()
