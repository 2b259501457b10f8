"""Dataset -> device-block builders: the shuffle work, done once at prep time.

Rebuild of the reference's per-coordinate dataset machinery:
  - FixedEffectDataSet (photon-api/.../data/FixedEffectDataSet.scala:30-148)
  - RandomEffectDataSet build: group-by-entity, per-entity sample cap with
    weight rescaling, passive data, feature selection
    (photon-api/.../data/RandomEffectDataSet.scala:240-472)
  - LocalDataSet feature filtering (Pearson), local sampling
    (photon-api/.../data/LocalDataSet.scala:36-321)
  - IndexMapProjector: per-entity dense local feature space
    (photon-api/.../projector/IndexMapProjectorRDD.scala:32-208)
  - RandomEffectDataConfiguration / FixedEffectDataConfiguration
    (photon-api/.../data/{RandomEffect,FixedEffect}DataConfiguration.scala)

Where the reference shuffles (groupByKey by REId, MinHeap combineByKey for
the reservoir cap) every time a dataset is built on the cluster, here the
grouping/capping/projection run once on host numpy and emit static device
blocks; the training loop touches only dense arrays after this point.
"""
from __future__ import annotations

import dataclasses
import functools
import weakref
from typing import Dict, List, Optional, Tuple  # noqa: F401

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.data.game_data import GameDataset
from photon_ml_tpu.ops import features as fops
from photon_ml_tpu.parallel.random_effect import EntityBlocks
from photon_ml_tpu.utils.math import ceil_pow2 as _ceil_pow2

_SAFE_LABEL = 0.5  # valid for every loss family; see pad_batch_to_mesh


@dataclasses.dataclass(frozen=True)
class FixedEffectDataConfig:
    """reference: FixedEffectDataConfiguration.scala (featureShardId; the
    minNumPartitions knob is meaningless here — sharding is the mesh's)."""

    feature_shard: str


@dataclasses.dataclass(frozen=True)
class RandomEffectDataConfig:
    """reference: RandomEffectDataConfiguration.scala:42-140.
    `active_data_upper_bound` caps per-entity samples (reservoir-style, with
    weight rescaling); rows beyond the cap become passive data (scored, not
    trained on) when the entity has more than `passive_data_lower_bound`
    rows.  `features_to_samples_ratio` triggers per-entity Pearson feature
    selection.  `projector` in {"index_map", "identity"}."""

    random_effect_type: str
    feature_shard: str
    active_data_upper_bound: Optional[int] = None
    passive_data_lower_bound: Optional[int] = None
    features_to_samples_ratio: Optional[float] = None
    # "index_map" | "identity" | "random_projection:<k>"
    # (reference: ProjectorType.scala — IndexMapProjection, IdentityProjection,
    # RandomProjection(dim))
    projector: str = "index_map"
    seed: int = 7
    # cap on the number of S-buckets: each bucket shape is a separate XLA
    # compile (and launch) of the vmapped per-entity solver.  Under the cap
    # the boundaries are the ones that leave the fewest padded cells
    # (`_bucket_bounds`).  None = one bucket per power-of-two class of the
    # padded sample count, however many classes there are.
    max_buckets: Optional[int] = 4
    # keep the host numpy block arrays alongside the device copies so the
    # coordinate residency manager can EVICT the device blocks between
    # coordinate-descent visits and re-stream them from host (out-of-core
    # mode).  Costs one extra host copy of the blocks; off by default — the
    # resident path then transfers eagerly and frees the host staging
    # arrays exactly as before.
    keep_host_blocks: bool = False


@dataclasses.dataclass
class FixedEffectDataset:
    """Flat [n] arrays for one shard, canonical row order."""

    x: np.ndarray
    labels: np.ndarray
    weights: Optional[np.ndarray]
    offsets: Optional[np.ndarray]
    feature_shard: str

    @staticmethod
    def build(dataset: GameDataset, config: FixedEffectDataConfig) -> "FixedEffectDataset":
        return FixedEffectDataset(
            x=dataset.feature_shards[config.feature_shard],
            labels=dataset.response,
            weights=dataset.weights,
            offsets=dataset.offsets,
            feature_shard=config.feature_shard)


#: the chip's (sublane, lane) tile of a float32 array
_TILE = (8, 128)


def _tiled(entities: int, samples: int) -> Tuple[int, int]:
    """A bucket's `[S, E]` transpose padded to whole tiles: (S, E) rounded
    up to multiples of `_TILE`."""
    return (-(-samples // _TILE[0]) * _TILE[0],
            -(-entities // _TILE[1]) * _TILE[1])


@functools.partial(jax.jit, static_argnames=("dtype", "shapes", "interpret"))
def _gather_flat_offsets(flat, ids, masks, *, dtype, shapes=None,
                         interpret=False):
    """Canonical-order offsets -> every bucket's [Eb, Sb] block layout, ONE
    program a coordinate visit (addScoresToOffsets runs per bucket per
    coordinate update; op-by-op, or a program a bucket, it would be several
    dispatches, and a program a bucket shape to trace and lower).

    Without `shapes`, XLA's element gather a bucket, `flat[safe_ids] *
    mask` (`ids` and `masks` one a bucket).  With `shapes` (the buckets'
    (Eb, Sb)), `ids` is the visit's one index stream
    (`RandomEffectDataset.offsets_stream`), which `fops.vmem_take` reads
    from the offsets held in VMEM, and the blocks are cut from its cells:
    a padded cell reads the zero past the table's end where the mask would
    make `flat[0] * 0`, so the two differ at most in the sign of a zero in
    cells that train nothing.  The stream holds a bucket as the tiles of
    its `[S, E]` transpose, so a block is a slice of whole rows of 128
    cells that the compiler lays out by a bitcast (cut from cells in row
    order, it compiled to 3.6 MB of element shuffles on a TPU v5e)."""
    if shapes is None:
        return tuple((flat[i] * m).astype(dtype) for i, m in zip(ids, masks))
    rows = fops.vmem_take(flat, ids, interpret=interpret).reshape(
        -1, _TILE[1])
    blocks, start = [], 0
    for entities, samples in shapes:
        s_pad, e_pad = _tiled(entities, samples)
        end = start + s_pad * e_pad // _TILE[1]
        tiles = rows[start:end].reshape(s_pad // _TILE[0], e_pad // _TILE[1],
                                        *_TILE)
        blocks.append(tiles.transpose(0, 2, 1, 3).reshape(s_pad, e_pad)
                      .T[:entities, :samples].astype(dtype))
        start = end
    return tuple(blocks)


@dataclasses.dataclass
class EntityBucket:
    """One size-class of entities: lanes [lane_start, lane_start + Eb) of the
    dataset's count-descending lane order, padded to this bucket's own S.

    SURVEY §7 "Hard parts" — bucketed batches: one hot entity must not pad
    every block, so the count-descending lanes are cut into at most
    `max_buckets` contiguous runs, at the boundaries that leave the fewest
    padded cells, and each run is padded only to its own largest count,
    rounded up to the sample granule the chip's layout pads to anyway (the
    reference never faces this because its per-entity data is ragged RDD
    rows).

    Device residency: `blocks` is a lazily materialized device copy.  In the
    default (resident) build the device copy is created eagerly at build
    time and `host_blocks` is None — steady state identical to the
    pre-out-of-core code.  With keep_host_blocks the numpy originals stay in
    `host_blocks`, `evict()` drops the device copy between coordinate-
    descent visits, and the next `blocks` access re-streams it — the
    re-stream source of the HBM residency budget (game/residency.py)."""

    lane_start: int
    row_ids: np.ndarray             # [Eb, Sb] canonical row ids, -1 = pad
    host_blocks: Optional[EntityBlocks] = None    # numpy leaves (re-stream src)
    _blocks: Optional[EntityBlocks] = dataclasses.field(default=None,
                                                        repr=False,
                                                        compare=False)
    _safe_ids_dev: object = dataclasses.field(default=None, repr=False,
                                              compare=False)

    @property
    def num_entities(self) -> int:
        return self.row_ids.shape[0]

    @property
    def samples_per_entity(self) -> int:
        return self.row_ids.shape[1]

    @property
    def dim(self) -> int:
        src = self._blocks if self._blocks is not None else self.host_blocks
        return src.x.shape[2]

    @property
    def block_dtype(self):
        """Dtype the DEVICE blocks carry (host staging arrays may be wider:
        float64 host -> float32 device under the default jax config)."""
        if self._blocks is not None:
            return self._blocks.x.dtype
        return jnp.dtype(jax.dtypes.canonicalize_dtype(
            self.host_blocks.x.dtype))

    @property
    def blocks(self) -> EntityBlocks:
        """Device EntityBlocks, transferred on first access (or re-streamed
        after an evict())."""
        if self._blocks is None:
            h = self.host_blocks
            if h is None:
                raise ValueError("bucket was built without host blocks and "
                                 "its device copy is gone; rebuild the "
                                 "random-effect dataset")
            self._blocks = EntityBlocks(
                x=jnp.asarray(h.x), labels=jnp.asarray(h.labels),
                mask=jnp.asarray(h.mask),
                weights=None if h.weights is None else jnp.asarray(h.weights),
                offsets=None if h.offsets is None else jnp.asarray(h.offsets))
        return self._blocks

    @property
    def is_resident(self) -> bool:
        return self._blocks is not None

    def evict(self) -> None:
        """Drop the device copy (requires host_blocks to re-stream)."""
        if self.host_blocks is None:
            return  # nothing to re-stream from: keep the device copy
        self._blocks = None
        self._safe_ids_dev = None

    def device_bytes(self) -> int:
        """Bytes this bucket holds (or would hold) on device."""
        src = self._blocks if self._blocks is not None else self.host_blocks
        if src is None:
            return 0
        total = 0
        for leaf in (src.x, src.labels, src.mask, src.weights, src.offsets):
            if leaf is None:
                continue
            itemsize = np.dtype(
                jax.dtypes.canonicalize_dtype(leaf.dtype)).itemsize
            total += int(np.prod(leaf.shape)) * itemsize
        return total

    def safe_ids_dev(self) -> jnp.ndarray:
        """Device copy of clamped row ids, transferred once per bucket
        (XLA's form of the offsets gather, and the vectorized sweep)."""
        if self._safe_ids_dev is None:
            self._safe_ids_dev = jnp.asarray(
                np.maximum(self.row_ids, 0).astype(np.int32))
        return self._safe_ids_dev


@dataclasses.dataclass
class RandomEffectDataset:
    """Per-entity training blocks + the index plumbing to score flat rows.

    reference: RandomEffectDataSet (activeData + uniqueId->REId map +
    passiveData) — here the "joins" are materialized index arrays:
      - entity_position[v]: vocab entity v -> block lane (-1 if unseen)
      - active_row_ids[e, s]: block cell -> canonical row id (-1 pad), which
        also realizes addScoresToOffsets as one gather

    Entities live in count-descending lane order, partitioned into S-buckets
    (`buckets`: contiguous runs of lanes, boundaries by `_bucket_bounds`, S a
    multiple of the sample granule); `blocks` / `active_row_ids` are single-S
    compatibility views padded to the global max (materialized lazily — the
    plain random-effect solve path iterates buckets and never builds them).
    """

    config: RandomEffectDataConfig
    buckets: list  # List[EntityBucket], contiguous lanes, ascending start
    entity_ids: np.ndarray          # [E] vocab indices, block lane order
    entity_position: np.ndarray     # [V] vocab index -> block lane or -1
    projection: Optional[np.ndarray]  # [E, d_local] global col ids, -1 pad
    global_dim: int
    num_active: int
    num_passive: int
    # dense Gaussian random-projection matrix [d_local, d_global], shared by
    # all entities (reference: ProjectionMatrixBroadcast) — exclusive with
    # the per-entity index `projection`
    projection_matrix: Optional[np.ndarray] = None
    # canonical rows capped out of entities whose LEFTOVER count is at/below
    # passive_data_lower_bound: DISCARDED, not scored (reference:
    # RandomEffectDataSet.scala:399-446 keeps passive data only for entities
    # whose passive count exceeds the bound) — flat_entity_lanes maps them to
    # lane -1 so they contribute score 0, the missing-score default.
    discarded_rows: Optional[np.ndarray] = None  # [k] canonical row ids
    # what the build did with the rows, counted once at the build: rows
    # that train (`active_rows`), are only scored (`passive_rows`) or are
    # dropped (`discarded_rows`), entities the cap cut (`capped_entities`,
    # their weights are rescaled), `cells` of the padded blocks and those
    # that hold no row (`padded_cells`), and `buckets` as [[entities,
    # samples, real rows]] in lane order
    build_counts: Dict[str, object] = dataclasses.field(default_factory=dict)
    # [E] the cap's weight rescale by lane, count / cap for an entity the
    # reservoir cut and 1 for any other: with `row_ids` it gives back every
    # cell's weight without the blocks (`flat_active_weights`)
    lane_weight_scale: Optional[np.ndarray] = None
    _flat_weights_dev: object = dataclasses.field(default=None, repr=False,
                                                  compare=False)
    _flat_lanes_dev: object = dataclasses.field(default=None, repr=False,
                                                compare=False)
    _offsets_stream_dev: object = dataclasses.field(default=None, repr=False,
                                                    compare=False)
    _global_blocks: Optional[EntityBlocks] = dataclasses.field(
        default=None, repr=False, compare=False)
    _global_row_ids: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def num_entities(self) -> int:
        return len(self.entity_ids)

    @property
    def local_dim(self) -> int:
        return self.buckets[0].dim

    @property
    def dtype(self):
        return self.buckets[0].block_dtype

    @property
    def max_samples(self) -> int:
        return max(b.samples_per_entity for b in self.buckets)

    @property
    def active_row_ids(self) -> np.ndarray:
        """[E, S_max] single-S view (lazily materialized)."""
        if self._global_row_ids is None:
            S = self.max_samples
            parts = [np.pad(b.row_ids, ((0, 0), (0, S - b.row_ids.shape[1])),
                            constant_values=-1) for b in self.buckets]
            self._global_row_ids = np.concatenate(parts, axis=0)
        return self._global_row_ids

    @property
    def blocks(self) -> EntityBlocks:
        """Single-S EntityBlocks view over all lanes (lazily materialized;
        `game/anchored.py` solves on it)."""
        if self._global_blocks is None:
            S = self.max_samples
            def cat(get, fill):
                if any(get(b.blocks) is None for b in self.buckets):
                    return None
                return jnp.concatenate([
                    jnp.pad(get(b.blocks),
                            ((0, 0), (0, S - b.blocks.samples_per_entity))
                            + ((0, 0),) * (get(b.blocks).ndim - 2),
                            constant_values=fill)
                    for b in self.buckets], axis=0)
            self._global_blocks = EntityBlocks(
                x=cat(lambda b: b.x, 0.0), labels=cat(lambda b: b.labels, _SAFE_LABEL),
                mask=cat(lambda b: b.mask, 0.0), weights=cat(lambda b: b.weights, 0.0),
                offsets=cat(lambda b: b.offsets, 0.0))
        return self._global_blocks

    def flat_active_weights(self, dataset: GameDataset) -> jnp.ndarray:
        """[n] device vector, canonical row order: the weight a row trains
        its entity at (its own weight times the cap's rescale, as its block
        cell has it), 0 for a row that does not train (passive, discarded,
        of no entity).  The flat view of the blocks' weights, for a consumer
        that reads the rows where they lie (the factored refit's
        `ProjectionRows`).  Made once a dataset, from the host's row ids."""
        if self._flat_weights_dev is None:
            flat = np.zeros(dataset.num_rows, np.dtype(self.dtype))
            for b in self.buckets:
                lane, slot = np.nonzero(b.row_ids >= 0)
                rows = b.row_ids[lane, slot]
                flat[rows] = self.lane_weight_scale[b.lane_start + lane]
                if dataset.weights is not None:
                    flat[rows] *= np.asarray(dataset.weights)[rows]
            self._flat_weights_dev = jnp.asarray(flat)
        return self._flat_weights_dev

    def flat_train_lanes(self, dataset: GameDataset) -> jnp.ndarray:
        """[n] device vector, canonical row order: the block lane of each
        row of `dataset`, the one this was built from (-1 for a discarded
        row or one of no entity): `flat_entity_lanes` of its own entity
        column.  It depends on the build alone, so it is made at its first
        use and kept with the memoised build: every coordinate of every fit
        over one (dataset, config) reads ONE map, and it stays resident
        under a budget (flat-vector class, like the labels)."""
        if self._flat_lanes_dev is None:
            self._flat_lanes_dev = jnp.asarray(self.flat_entity_lanes(
                dataset.entity_indices[self.config.random_effect_type]))
        return self._flat_lanes_dev

    def vmem_offsets(self, num_rows: int, one_device: bool) -> bool:
        """Whether a coordinate on this build gathers its offsets from a
        table held in VMEM (`fops.vmem_take`): where its arrays land on a
        TPU, on `one_device`, the blocks are float32, the `num_rows` flat
        offsets fit `fops.VMEM_TABLE_BYTES`, and the blocks stay resident
        (no host copies kept for an HBM budget's evictions).  Anything else
        runs XLA's element gather.  Decided once, at the coordinate's
        build, from what it can see; no option chooses it."""
        return (one_device and not self.config.keep_host_blocks
                and fops._on_tpu() and fops.vmem_take_fits(num_rows,
                                                           self.dtype))

    def offsets_stream(self, num_rows: int) -> jnp.ndarray:
        """[L] int32 device vector, the index stream `fops.vmem_take` reads
        for the offsets gather: bucket after bucket in lane order, the
        canonical row id of each cell, as the `[8, 128]` tiles of the
        bucket's `[S, E]` transpose padded to whole tiles (`_tiled`), tile
        rows in order; `num_rows` (the zero past the table's end) for a
        padded cell, a tile's padding and the tail to whole grid steps
        (`fops.vmem_take_cells`).  It depends on the build alone: made at
        its first use and kept with the memoised build, as
        `flat_train_lanes` is."""
        if self._offsets_stream_dev is None:
            parts = []
            for b in self.buckets:
                s_pad, e_pad = _tiled(b.num_entities, b.samples_per_entity)
                ids = np.full((e_pad, s_pad), num_rows, np.int32)
                ids[:b.num_entities, :b.samples_per_entity] = np.where(
                    b.row_ids >= 0, b.row_ids, num_rows)
                parts.append(ids.T.reshape(
                    s_pad // _TILE[0], _TILE[0], e_pad // _TILE[1], _TILE[1])
                    .transpose(0, 2, 1, 3).reshape(-1))
            cells = sum(len(p) for p in parts)
            parts.append(np.full(fops.vmem_take_cells(cells) - cells,
                                 num_rows, np.int32))
            self._offsets_stream_dev = jnp.asarray(np.concatenate(parts))
        return self._offsets_stream_dev

    def blocks_with_offsets(self, flat_offsets,
                            vmem: bool = False) -> List[EntityBlocks]:
        """Every bucket's device blocks with its cells' offsets taken from
        `flat_offsets` ([n], canonical row order; 0 in a padded cell), by
        ONE `_gather_flat_offsets` program: `fops.vmem_take` over
        `offsets_stream` where `vmem` (the coordinate's `vmem_offsets`)
        holds and the vector is float32 on one device, else XLA's element
        gather a bucket."""
        flat = jnp.asarray(flat_offsets)
        blocks = [b.blocks for b in self.buckets]
        dtype = jnp.dtype(blocks[0].x.dtype).name
        if (vmem and flat.dtype == jnp.float32
                and len(flat.sharding.device_set) == 1):
            offsets = _gather_flat_offsets(
                flat, self.offsets_stream(flat.shape[0]), None, dtype=dtype,
                shapes=tuple((b.num_entities, b.samples_per_entity)
                             for b in self.buckets),
                interpret=not fops._on_tpu())
        else:
            offsets = _gather_flat_offsets(
                flat, tuple(b.safe_ids_dev() for b in self.buckets),
                tuple(blk.mask for blk in blocks), dtype=dtype)
        return [blk.with_offsets(off) for blk, off in zip(blocks, offsets)]

    def scatter_to_global(self, local_coefficients) -> jnp.ndarray:
        """[E, d_local] local-space coefficients -> [E, d_global]
        (reference: IndexMapProjector.projectCoefficients /
        ProjectionMatrix.projectCoefficients = P^T c)."""
        if self.projection_matrix is not None:
            return jnp.asarray(local_coefficients) @ jnp.asarray(self.projection_matrix)
        from photon_ml_tpu.parallel.random_effect import scatter_local_to_global
        return scatter_local_to_global(jnp.asarray(local_coefficients),
                                       self.projection, self.global_dim)

    def evict_device_blocks(self) -> None:
        """Drop every device block copy (buckets + the single-S views).
        Requires keep_host_blocks on the build config; buckets without a
        host source keep their device copy (evict is then a no-op for
        them).  Next access re-streams lazily — the residency manager's
        between-visits rotation (game/residency.py)."""
        for b in self.buckets:
            b.evict()
        self._global_blocks = None       # (_global_row_ids is host: kept)
        self._flat_weights_dev = None

    def device_bytes(self) -> int:
        """Device bytes of all bucket blocks (+ the single-S view when it
        has been materialized)."""
        total = sum(b.device_bytes() for b in self.buckets)
        g = self._global_blocks
        if g is not None:
            total += sum(int(leaf.nbytes) for leaf in
                         (g.x, g.labels, g.mask, g.weights, g.offsets)
                         if leaf is not None)
        return total

    def flat_entity_lanes(self, entity_index: np.ndarray) -> np.ndarray:
        """Map a canonical-order entity-index column to block lanes.
        Discarded rows (capped out of below-bound entities) get lane -1."""
        idx = np.asarray(entity_index)
        lanes = np.full_like(idx, -1)
        valid = idx >= 0
        lanes[valid] = self.entity_position[idx[valid]]
        if self.discarded_rows is not None and len(self.discarded_rows):
            lanes[self.discarded_rows] = -1
        return lanes


# (dataset -> {(config, dtype) -> built blocks}) memo: grid sweeps and
# hyperparameter tuning refit the same data under many lambdas — the blocks
# depend only on (data, config, seed), never on the lambdas being searched
_BUILD_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def build_random_effect_dataset(
    dataset: GameDataset,
    config: RandomEffectDataConfig,
    dtype=np.float64,
) -> RandomEffectDataset:
    """Group-by-entity -> cap -> select features -> project -> pad.
    Memoized per (dataset, config, dtype) — see _BUILD_CACHE.

    reference call path: RandomEffectDataSet.apply (scala:240-277) +
    featureSelectionOnActiveData (scala:457-471) +
    RandomEffectDataSetInProjectedSpace.buildWithProjectorType."""
    per_ds = _BUILD_CACHE.setdefault(dataset, {})
    key = (config, np.dtype(dtype).name)
    if key in per_ds:
        return per_ds[key]
    built = _build_random_effect_dataset(dataset, config, dtype)
    per_ds[key] = built
    return built


def _is_np_dense(x) -> bool:
    try:
        import scipy.sparse as sp
        return not sp.issparse(x)
    except ImportError:
        return True


#: rows the chip's layout pads a bucket's sample axis to.  The v5e compile of
#: `jit_re_bucket_solve` lays S on sublanes (`f32[E,S]{0,1:T(8,128)}`,
#: `f32[E,S,d]{0,1,2:T(8,128)}`: lanes minor) unless S is a multiple of 128,
#: when it lies on lanes and pads nothing; either way a multiple of 8 costs
#: what it says and anything else costs the next one.  (The line search's
#: trial fusion reads S on lanes at every S; boundaries that counted that
#: measured no faster on the chip: PERF.md, PR 30.)
_SAMPLE_GRANULE = 8
#: bucket boundaries are searched among the distinct padded counts, thinned
#: to one per factor of (1 + this): at most this share of cells above the
#: minimum, and a candidate list whose length grows with the logarithm of
#: the largest count.  Multiples of the granule up to 64 granules (512 rows)
#: differ by more than the factor, so none of them is thinned away.
_BOUNDARY_LOSS = 1.0 / 64.0


def _padded_samples(counts: np.ndarray) -> np.ndarray:
    """Per-entity row counts rounded up to the sample granule."""
    return -(-np.asarray(counts, np.int64) // _SAMPLE_GRANULE) * _SAMPLE_GRANULE


def _bucket_bounds(samples_lane: np.ndarray,
                   max_buckets: Optional[int]) -> np.ndarray:
    """Lane boundaries `[0, ..., E]` of the S-buckets, given the padded
    sample counts in lane (descending) order.

    Under a cap it is the partition of the lanes into at most `max_buckets`
    contiguous runs that minimises the padded cells, sum of entities x S
    with S the run's first (largest) padded count: what the solves, the
    offsets gather and the staging stream is proportional to cells, and the
    cap bounds the compiled bucket shapes, so the boundaries go where the
    counts' mass is.  A run only ever starts at the first lane of a distinct
    padded count (starting later pads that count's lanes to the run before),
    so it is a dynamic programme over those candidates, no loop over
    entities.  Without a cap (`None`): one bucket per power-of-two class of
    the padded count."""
    E = len(samples_lane)
    if max_buckets is None or max_buckets < 1:
        starts = np.flatnonzero(np.diff(_ceil_pow2(samples_lane))) + 1
        return np.concatenate([[0], starts, [E]])
    first = np.concatenate([[0], np.flatnonzero(np.diff(samples_lane)) + 1])
    # the largest count of each geometric bin stands for the bin
    geo = np.floor(np.log(samples_lane[first]) / np.log1p(_BOUNDARY_LOSS))
    first = first[np.concatenate([[True], np.diff(geo) != 0])]
    m = len(first)
    s_of = samples_lane[first]
    end = np.append(first[1:], E)
    # best[j]: fewest cells of lanes [0, end[j]) in at most k runs.  Row 0 of
    # `options` keeps the k - 1 answer (ties go to fewer buckets); row i >= 1
    # starts the last run at candidate i
    best = s_of[0] * end
    last_run = np.where(np.arange(m)[:, None] <= np.arange(m),
                        s_of[:, None] * (end - first[:, None]),
                        np.iinfo(np.int64).max // 2)[1:]
    choices = []
    for _ in range(min(max_buckets, m) - 1):
        options = np.concatenate([best[None, :],
                                  best[:-1, None] + last_run])
        choices.append(options.argmin(axis=0))
        best = options.min(axis=0)
    starts, j = [], m - 1
    for choice in reversed(choices):
        i = int(choice[j])
        if i:
            starts.append(first[i])
            j = i - 1
    return np.concatenate([[0], starts[::-1], [E]]).astype(np.int64)


def _build_random_effect_dataset(
    dataset: GameDataset,
    config: RandomEffectDataConfig,
    dtype,
) -> RandomEffectDataset:
    """Fully vectorized build: one lexsort replaces groupByKey, the per-entity
    reservoir cap is a segmented random-key rank cut, the index-map projector
    is segment reductions over the group-sorted rows, and entities are packed
    into cell-minimal S-buckets in count-descending lane order.  No O(E)
    Python loops anywhere (VERDICT r2 item #2; reference:
    RandomEffectDataSet.scala:240-472 + MinHeapWithFixedCapacity)."""
    re_type = config.random_effect_type
    x_flat = np.asarray(dataset.feature_shards[config.feature_shard], dtype=dtype)
    y_flat = np.asarray(dataset.response, dtype=dtype)
    w_flat = None if dataset.weights is None else np.asarray(dataset.weights, dtype)
    o_flat = None if dataset.offsets is None else np.asarray(dataset.offsets, dtype)
    ent = np.asarray(dataset.entity_indices[re_type])
    n, d_global = x_flat.shape
    rng = np.random.default_rng(config.seed)

    present = ent >= 0
    uniq = np.unique(ent[present])
    E = len(uniq)
    if E == 0:
        raise ValueError(f"no rows carry entity ids for {re_type!r}")

    # group rows per entity (one argsort — the groupByKey replacement);
    # within an entity, canonical row order is preserved (stable sort)
    uniq_rank_of = np.full(dataset.num_entities(re_type), -1, dtype=np.int64)
    uniq_rank_of[uniq] = np.arange(E)
    grp_all = uniq_rank_of[ent[present]]
    order = np.argsort(grp_all, kind="stable")
    rows_sorted = np.flatnonzero(present)[order]     # canonical ids, grouped
    grp = grp_all[order]                             # uniq-rank per sorted row
    counts = np.bincount(grp, minlength=E)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])

    # --- reservoir cap: segmented random-key rank cut --------------------
    cap = config.active_data_upper_bound
    weight_scale = np.ones(E)
    num_passive = num_capped = 0
    discarded_rows = np.zeros((0,), dtype=np.int64)
    if cap is not None and (counts > cap).any():
        keys = rng.random(len(rows_sorted))
        rand_order = np.lexsort((keys, grp))
        rank_in_entity = np.arange(len(rows_sorted)) - np.repeat(starts, counts)
        keep = np.empty(len(rows_sorted), dtype=bool)
        keep[rand_order] = rank_in_entity < cap   # rank is position in
        # rand_order space: row rand_order[i] has within-entity random rank
        # rank_in_entity[i] because groups stay contiguous under lexsort
        over = counts > cap
        num_capped = int(over.sum())
        # weight rescale so the capped sample represents the full count
        # (reference: MinHeapWithFixedCapacity cumCount/size rescale,
        # RandomEffectDataSet.scala:325-388)
        weight_scale[over] = counts[over] / cap
        leftover = counts - np.minimum(counts, cap)
        lower = config.passive_data_lower_bound
        # leftovers of entities above the passive lower bound are passive
        # (scored, not trained on); at/below the bound they are discarded
        # (reference: RandomEffectDataSet.scala:399-446)
        passive_entities = (np.ones(E, dtype=bool) if lower is None
                            else leftover > lower)
        num_passive = int(leftover[passive_entities & over].sum())
        drop_mask = ~keep & ~passive_entities[grp]
        discarded_rows = rows_sorted[drop_mask]
        rows_sorted, grp = rows_sorted[keep], grp[keep]
        counts = np.bincount(grp, minlength=E)
        starts = np.concatenate([[0], np.cumsum(counts)[:-1]])

    # --- lane order: count-descending, then cell-minimal S-buckets -------
    perm = np.argsort(-counts, kind="stable")        # lane -> uniq rank
    lane_of = np.empty(E, dtype=np.int64)
    lane_of[perm] = np.arange(E)                     # uniq rank -> lane
    counts_lane = counts[perm]
    entity_ids = uniq[perm]
    entity_position = np.full(dataset.num_entities(re_type), -1, dtype=np.int64)
    entity_position[entity_ids] = np.arange(E)

    samples_lane = _padded_samples(counts_lane)
    bucket_bounds = _bucket_bounds(samples_lane, config.max_buckets)

    # kept rows in (lane, canonical-row) order; per-lane slot index
    lane_rows = lane_of[grp]
    ord_lane = np.lexsort((rows_sorted, lane_rows))
    row_ids_l = rows_sorted[ord_lane]
    lane_l = lane_rows[ord_lane]
    lane_starts = np.concatenate([[0], np.cumsum(counts_lane)[:-1]])
    slot_l = np.arange(len(row_ids_l)) - np.repeat(lane_starts, counts_lane)

    # --- per-entity feature projection (index-map projector) --------------
    projection = None
    proj_matrix = None
    if config.projector == "index_map":
        # observed-column mask per entity: segmented any over kept rows
        # (uniq-rank order; reordered to lanes below).  Every entity keeps
        # >= 1 row after capping, so reduceat segments are never empty.
        ind = (x_flat[rows_sorted] != 0)
        obs = np.logical_or.reduceat(ind, starts)
        ratio = config.features_to_samples_ratio
        intercept_col = d_global - 1  # intercept-last convention (IndexMap)
        selected = obs
        if ratio is not None:
            selected = _pearson_select_segmented(
                x_flat, y_flat, rows_sorted, starts, counts, obs, ratio,
                intercept_col, w_flat)
        # ragged column lists -> [E, d_local] padded index array, columns
        # ascending per entity (np.nonzero yields row-major order)
        sel_lane = selected[perm]
        e_idx, col_idx = np.nonzero(sel_lane)
        per_entity = np.bincount(e_idx, minlength=E)
        d_local = int(per_entity.max()) if len(e_idx) else 1
        pos = np.arange(len(col_idx)) - np.repeat(
            np.concatenate([[0], np.cumsum(per_entity)[:-1]]), per_entity)
        projection = np.full((E, max(d_local, 1)), -1, dtype=np.int64)
        projection[e_idx, pos] = col_idx
    elif config.projector.startswith("random_projection:"):
        # Gaussian random projection shared across entities (reference:
        # ProjectionMatrixBroadcast.buildRandomProjectionBroadcastProjector +
        # ProjectionMatrix.buildGaussianRandomProjectionMatrix, scala:95-125);
        # the intercept column survives projection via the extra selector row
        k = int(config.projector.split(":", 1)[1])
        from photon_ml_tpu.parallel.factored import gaussian_projection_matrix
        proj_matrix = np.asarray(gaussian_projection_matrix(
            k, d_global, keep_intercept=True, seed=config.seed), dtype=dtype)
    elif config.projector != "identity":
        raise ValueError(f"unknown projector {config.projector!r} (expected "
                         "'index_map', 'identity', or 'random_projection:<k>')")

    # --- assemble buckets -------------------------------------------------
    # blocks assemble on the host and transfer asynchronously (jnp.asarray
    # starts the DMA immediately).  A device-side gather from the flat
    # shard would move about half the bytes at the cost of 8 more gather
    # programs to compile and load per process; which wins on an attached
    # chip is not measured (ROADMAP S4).
    if not _is_np_dense(dataset.feature_shards[config.feature_shard]):
        raise TypeError(
            f"random-effect shard {config.feature_shard!r} must be a dense "
            "array (sparse per-entity shards would gather ragged columns); "
            "project or densify it at ingest")
    buckets = []
    num_active = len(row_ids_l)
    in_bucket_of_lane = np.searchsorted(bucket_bounds, lane_l, side="right") - 1
    # pad-row/pad-column trick: one zero row (and, for the index-map
    # projector, one zero column) appended to the flat arrays lets padding
    # ids gather ZEROS directly — no [E, S, d]-sized mask multiplies, which
    # dominated this build at MovieLens-20M scale (measured ~40% of 12s)
    d_pad = d_global + (1 if projection is not None else 0)
    x_pad = np.zeros((n + 1, d_pad), x_flat.dtype)  # one copy, final shape
    x_pad[:n, :d_global] = x_flat
    y_pad = np.concatenate([y_flat, [_SAFE_LABEL]]).astype(dtype)
    w_pad = (None if w_flat is None
             else np.concatenate([w_flat, [0.0]]).astype(dtype))
    o_pad = (None if o_flat is None
             else np.concatenate([o_flat, [0.0]]).astype(dtype))
    for b in range(len(bucket_bounds) - 1):
        lb, ub = int(bucket_bounds[b]), int(bucket_bounds[b + 1])
        Eb = ub - lb
        Sb = int(samples_lane[lb])        # descending: the bucket's largest
        sel = in_bucket_of_lane == b
        r_ids = np.full((Eb, Sb), -1, dtype=np.int64)
        r_ids[lane_l[sel] - lb, slot_l[sel]] = row_ids_l[sel]
        mask = (r_ids >= 0).astype(dtype)
        gat = np.where(r_ids >= 0, r_ids, n)  # pad cell -> zero row

        if projection is not None:
            cols = projection[lb:ub]
            gcols = np.where(cols >= 0, cols, x_flat.shape[1])  # -> zero col
            xb = x_pad[gat[:, :, None], gcols[:, None, :]]
        elif proj_matrix is not None:
            xb = np.einsum("esd,kd->esk", x_pad[gat], proj_matrix)
        else:
            xb = x_pad[gat]

        labels = y_pad[gat]
        # both the mask and gathered weights are already 0 at padding cells
        weights = ((w_pad[gat] if w_pad is not None else mask)
                   * weight_scale[perm[lb:ub], None])
        offsets = None if o_pad is None else o_pad[gat]
        host = EntityBlocks(x=xb, labels=labels, mask=mask, weights=weights,
                            offsets=offsets)
        if config.keep_host_blocks:
            # out-of-core build: the numpy blocks ARE the source of truth;
            # device copies materialize lazily and can be evicted/re-streamed
            buckets.append(EntityBucket(lane_start=lb, row_ids=r_ids,
                                        host_blocks=host))
        else:
            # resident build: transfer eagerly (jnp.asarray starts the DMA
            # immediately) and let the numpy staging arrays free
            buckets.append(EntityBucket(
                lane_start=lb, row_ids=r_ids, host_blocks=None,
                _blocks=EntityBlocks(
                    x=jnp.asarray(xb), labels=jnp.asarray(labels),
                    mask=jnp.asarray(mask), weights=jnp.asarray(weights),
                    offsets=None if offsets is None
                    else jnp.asarray(offsets))))

    shapes = [[b.num_entities, b.samples_per_entity,
               int((b.row_ids >= 0).sum())] for b in buckets]
    cells = sum(e * s for e, s, _ in shapes)
    return RandomEffectDataset(
        config=config, buckets=buckets, entity_ids=entity_ids,
        entity_position=entity_position,
        projection=projection, global_dim=d_global,
        num_active=num_active, num_passive=num_passive,
        discarded_rows=discarded_rows, projection_matrix=proj_matrix,
        lane_weight_scale=weight_scale[perm],
        build_counts={
            "entities": E, "active_rows": num_active,
            "passive_rows": num_passive,
            "discarded_rows": len(discarded_rows),
            "capped_entities": num_capped, "cells": cells,
            "padded_cells": cells - num_active, "buckets": shapes})


def _pearson_select_segmented(
    x_flat: np.ndarray,
    y_flat: np.ndarray,
    rows_sorted: np.ndarray,
    starts: np.ndarray,
    counts: np.ndarray,
    obs: np.ndarray,
    ratio: float,
    intercept_col: int,
    w_flat: Optional[np.ndarray],
) -> np.ndarray:
    """Per-entity Pearson feature selection, all entities at once.

    For entities whose observed-column count exceeds ratio * num_samples,
    keep the ceil(ratio * num_samples) columns with the largest |corr(x, y)|
    (the intercept always survives).  reference: LocalDataSet
    .filterFeaturesByPearsonCorrelationScore (scala:135, 221-288).
    Segment sums give per-entity moments; one argsort along the column axis
    ranks every entity's columns simultaneously.
    """
    del w_flat  # reference Pearson is unweighted
    E, d = obs.shape
    xs = x_flat[rows_sorted]
    ys = y_flat[rows_sorted]
    ne = np.maximum(counts, 1).astype(np.float64)[:, None]
    sum_x = np.add.reduceat(xs, starts, axis=0)
    sum_x2 = np.add.reduceat(xs * xs, starts, axis=0)
    sum_xy = np.add.reduceat(xs * ys[:, None], starts, axis=0)
    sum_y = np.add.reduceat(ys, starts)[:, None]
    sum_y2 = np.add.reduceat(ys * ys, starts)[:, None]
    cov = sum_xy - sum_x * sum_y / ne
    var_x = np.maximum(sum_x2 - sum_x * sum_x / ne, 0.0)
    var_y = np.maximum(sum_y2 - sum_y * sum_y / ne, 0.0)
    denom = np.sqrt(var_x * var_y)
    corr = np.where(denom > 0, np.abs(cov) / np.where(denom > 0, denom, 1.0), 0.0)

    target = np.ceil(ratio * np.maximum(counts, 1)).astype(np.int64)
    needs = obs.sum(axis=1) > ratio * np.maximum(counts, 1)
    has_int = obs[:, intercept_col]
    # rank candidate (observed, non-intercept) columns by -corr, stable
    score = np.where(obs, corr, -np.inf)
    score[:, intercept_col] = -np.inf
    col_order = np.argsort(-score, axis=1, kind="stable")
    ranks = np.empty_like(col_order)
    np.put_along_axis(ranks, col_order, np.arange(d)[None, :], axis=1)
    keep_n = np.maximum(target - has_int.astype(np.int64), 1)
    chosen = obs & (ranks < keep_n[:, None])
    chosen[:, intercept_col] = has_int
    return np.where(needs[:, None], chosen, obs)
