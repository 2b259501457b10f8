"""GAME dataset: struct-of-arrays with a fixed canonical row order.

Rebuild of the reference's data containers:
  - GameDatum (photon-lib/.../data/GameDatum.scala:38-70): per-row
    (response, offset, weight, per-shard features, id tags)
  - GameConverters (photon-api/.../data/GameConverters.scala:29-171):
    DataFrame -> RDD[(uid, GameDatum)] with monotonically_increasing_id
  - FixedEffectDataSet (photon-api/.../data/FixedEffectDataSet.scala:30-148)
  - InputColumnsNames (photon-api/.../data/InputColumnsNames.scala)

Key TPU design decision (SURVEY §7 "Score bookkeeping"): the uid IS the row
position.  Every coordinate keeps its scores as a dense [n] device array in
this canonical order, so CoordinateDescent's add/subtract-scores joins
(reference: DataScores +/- via full outer joins, CoordinateDataScores
.scala:38-61) become elementwise array ops.  Entity membership per random
effect type is materialized once at ingest as an int index column
(`entity_index[re_type][row]`), which turns every keyBy(REId) shuffle of the
reference into a static gather.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from photon_ml_tpu.data.index_map import IndexMap


def _is_sparse(x) -> bool:
    try:
        import scipy.sparse as sp
        return sp.issparse(x)
    except ImportError:  # pragma: no cover
        return False


@dataclasses.dataclass(frozen=True)
class ReleasedHostShard:
    """Placeholder left in GameDataset.feature_shards after
    release_host_shard: keeps the shape/dtype metadata (shard_dim, byte
    accounting) while making accidental array reads fail loudly instead of
    silently operating on stale data."""

    shape: tuple
    dtype: np.dtype
    nbytes: int

    def __array__(self, *a, **kw):
        raise ValueError("this host shard was released "
                         "(GameDataset.release_host_shard); only the device "
                         "copy survives")


@dataclasses.dataclass
class InputColumnNames:
    """Remappable input column names (reference: InputColumnsNames.scala)."""

    response: str = "response"
    offset: str = "offset"
    weight: str = "weight"
    uid: str = "uid"


@dataclasses.dataclass(eq=False)  # identity semantics: holds arrays, and the
# RE-dataset build memo (data/batching.py) weak-keys on dataset identity
class GameDataset:
    """n rows in canonical order; everything else hangs off row position."""

    response: np.ndarray                       # [n] float
    feature_shards: Dict[str, np.ndarray]      # shard -> [n, d_shard] float
    offsets: Optional[np.ndarray] = None       # [n]
    weights: Optional[np.ndarray] = None       # [n]
    # re_type -> [n] int index into entity_vocabs[re_type]; -1 = missing id
    entity_indices: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    # re_type -> [num_entities] entity id strings (row i of a RandomEffect
    # model belongs to entity_vocabs[re_type][i])
    entity_vocabs: Dict[str, np.ndarray] = dataclasses.field(default_factory=dict)
    index_maps: Dict[str, IndexMap] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        n = len(self.response)
        for shard, x in self.feature_shards.items():
            if x.shape[0] != n:
                raise ValueError(f"shard {shard!r} has {x.shape[0]} rows, expected {n}")
        for re_type, idx in self.entity_indices.items():
            if len(idx) != n:
                raise ValueError(f"entity index {re_type!r} has {len(idx)} rows, expected {n}")

    # device copies of feature shards, transferred ONCE per dataset and
    # shared by every consumer (coordinate scoring, validation rescoring,
    # per-entity block gathers): over a slow host->device link a duplicate
    # shard transfer costs seconds, and validation rescoring runs every
    # coordinate update
    _device_shards: Dict[str, object] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    # what the pack of a SPARSE shard's device copy made (pack_sparse's
    # counts, `pack_s` among them); dropped with the copy
    shard_build: Dict[str, dict] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    # device copies of the flat [n] vectors (response, weights, offsets),
    # one a (vector, device dtype), each beside the host array it was made
    # from (`device_vector`)
    _device_vectors: Dict[object, tuple] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)
    # scoring-side memos (entity-lane maps etc.), keyed by consumer
    _scoring_cache: Dict[object, object] = dataclasses.field(
        default_factory=dict, repr=False, compare=False)

    def device_shard(self, shard: str, *, with_csc: bool = False,
                     release_host: bool = False):
        """Device FeatureMatrix view of a shard (dense -> jnp array, scipy
        sparse -> PaddedSparse), built once and shared.  `with_csc` asks a
        wide sparse shard for its column-sorted gradient view as well
        (`ops/features.py::pack_sparse`, which packs what the cached copy
        lacks and nothing twice); what a pack made is `shard_build[shard]`.

        Every resident consumer reads this ONE copy: entity coordinates'
        scoring and block gathers, validation rescoring, and on a mesh whose
        data axis is one device the fixed-effect solve itself
        (`FixedEffectCoordinate._mesh_x_source`), so no fit after the
        dataset's first moves the shard over the host link.  A data axis
        over several devices stages the host shard into its sharded layout
        instead (`parallel/mesh_residency.py`).

        NOTE the memory doubling: the host numpy shard and the device copy
        both stay alive for the whole fit (every byte of feature data
        exists twice).  `release_host=True` drops the host copy once the
        device copy exists — safe ONLY when nothing will re-read the host
        array (no out-of-core re-streaming, no dataset.subset, no stats);
        resident single-fit jobs qualify.  Streaming mode does the inverse
        (release_device_shard): chunks stage from the host copy and a full
        device copy would defeat the HBM budget."""
        from photon_ml_tpu.ops import features as fops
        cached = self._device_shards.get(shard)
        host = self.feature_shards[shard]
        if isinstance(host, ReleasedHostShard):
            if cached is None:
                raise ValueError(
                    f"host shard {shard!r} was released (release_host_shard) "
                    "and no device copy survives; rebuild the dataset")
            host = None
        elif isinstance(host, fops.PaddedSparse):   # handed over packed
            cached, host = (host if cached is None else cached), None
        if fops.is_scipy_sparse(host) or isinstance(cached,
                                                    fops.PaddedSparse):
            x, counts = fops.pack_sparse(host, cached, with_csc)
            self._device_shards[shard] = x
            if counts is not None:
                self.shard_build[shard] = counts
        elif cached is None:
            self._device_shards[shard] = fops.as_feature_matrix(host)
        if release_host:
            self.release_host_shard(shard)
        return self._device_shards[shard]

    def device_vector(self, name: str, dtype=None):
        """Device copy of one flat [n] vector (`"response"`, `"weights"`,
        `"offsets"`; None where the dataset has none) in `dtype` (default:
        what `jnp.asarray` of the host array gives), transferred ONCE per
        dataset and dtype and shared by every consumer: each coordinate of
        each fit and the descent's own labels read one copy, where each
        used to convert and upload its own.  The copy is held beside the
        host array it was made from and made anew if the field is another
        array; nothing donates or writes it."""
        import jax
        import jax.numpy as jnp
        host = getattr(self, name)
        if host is None:
            return None
        want = jnp.dtype(jax.dtypes.canonicalize_dtype(
            host.dtype if dtype is None else dtype))
        held = self._device_vectors.get((name, want))
        if held is None or held[0] is not host:
            held = (host, jnp.asarray(host, want))
            self._device_vectors[(name, want)] = held
        return held[1]

    def release_host_shard(self, shard: str) -> None:
        """Drop the host numpy copy of a shard, keeping only the device
        copy (halves the footprint of `device_shard`'s doubling).  The slot
        keeps a shape/dtype placeholder so shard_dim etc. still answer;
        array reads raise via device_shard's guard."""
        host = self.feature_shards.get(shard)
        if host is None or isinstance(host, ReleasedHostShard):
            return
        if shard not in self._device_shards:
            raise ValueError(f"no device copy of shard {shard!r} exists yet; "
                             "releasing the host copy would lose the data")
        self.feature_shards[shard] = ReleasedHostShard(
            shape=tuple(host.shape), dtype=np.dtype(getattr(host, "dtype",
                                                            np.float64)),
            nbytes=int(getattr(host, "nbytes", 0) or
                       getattr(host, "data", np.empty(0)).nbytes))

    def release_device_shard(self, shard: str) -> None:
        """Drop the shared device copy of a shard (the host copy remains
        the source of truth).  Used by streaming mode's staging path and by
        the coordinate residency manager's eviction rotation."""
        self._device_shards.pop(shard, None)
        self.shard_build.pop(shard, None)

    @property
    def num_rows(self) -> int:
        return len(self.response)

    def num_entities(self, re_type: str) -> int:
        return len(self.entity_vocabs[re_type])

    def shard_dim(self, shard: str) -> int:
        return self.feature_shards[shard].shape[1]

    def process_slice(self, count: int = None,
                      index: int = None) -> "GameDataset":
        """THIS process's contiguous 1/P row block of the dataset (count/
        index default to the multihost runtime's identity) — the
        process-slice view a multi-host ingest uses so each host holds only
        the rows its mesh devices own.  Vocabularies and index maps are
        SHARED with the parent (every process sees identical global entity
        spaces, whatever rows it holds)."""
        from photon_ml_tpu.parallel.multihost import process_row_range
        r = process_row_range(self.num_rows, count=count, index=index)
        return self.subset(np.arange(r.start, r.stop))

    def subset(self, rows: np.ndarray) -> "GameDataset":
        """Row slice sharing vocabularies (for train/validation splits)."""
        take = lambda a: None if a is None else a[rows]
        return GameDataset(
            response=self.response[rows],
            feature_shards={s: x[rows] for s, x in self.feature_shards.items()},
            offsets=take(self.offsets),
            weights=take(self.weights),
            entity_indices={t: idx[rows] for t, idx in self.entity_indices.items()},
            entity_vocabs=self.entity_vocabs,
            index_maps=self.index_maps,
        )


def save_game_dataset(dataset: GameDataset, path: str) -> None:
    """Columnar npz persistence of a GameDataset (role of the reference's
    Avro input files once converted; see data/avro_io.py for Avro itself)."""
    arrays = {"response": dataset.response}
    if dataset.offsets is not None:
        arrays["offsets"] = dataset.offsets
    if dataset.weights is not None:
        arrays["weights"] = dataset.weights
    for s, x in dataset.feature_shards.items():
        if _is_sparse(x):
            if "::" in s:
                raise ValueError(
                    f"sparse shard name {s!r} may not contain '::' (it is "
                    "the npz key delimiter)")
            csr = x.tocsr()
            arrays[f"spshard::{s}::data"] = csr.data
            arrays[f"spshard::{s}::indices"] = csr.indices
            arrays[f"spshard::{s}::indptr"] = csr.indptr
            arrays[f"spshard::{s}::shape"] = np.asarray(csr.shape)
        else:
            arrays[f"shard::{s}"] = x
    for t, idx in dataset.entity_indices.items():
        arrays[f"entidx::{t}"] = idx
        arrays[f"entvocab::{t}"] = np.asarray(dataset.entity_vocabs[t]).astype(object)
    np.savez_compressed(path if path.endswith(".npz") else path + ".npz", **arrays)


def load_game_dataset(path: str) -> GameDataset:
    z = np.load(path if path.endswith(".npz") else path + ".npz",
                allow_pickle=True)
    shards, entidx, entvocab = {}, {}, {}
    sp_names = {k.split("::")[1] for k in z.files if k.startswith("spshard::")}
    for s in sp_names:
        import scipy.sparse as sp
        shards[s] = sp.csr_matrix(
            (z[f"spshard::{s}::data"], z[f"spshard::{s}::indices"],
             z[f"spshard::{s}::indptr"]),
            shape=tuple(z[f"spshard::{s}::shape"]))
    for k in z.files:
        if k.startswith("shard::"):
            shards[k[7:]] = z[k]
        elif k.startswith("entidx::"):
            entidx[k[8:]] = z[k]
        elif k.startswith("entvocab::"):
            entvocab[k[10:]] = z[k]
    return GameDataset(
        response=z["response"],
        feature_shards=shards,
        offsets=z["offsets"] if "offsets" in z.files else None,
        weights=z["weights"] if "weights" in z.files else None,
        entity_indices=entidx,
        entity_vocabs=entvocab)


def build_game_dataset(
    response: np.ndarray,
    feature_shards: Dict[str, np.ndarray],
    *,
    offsets: Optional[np.ndarray] = None,
    weights: Optional[np.ndarray] = None,
    entity_ids: Optional[Dict[str, np.ndarray]] = None,
    entity_vocabs: Optional[Dict[str, np.ndarray]] = None,
    index_maps: Optional[Dict[str, IndexMap]] = None,
) -> GameDataset:
    """GameConverters equivalent: raw id columns -> indexed entity columns.

    `entity_ids[re_type]` is a [n] array of raw ids (strings/ints); ids are
    interned into a vocabulary (sorted for determinism) unless a shared
    vocab is supplied (scoring against a trained model's entity space, where
    unseen ids must map to -1 — the reference's passive/missing-score path).
    """
    entity_indices, vocabs = {}, {}
    for re_type, ids in (entity_ids or {}).items():
        ids = np.asarray(ids)
        if entity_vocabs and re_type in entity_vocabs:
            vocab = np.asarray(entity_vocabs[re_type])
            lookup = {v: i for i, v in enumerate(vocab.tolist())}
            idx = np.asarray([lookup.get(v, -1) for v in ids.tolist()],
                             dtype=np.int32)
        else:
            vocab, idx = np.unique(ids, return_inverse=True)
            idx = idx.astype(np.int32)
        entity_indices[re_type] = idx
        vocabs[re_type] = vocab
    return GameDataset(
        response=np.asarray(response, dtype=np.float64),
        # scipy.sparse shards stay sparse, canonicalized to CSR (row
        # slicing for subset/validation; the wide fixed-effect regime,
        # reference: AvroDataReader SparseVector columns); np.asarray on
        # them would produce a useless 0-d object array
        feature_shards={s: (x.tocsr() if _is_sparse(x) else np.asarray(x))
                        for s, x in feature_shards.items()},
        offsets=None if offsets is None else np.asarray(offsets, dtype=np.float64),
        weights=None if weights is None else np.asarray(weights, dtype=np.float64),
        entity_indices=entity_indices,
        entity_vocabs=vocabs,
        index_maps=index_maps or {},
    )
