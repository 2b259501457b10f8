"""Compiled online scorer: a GAME model resident on the device.

The offline scoring path (`GameModel.score_dataset`) builds per-dataset
caches and is shaped for one huge batch; serving needs the transpose —
the MODEL stays resident (fixed-effect coefficient vectors, stacked
random-effect coefficient tables, MF factors, all device arrays built once
at load), and small request batches stream through ONE pre-jitted program
per power-of-two batch bucket.  Related work keeps the model on the
accelerator and amortizes launches over batched requests for exactly this
reason (Snap ML, arXiv:1803.06333; GPU primal learning, arXiv:2008.03433).

Entity identity is resolved host-side: each random-effect coordinate
carries an id->row hash map; ids unseen at training time map to row -1 and
contribute score 0, so such rows fall back to fixed-effect-only scores
exactly like the offline path (reference: the missing-score default,
Evaluator.scala:35-45).

Models past the device budget serve through the tiered entity store
(`store=StoreConfig(...)`): each random-effect table lives in a
photon_ml_tpu.store.TieredEntityStore — a device-resident HOT subset the
bucket programs gather from by slot, a host warm tier, and sealed cold
segments on disk.  A request chunk's misses ride the chunk's own device
transfer as a per-batch staging window (its lanes gather from a second
traced table argument), so a miss never compiles anything or copies the
hot table; promotion into the hot set is amortized in the store.  Online
deltas land in whatever tier a row lives in and feedback for cold
entities promotes them; tiered scores are bit-identical to the
fully-resident scorer's.

Scoring semantics match `GameModel.score_dataset`: the returned value is
the summed margin contribution of every coordinate, WITHOUT offsets or the
inverse link (`mean_prediction` applies the link when callers want means).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu import telemetry
from photon_ml_tpu.telemetry.timings import clock

from photon_ml_tpu.models.game import (
    FactoredRandomEffectModel, FixedEffectModel, GameModel,
    MatrixFactorizationModel, RandomEffectModel,
)
from photon_ml_tpu.ops import losses as L
from photon_ml_tpu.parallel.random_effect import score_by_entity
from photon_ml_tpu.utils.devices import device_summary
from photon_ml_tpu.utils.math import ceil_pow2


@dataclasses.dataclass
class ScoreBatchResult:
    """One scored request batch + the stats the metrics accumulator wants."""

    scores: np.ndarray          # [n] margins, request row order
    num_rows: int
    buckets: List[int]          # padded bucket size per device call
    entity_lookups: int         # id resolutions attempted (all RE + MF)
    entity_hits: int            # resolutions that found a trained row
    new_compiles: int           # bucket shapes first seen by this call


def _id_lookup(entity_ids: np.ndarray) -> dict:
    """Host-side id -> table-row hash map (the serving replacement for the
    offline path's per-dataset vocab joins)."""
    return {v: i for i, v in enumerate(np.asarray(entity_ids).tolist())}


@jax.jit
def _scatter_rows(table, rows, values):
    """Row-level delta swap: scatter changed rows into a stacked table.
    Padding lanes carry an out-of-range row index and DROP, so one
    compiled program per (table shape, pow-2 row count) covers every
    delta — steady-state updates trace nothing new."""
    return table.at[rows].set(values, mode="drop")


@jax.jit
def _gather_rows(table, rows):
    """Row gather for delta priors (pad lanes clamp to row 0; callers mask
    them out host-side)."""
    return table[jnp.maximum(rows, 0)]


def _pad_pow2_rows(rows: np.ndarray, values: np.ndarray, num_table_rows: int
                   ) -> Tuple[np.ndarray, np.ndarray]:
    """Pad a row-update set to the next power of two with out-of-range
    (dropped) scatter lanes, so delta row counts map onto a bounded set of
    compiled scatter shapes."""
    k = len(rows)
    pad = int(ceil_pow2(max(k, 1))) - k
    if pad == 0:
        return rows, values
    rows_p = np.concatenate(
        [rows, np.full(pad, num_table_rows, dtype=rows.dtype)])
    values_p = np.concatenate(
        [values, np.zeros((pad, values.shape[1]), values.dtype)])
    return rows_p, values_p


def _resolve_lanes(lookup: dict, ids: np.ndarray) -> np.ndarray:
    return np.fromiter((lookup.get(v, -1) for v in np.asarray(ids).tolist()),
                       dtype=np.int32, count=len(ids))


class CompiledScorer:
    """Device-resident GAME model + bucket-jitted scoring programs.

    `score(features, ids)` takes per-shard feature rows
    (`{shard: [n, d]}`) and per-entity-type raw ids (`{re_type: [n]}`),
    pads each chunk to the smallest power-of-two bucket
    (`utils.math.ceil_pow2`, the same rule training prep buckets with),
    and runs one fused XLA program.  `warmup()` pre-compiles every bucket
    so no request triggers a compile afterwards.
    """

    def __init__(self, model: GameModel, *, max_batch: int = 1024,
                 min_bucket: int = 8, version: Optional[str] = None,
                 store=None, store_dir: Optional[str] = None,
                 shard=None, warm_margins: Optional[bool] = None):
        if max_batch < 1 or min_bucket < 1:
            raise ValueError("max_batch and min_bucket must be >= 1")
        self.model = model
        self.version = version
        # entity-sharded serving (fleet/shards.py): a ShardAssignment
        # makes this scorer hold ONLY its owned slice of every
        # random-effect table (FE/MF coordinates replicate in full), and
        # filter replicated delta/row-state scatters to owned rows
        self.shard = shard
        # margins-program warmup: sharded replicas serve score_margins()
        # on the fan-out path, so they pre-compile it by default
        self.warm_margins = (shard is not None if warm_margins is None
                             else bool(warm_margins))
        self.max_batch = int(ceil_pow2(max_batch))
        self.min_bucket = min(int(ceil_pow2(min_bucket)), self.max_batch)
        self._loss = L.TASK_LOSSES.get(model.task_type)
        # tiered-store serving (photon_ml_tpu/store/): every RE table
        # lives behind a TieredEntityStore instead of fully device-resident
        if store is not None and store_dir is None:
            raise ValueError("store=StoreConfig(...) requires store_dir "
                             "(the cold tier's segment directory)")
        if store is not None and store.overlay_rows < self.max_batch:
            raise ValueError(
                f"store overlay_rows ({store.overlay_rows}) must cover "
                f"the largest scoring chunk (max_batch={self.max_batch}):"
                " a single batch could otherwise miss more distinct rows "
                "than the staging overlay holds")
        self._store_config = store
        self._store_dir = store_dir
        self._stores: Dict[str, object] = {}

        # static program structure (baked into _compute) + device tables
        self._fe_meta: List[Tuple[str, str]] = []          # (name, shard)
        self._re_meta: List[Tuple[str, str, str]] = []     # (name, shard, re_type)
        self._mf_meta: List[Tuple[str, str, str]] = []     # (name, row_t, col_t)
        self._lookups: Dict[str, dict] = {}                # lane key -> id map
        self._table_slot: Dict[str, int] = {}              # RE name -> slot
        self._overlay_slot: Dict[str, int] = {}            # store coord -> slot
        self._entity_ids: Dict[str, np.ndarray] = {}       # RE name -> ids held
        self._shard_row_maps: Dict[str, dict] = {}         # RE name -> full->local
        self._logical_rows: Dict[str, int] = {}            # RE name -> owned rows
        self.shard_rows_dropped = 0   # unowned delta/replay rows filtered
        tables = []
        shard_dims: Dict[str, int] = {}

        def shard_slice(m):
            """A RE coordinate's (entity_ids, table, full->local map) under
            this scorer's shard assignment — owned rows only, ORIGINAL row
            order preserved (so the slice is a pure filter of the full
            table and per-shard audits hash the same bytes on the
            publisher's filtered view and the replica's resident table).
            A shard owning zero entities keeps one never-addressed zero
            row so the gather programs stay well-formed; its logical row
            count is 0 and audits hash the empty slice."""
            ids_full = np.asarray(m.entity_ids)
            table_full = np.asarray(m.global_coefficients())
            if self.shard is None:
                return ids_full, table_full, None, len(ids_full)
            mask = self.shard.spec.owned_mask(ids_full, self.shard.index)
            row_map = {int(full): local for local, full
                       in enumerate(np.nonzero(mask)[0].tolist())}
            ids_own = ids_full[mask]
            table_own = table_full[mask]
            logical = len(ids_own)
            if logical == 0:
                table_own = np.zeros((1, table_full.shape[1]),
                                     table_full.dtype)
            return ids_own, table_own, row_map, logical

        def note_shard(shard, dim, owner):
            prev = shard_dims.setdefault(shard, int(dim))
            if prev != int(dim):
                raise ValueError(
                    f"coordinate {owner!r} scores shard {shard!r} at width "
                    f"{int(dim)} but another coordinate uses width {prev}")

        for name, m in model.coordinates.items():
            if isinstance(m, FixedEffectModel):
                w = jnp.asarray(m.glm.coefficients.means)
                note_shard(m.feature_shard, w.shape[-1], name)
                self._fe_meta.append((name, m.feature_shard))
                tables.append(w)
            elif isinstance(m, (RandomEffectModel, FactoredRandomEffectModel)):
                # stacked per-entity table in the ORIGINAL shard space:
                # projected/factored coordinates materialize P^T c once at
                # load so serving is a single gather + row dot per request
                own_ids, own_table, row_map, logical = shard_slice(m)
                self._entity_ids[name] = own_ids
                self._logical_rows[name] = logical
                if row_map is not None:
                    self._shard_row_maps[name] = row_map
                if store is not None:
                    import os
                    from photon_ml_tpu.store import TieredEntityStore
                    table_np = own_table
                    note_shard(m.feature_shard, table_np.shape[-1], name)
                    st = TieredEntityStore.create(
                        os.path.join(store_dir, name.replace("/", "_")),
                        table_np, store,
                        entity_ids=own_ids if logical else
                        np.asarray(["\0__shard_pad__"], dtype=object),
                        name=name)
                    self._stores[name] = st
                    self._re_meta.append((name, m.feature_shard,
                                          m.random_effect_type))
                    self._table_slot[name] = len(tables)
                    tables.append(st.table())
                    # the staging window rides as its own traced table:
                    # a batch's missed-row values score out of it (built
                    # host-side per batch, shipped with the batch's own
                    # transfer) while promotion into the main hot table
                    # stays amortized.  The entry here is a placeholder
                    # pinning the static [overlay_rows, d] shape.
                    self._overlay_slot[name] = len(tables)
                    tables.append(jnp.zeros((st.overlay_rows, st.dim),
                                            st.dtype))
                else:
                    table = jnp.asarray(own_table)
                    note_shard(m.feature_shard, table.shape[-1], name)
                    self._re_meta.append((name, m.feature_shard,
                                          m.random_effect_type))
                    self._lookups[name] = (_id_lookup(own_ids) if logical
                                           else {})
                    self._table_slot[name] = len(tables)
                    tables.append(table)
            elif isinstance(m, MatrixFactorizationModel):
                self._mf_meta.append((name, m.row_effect_type,
                                      m.col_effect_type))
                self._lookups[name + "/row"] = _id_lookup(m.row_ids)
                self._lookups[name + "/col"] = _id_lookup(m.col_ids)
                tables.append(jnp.asarray(m.row_factors))
                tables.append(jnp.asarray(m.col_factors))
            else:
                raise TypeError(f"unknown coordinate model type {type(m)}")
        if not tables:
            raise ValueError("model has no coordinates to serve")
        # deliberately lock-free: delta publishers replace the WHOLE tuple
        # (never mutate in place) and scoring threads read it once per
        # batch — atomic publish at batch granularity
        self._tables = tuple(tables)  # photonlint: guarded-by=atomic
        self.feature_shards: Dict[str, int] = shard_dims
        self.entity_types = sorted(
            {t for _, _, t in self._re_meta}
            | {t for _, r, c in self._mf_meta for t in (r, c)})
        self._dtype = (jnp.result_type(*self._tables) if self._tables
                       else jnp.float32)
        # one jitted program, cached per bucket shape; tables are traced
        # ARGUMENTS (not closed-over constants), so a same-shape hot swap
        # reuses every compiled bucket program
        self._program = jax.jit(self._compute)
        # the fan-out twin: same contributions, returned per coordinate
        # instead of folded — what sharded replicas serve to the front
        self._program_margins = jax.jit(self._compute_margins)
        self._seen_buckets: set = set()
        self.bucket_compiles = 0
        self.warmup_s = 0.0
        self.warmed = False
        # online-update version vector: seq of the newest applied delta
        # (0 = pristine full-model load) + lifetime apply/revert counts
        self.delta_seq = 0
        self.deltas_applied = 0
        self.deltas_reverted = 0

    # -- construction ------------------------------------------------------

    @classmethod
    def from_model_dir(cls, model_dir: str, *, max_batch: int = 1024,
                       min_bucket: int = 8, version: Optional[str] = None,
                       warmup: bool = True, store=None,
                       store_dir: Optional[str] = None, shard=None,
                       warm_margins: Optional[bool] = None
                       ) -> "CompiledScorer":
        from photon_ml_tpu.models.io import load_game_model
        model, _config = load_game_model(model_dir)
        scorer = cls(model, max_batch=max_batch, min_bucket=min_bucket,
                     version=version, store=store, store_dir=store_dir,
                     shard=shard, warm_margins=warm_margins)
        if warmup:
            scorer.warmup()
        return scorer

    def device_summary(self) -> Dict[str, object]:
        """{platform, kind, count} of the devices holding the tables."""
        return device_summary(self._tables)

    def bucket_sizes(self) -> List[int]:
        out, b = [], self.min_bucket
        while b < self.max_batch:
            out.append(b)
            b <<= 1
        out.append(self.max_batch)
        return out

    def _lane_names(self) -> List[str]:
        names = []
        for name, _, _ in self._re_meta:
            names.append(name)
            if name in self._stores:
                names.append(name + "@stage")
        names += [name + side for name, _, _ in self._mf_meta
                  for side in ("/row", "/col")]
        return names

    def warmup(self) -> float:
        """Compile every bucket program now, so no request ever does.
        Store-backed tables also pre-compile their promotion/delta
        scatter shapes, so steady-state misses trace nothing either."""
        t0 = clock()
        with telemetry.span("serve_warmup", version=self.version):
            for st in self._stores.values():
                st.warmup()
            for b in self.bucket_sizes():
                xs = {s: np.zeros((b, d), np.float64)
                      for s, d in self.feature_shards.items()}
                lanes = {k: np.full(b, -1, np.int32)
                         for k in self._lane_names()}
                jax.block_until_ready(self._run_bucket(xs, lanes, b))
                if self.warm_margins:
                    jax.block_until_ready(
                        self._run_bucket(xs, lanes, b, margins=True))
        self.warmup_s = clock() - t0
        self.warmed = True
        return self.warmup_s

    # -- the device program ------------------------------------------------

    def _compute(self, tables, xs, lanes):
        """Summed coordinate margins for one padded bucket — ONE fused
        program (FE matvecs + RE gather-dots + MF factor dots), mirroring
        GameModel.score_dataset coordinate by coordinate."""
        i = 0
        total = None

        def add(z):
            nonlocal total
            total = z if total is None else total + z

        for _name, shard in self._fe_meta:
            w = tables[i]; i += 1
            add(xs[shard] @ w)
        for name, shard, _re_type in self._re_meta:
            table = tables[i]; i += 1
            add(score_by_entity(table, xs[shard], lanes[name]))
            if name in self._stores:
                # tiered coordinate: a row lives in EXACTLY one of the
                # main hot table / staging overlay (the other lane is
                # -1 -> contributes 0), so the sum is the full margin
                overlay = tables[i]; i += 1
                add(score_by_entity(overlay, xs[shard],
                                    lanes[name + "@stage"]))
        for name, _row_t, _col_t in self._mf_meta:
            rf, cf = tables[i], tables[i + 1]; i += 2
            rl, cl = lanes[name + "/row"], lanes[name + "/col"]
            ok = (rl >= 0) & (cl >= 0)
            rfa = rf[jnp.maximum(rl, 0)]
            cfa = cf[jnp.maximum(cl, 0)]
            add(jnp.where(ok, jnp.sum(rfa * cfa, axis=-1), 0.0))
        return total

    def coordinate_meta(self) -> List[Dict[str, str]]:
        """The coordinate fold order as data — one ordered entry per
        margin `_compute` adds (FE, then RE, then MF, each in model
        order).  This is the merge contract of entity-sharded fan-out
        scoring: the front re-folds per-coordinate margins host-side in
        EXACTLY this order (fleet/shards.py merge_margins), which is what
        makes merged scores bit-identical to a monolithic replica's."""
        out: List[Dict[str, str]] = []
        for name, shard in self._fe_meta:
            out.append({"name": name, "kind": "fixed",
                        "feature_shard": shard})
        for name, shard, re_type in self._re_meta:
            out.append({"name": name, "kind": "random",
                        "feature_shard": shard, "entity_type": re_type})
        for name, row_t, col_t in self._mf_meta:
            out.append({"name": name, "kind": "matrix",
                        "row_type": row_t, "col_type": col_t})
        return out

    def _compute_margins(self, tables, xs, lanes):
        """Per-coordinate margins for one padded bucket, in
        `coordinate_meta()` order — the same contribution terms `_compute`
        folds, returned unfolded.  A tiered coordinate's hot-table and
        staging-window contributions combine into ONE margin here (a row
        lives in exactly one of the two, the other lane is -1 -> 0.0), so
        the margin is the coordinate's full contribution regardless of
        tiering — and the merge fold stays one add per coordinate,
        matching the fully-resident monolithic chain."""
        i = 0
        margins = []
        for _name, shard in self._fe_meta:
            w = tables[i]; i += 1
            margins.append(xs[shard] @ w)
        for name, shard, _re_type in self._re_meta:
            table = tables[i]; i += 1
            z = score_by_entity(table, xs[shard], lanes[name])
            if name in self._stores:
                overlay = tables[i]; i += 1
                z = z + score_by_entity(overlay, xs[shard],
                                        lanes[name + "@stage"])
            margins.append(z)
        for name, _row_t, _col_t in self._mf_meta:
            rf, cf = tables[i], tables[i + 1]; i += 2
            rl, cl = lanes[name + "/row"], lanes[name + "/col"]
            ok = (rl >= 0) & (cl >= 0)
            rfa = rf[jnp.maximum(rl, 0)]
            cfa = cf[jnp.maximum(cl, 0)]
            margins.append(jnp.where(ok, jnp.sum(rfa * cfa, axis=-1), 0.0))
        return tuple(margins)

    def _run_bucket(self, xs, lanes, bucket: int, store_tables=None,
                    margins: bool = False):
        if bucket not in self._seen_buckets:
            self._seen_buckets.add(bucket)
            self.bucket_compiles += 1
        # ONE batched host->device transfer for every feature shard,
        # lane array, and staged-miss window (per-array dispatch
        # overhead dominates small-batch serving latency on weak hosts;
        # the dtype cast stays host-side)
        np_dtype = np.dtype(self._dtype)
        windows = {name: w for name, (_t, w) in store_tables.items()} \
            if store_tables else {}
        xs, lanes, windows = jax.device_put((
            {s: np.asarray(x, np_dtype) for s, x in xs.items()},
            {k: np.asarray(v) for k, v in lanes.items()},
            windows))
        tables = self._tables
        if store_tables:
            # tiered mode: each chunk scores against the EXACT hot-table
            # snapshot its slots were resolved into (batch-granularity
            # consistency — a concurrent promotion replaces the store's
            # table functionally, never mutating this snapshot) plus its
            # own private staging window
            t = list(tables)
            for name, (table, _w) in store_tables.items():
                t[self._table_slot[name]] = table
                t[self._overlay_slot[name]] = windows[name]
            tables = tuple(t)
        if margins:
            return self._program_margins(tables, xs, lanes)
        return self._program(tables, xs, lanes)

    # -- online row-level updates ------------------------------------------

    def updatable_coordinates(self) -> List[Tuple[str, str, str]]:
        """(name, feature_shard, re_type) of every coordinate whose stacked
        table accepts row-level delta swaps (plain + factored random
        effects; MF factor pairs are not online-updatable — prefer a full
        refit there)."""
        return list(self._re_meta)

    def re_table(self, name: str) -> jax.Array:
        """The device-resident stacked table of one RE coordinate
        (original shard space — what apply_delta scatters into; in tiered
        mode this is the HOT subset, addressed by slot)."""
        st = self._stores.get(name)
        if st is not None:
            return st.table()
        return self._tables[self._table_slot[name]]

    def entity_row(self, name: str, entity_id) -> int:
        """Table row of a raw entity id under coordinate `name`
        (-1 = unseen at training time; such entities cannot be
        online-updated — the table has no row to anchor at)."""
        st = self._stores.get(name)
        if st is not None:
            return st.resolve_one(entity_id)
        return self._lookups[name].get(entity_id, -1)

    def entity_store(self, name: str):
        """The TieredEntityStore behind one coordinate (None when the
        table is fully device-resident)."""
        return self._stores.get(name)

    @property
    def tiered(self) -> bool:
        return bool(self._stores)

    def gather_rows(self, name: str, rows: np.ndarray) -> jax.Array:
        """Gather of table rows (delta priors / anchors).  Tiered mode
        reads the authoritative warm/cold bytes host-side — bit-exact
        with what the hot tier serves."""
        st = self._stores.get(name)
        if st is not None:
            return jnp.asarray(st.gather_rows(np.asarray(rows, np.int64)))
        return _gather_rows(self.re_table(name),
                            jnp.asarray(np.asarray(rows, np.int64)))

    def _filter_shard_rows(self, name: str, rows: np.ndarray,
                           values: np.ndarray
                           ) -> Tuple[np.ndarray, np.ndarray]:
        """The shard-filtering chokepoint of EVERY row write: replicated
        deltas, rollback row-state replays, and snapshot bootstraps all
        carry FULL-model row indices; a sharded scorer keeps only its
        owned rows, remapped to its local (filtered) table space.
        Unowned rows drop silently — their owner's replica applies them —
        and are counted in `shard_rows_dropped`."""
        row_map = self._shard_row_maps.get(name)
        if row_map is None:
            return rows, values
        keep = [i for i, r in enumerate(rows.tolist()) if int(r) in row_map]
        self.shard_rows_dropped += len(rows) - len(keep)
        local = np.asarray([row_map[int(rows[i])] for i in keep], np.int64)
        return local, values[keep]

    def _scatter_coordinate(self, name: str, rows: np.ndarray,
                            values: np.ndarray,
                            promote: bool = False) -> None:
        slot = self._table_slot.get(name)
        if slot is None:
            known = sorted(self._table_slot)
            raise KeyError(f"coordinate {name!r} has no online-updatable "
                           f"table (updatable: {known})")
        rows = np.asarray(rows, np.int64)
        values = np.asarray(values)
        rows, values = self._filter_shard_rows(name, rows, values)
        if len(rows) == 0 and self.shard is not None:
            return  # this shard owns none of the delta's rows
        st = self._stores.get(name)
        if st is not None:
            # tiered mode: the delta lands in whatever tier each row
            # lives in (warm always, hot write-through for resident rows,
            # promote=True pulls cold rows hot — the feedback path)
            if values.shape != (len(rows), st.dim):
                raise ValueError(
                    f"delta values for {name!r} must be [{len(rows)}, "
                    f"{st.dim}], got {values.shape}")
            st.update_rows(rows, values, promote=promote)
            return
        table = self._tables[slot]
        if values.shape != (len(rows), table.shape[1]):
            raise ValueError(
                f"delta values for {name!r} must be [{len(rows)}, "
                f"{table.shape[1]}], got {values.shape}")
        if len(rows) and int(rows.max()) >= table.shape[0]:
            raise ValueError(
                f"delta row {int(rows.max())} out of range for {name!r} "
                f"(table has {table.shape[0]} rows)")
        rows_p, values_p = _pad_pow2_rows(rows, values, table.shape[0])
        new_table = _scatter_rows(table, jnp.asarray(rows_p),
                                  jnp.asarray(values_p, table.dtype))
        tables = list(self._tables)
        tables[slot] = new_table
        # one atomic tuple swap: a concurrent score() batch reads either
        # the old or the new tuple — batch-granularity consistency, same
        # contract as a full-model hot swap
        self._tables = tuple(tables)

    def scatter_rows(self, name: str, rows: np.ndarray,
                     values: np.ndarray) -> None:
        """Scatter raw row values into one coordinate's live table (the
        replication layer's replay primitive: rollback records and
        snapshot bootstraps carry explicit row states rather than
        ModelDeltas).  Callers serialize through the registry lock, same
        contract as apply_delta."""
        self._scatter_coordinate(name, rows, values)

    def warmup_delta(self, max_rows: int = 64) -> float:
        """Pre-compile the delta scatter programs for every pow-2 row
        count up to `max_rows` on every updatable table — the replica
        twin of OnlineUpdater.warmup's scatter block, so steady-state
        delta REPLAY traces nothing (a follower replica has no updater
        to warm these for it)."""
        t0 = clock()
        with telemetry.span("replica_delta_warmup", version=self.version):
            for name, _shard, _re_type in self.updatable_coordinates():
                st = self._stores.get(name)
                if st is not None:
                    # tiered tables replay deltas through the store's own
                    # pre-jitted scatter shapes
                    if not st.warmed:
                        st.warmup()
                    continue
                table = self.re_table(name)
                k = 1
                bound = int(ceil_pow2(max(max_rows, 1)))
                while k <= bound:
                    rows = np.arange(min(k, table.shape[0]), dtype=np.int64)
                    vals = np.zeros((len(rows), table.shape[1]))
                    rows_p, vals_p = _pad_pow2_rows(rows, vals,
                                                    table.shape[0])
                    # result discarded: the live table is never touched
                    jax.block_until_ready(_scatter_rows(
                        table, jnp.asarray(rows_p),
                        jnp.asarray(vals_p, table.dtype)))
                    k <<= 1
        return clock() - t0

    def table_hashes(self):  # photonlint: flush-point -- audit endpoint: one deliberate full-table readback per call, never on the scoring path
        """sha256 of every device table's exact byte content, keyed by
        coordinate lane (MF factor pairs hash as name/row + name/col).
        The fleet audit primitive: two replicas whose version vectors AND
        table hashes agree converged bit-identically."""
        import hashlib
        i = 0
        out: Dict[str, str] = {}
        for name, _shard in self._fe_meta:
            out[name] = hashlib.sha256(
                np.ascontiguousarray(np.asarray(self._tables[i]))
                .tobytes()).hexdigest()
            i += 1
        for name, _shard, _re_type in self._re_meta:
            st = self._stores.get(name)
            if st is not None:
                # tiered mode hashes the LOGICAL table (cold + warm
                # overlay): two replicas whose tiering histories differ
                # but whose row values agree hash identically
                rows_np = np.asarray(st.full_table())
            else:
                rows_np = np.asarray(self._tables[i])
            if self.shard is not None:
                # sharded mode hashes the OWNED slice (a zero-owned
                # shard's never-addressed pad row is excluded), so the
                # hash equals the publisher's shard_table_hashes() of
                # the same filtered rows
                rows_np = rows_np[:self._logical_rows[name]]
            out[name] = hashlib.sha256(
                np.ascontiguousarray(rows_np).tobytes()).hexdigest()
            i += 2 if st is not None else 1
        for name, _row_t, _col_t in self._mf_meta:
            for side in ("/row", "/col"):
                out[name + side] = hashlib.sha256(
                    np.ascontiguousarray(np.asarray(self._tables[i]))
                    .tobytes()).hexdigest()
                i += 1
        return out

    def apply_delta(self, delta) -> None:
        """Scatter a ModelDelta's changed rows into the live tables.
        Callers serialize through the registry lock; scoring threads need
        no lock (the table tuple swap is atomic, and the compiled bucket
        programs take tables as traced ARGUMENTS, so no re-trace).
        Tiered tables land the rows in whatever tier they live in, and
        PROMOTE cold rows hot — an entity the traffic cares enough about
        to send feedback for belongs in the hot set."""
        for name, cd in delta.coordinates.items():
            self._scatter_coordinate(name, cd.rows, cd.values,
                                     promote=True)
        self.delta_seq = delta.seq
        self.deltas_applied += 1

    def revert_delta(self, delta) -> None:
        """Scatter a delta's pre-delta rows back (exact rollback: restores
        the bit pattern the rows had before apply_delta — in tiered mode
        across every tier the delta touched)."""
        for name, cd in delta.coordinates.items():
            self._scatter_coordinate(name, cd.rows, cd.prior)
        self.delta_seq = delta.seq - 1
        self.deltas_reverted += 1

    # -- tiered-store observability ----------------------------------------

    def store_totals(self) -> Dict[str, int]:
        """Cumulative tier counters summed over every store-backed
        coordinate (the ServingMetrics probe; all zeros when fully
        resident)."""
        from photon_ml_tpu.store.entity import store_totals
        return store_totals(self._stores)

    def store_health(self) -> Optional[Dict]:
        """Per-coordinate residency + the aggregate hot hit rate for
        /healthz (None when fully resident)."""
        if not self._stores:
            return None
        totals = self.store_totals()
        lookups = (totals["hot_hits"] + totals["warm_hits"]
                   + totals["cold_misses"])
        return {
            "hit_rate": (round(totals["hot_hits"] / lookups, 4)
                         if lookups else None),
            "promotions": totals["promotions"],
            "spills": totals["spills"],
            "coordinates": {name: st.residency()
                            for name, st in self._stores.items()},
        }

    def flush_stores(self) -> int:
        """Durably spill every dirty warm segment (shutdown/seal hook).
        Returns segments written."""
        return sum(st.flush() for st in self._stores.values())

    # -- request scoring ---------------------------------------------------

    def validate_request(self, features: Dict[str, np.ndarray],
                         ids: Dict[str, np.ndarray]) -> int:
        """Shape/coverage check -> the request's row count.  Raised errors
        are per-request (the batcher propagates them to one caller, not the
        whole batch)."""
        missing = sorted(set(self.feature_shards) - set(features))
        if missing:
            raise ValueError(f"request is missing feature shard(s) {missing}"
                             f" (model scores {sorted(self.feature_shards)})")
        n = None
        for shard, want in self.feature_shards.items():
            x = np.asarray(features[shard])
            if x.ndim != 2 or x.shape[1] != want:
                raise ValueError(
                    f"feature shard {shard!r} must be [n, {want}], got "
                    f"shape {x.shape}")
            if n is None:
                n = x.shape[0]
            elif x.shape[0] != n:
                raise ValueError(
                    f"feature shard {shard!r} has {x.shape[0]} rows; other "
                    f"shards have {n}")
        missing_ids = sorted(set(self.entity_types) - set(ids or {}))
        if missing_ids:
            raise ValueError(
                f"request is missing entity id column(s) {missing_ids} "
                f"(model has random effects over {self.entity_types})")
        for t in self.entity_types:
            col = np.asarray(ids[t])
            if n is None:
                n = len(col)
            if col.shape != (n,):
                raise ValueError(
                    f"id column {t!r} must be [{n}], got shape {col.shape}")
        if n is None or n == 0:
            raise ValueError("empty request")
        return n

    def _lanes_for_chunk(self, ids, lo, hi):
        lanes, hits, lookups = {}, 0, 0
        store_tables = {}
        for name, _shard, re_type in self._re_meta:
            col = np.asarray(ids[re_type])[lo:hi]
            st = self._stores.get(name)
            if st is not None:
                # tiered mode: resolve ids -> global rows, then stage the
                # chunk's misses into the per-batch staging window
                # (promotion into the main table is amortized); lanes
                # are SLOTS into the returned snapshot/window
                rows = st.resolve(col)
                slots, stage, table, staged_vals = st.lookup_slots(rows)
                window = np.zeros((st.overlay_rows, st.dim),
                                  np.dtype(st.dtype))
                window[: len(staged_vals)] = staged_vals
                lanes[name] = slots
                lanes[name + "@stage"] = stage
                store_tables[name] = (table, window)
                hits += int((rows >= 0).sum()); lookups += len(rows)
                continue
            ln = _resolve_lanes(self._lookups[name], col)
            lanes[name] = ln
            hits += int((ln >= 0).sum()); lookups += len(ln)
        for name, row_t, col_t in self._mf_meta:
            for side, t in (("/row", row_t), ("/col", col_t)):
                ln = _resolve_lanes(self._lookups[name + side],
                                    np.asarray(ids[t])[lo:hi])
                lanes[name + side] = ln
                hits += int((ln >= 0).sum()); lookups += len(ln)
        return lanes, hits, lookups, store_tables

    def score(self, features: Dict[str, np.ndarray],
              ids: Optional[Dict[str, np.ndarray]] = None,
              ) -> ScoreBatchResult:
        """Margins for a request batch of any size (chunked at max_batch)."""
        ids = ids or {}
        n = self.validate_request(features, ids)
        out = np.empty(n, np.float64)
        buckets: List[int] = []
        hits = lookups = 0
        compiles0 = self.bucket_compiles
        for lo in range(0, n, self.max_batch):
            hi = min(lo + self.max_batch, n)
            m = hi - lo
            bucket = min(max(int(ceil_pow2(m)), self.min_bucket),
                         self.max_batch)
            pad = bucket - m
            xs = {}
            for shard in self.feature_shards:
                x = np.asarray(features[shard])[lo:hi]
                xs[shard] = (x if pad == 0 else
                             np.pad(x, ((0, pad), (0, 0))))
            lanes, h, lk, store_tables = self._lanes_for_chunk(ids, lo, hi)
            if pad:
                lanes = {k: np.pad(v, (0, pad), constant_values=-1)
                         for k, v in lanes.items()}
            hits += h; lookups += lk
            buckets.append(bucket)
            z = self._run_bucket(xs, lanes, bucket,
                                 store_tables=store_tables)
            out[lo:hi] = np.asarray(z)[:m]
        return ScoreBatchResult(
            scores=out, num_rows=n, buckets=buckets,
            entity_lookups=lookups, entity_hits=hits,
            new_compiles=self.bucket_compiles - compiles0)

    def score_margins(self, features: Dict[str, np.ndarray],
                      ids: Optional[Dict[str, np.ndarray]] = None,
                      ) -> Dict[str, np.ndarray]:
        """Per-coordinate margins for a request batch (chunked at
        max_batch like `score`), keyed by coordinate name in
        `coordinate_meta()` order — one sharded replica's leg of a
        fan-out request.  Margins keep the device program's COMPUTE
        dtype (the merge fold must run in it to reproduce the on-device
        add chain bit-for-bit; `score` casts to f64 only at the end).
        Unowned/unseen entities resolve to lane -1 and contribute
        exactly 0.0, so the merge can fold any leg's margin for a
        coordinate the leg does not own without perturbing bits."""
        ids = ids or {}
        n = self.validate_request(features, ids)
        meta = self.coordinate_meta()
        out = {m["name"]: np.empty(n, np.dtype(self._dtype)) for m in meta}
        for lo in range(0, n, self.max_batch):
            hi = min(lo + self.max_batch, n)
            m = hi - lo
            bucket = min(max(int(ceil_pow2(m)), self.min_bucket),
                         self.max_batch)
            pad = bucket - m
            xs = {}
            for shard in self.feature_shards:
                x = np.asarray(features[shard])[lo:hi]
                xs[shard] = (x if pad == 0 else
                             np.pad(x, ((0, pad), (0, 0))))
            lanes, _h, _lk, store_tables = self._lanes_for_chunk(ids, lo, hi)
            if pad:
                lanes = {k: np.pad(v, (0, pad), constant_values=-1)
                         for k, v in lanes.items()}
            margins = self._run_bucket(xs, lanes, bucket,
                                       store_tables=store_tables,
                                       margins=True)
            for cm, z in zip(meta, margins):
                out[cm["name"]][lo:hi] = np.asarray(z)[:m]
        return out

    # -- entity-sharded serving (fleet/shards.py) --------------------------

    def shard_info(self) -> Optional[Dict[str, object]]:
        """This scorer's shard identity + owned-row counts (the /healthz
        and probe surface the front groups replicas by); None when the
        scorer holds the full model."""
        if self.shard is None:
            return None
        return {**self.shard.to_dict(),
                "owned_rows": {name: self._logical_rows[name]
                               for name, _s, _t in self._re_meta},
                "rows_dropped": self.shard_rows_dropped}

    def shard_table_hashes(self, spec, shard_index: int) -> Dict[str, str]:
        """The per-shard audit on a FULL (publisher) scorer: sha256 of
        every lane's rows FILTERED to `shard_index`'s owned entities
        (original row order) — exactly the bytes a converged shard
        replica's `table_hashes()` reports, since its resident table IS
        that filtered slice.  FE/MF lanes replicate in full and hash
        unfiltered."""
        import hashlib
        if self.shard is not None:
            raise ValueError("shard_table_hashes audits the FULL model; "
                             "this scorer already holds only shard "
                             f"{self.shard.index}")
        full = self.table_hashes()
        out: Dict[str, str] = {}
        for name, _shard in self._fe_meta:
            out[name] = full[name]
        for name, _shard, _re_type in self._re_meta:
            st = self._stores.get(name)
            table = (np.asarray(st.full_table()) if st is not None
                     else np.asarray(self._tables[self._table_slot[name]]))
            mask = spec.owned_mask(self._entity_ids[name], shard_index)
            out[name] = hashlib.sha256(
                np.ascontiguousarray(table[mask]).tobytes()).hexdigest()
        for name, _row_t, _col_t in self._mf_meta:
            for side in ("/row", "/col"):
                out[name + side] = full[name + side]
        return out

    def mean_prediction(self, scores: np.ndarray,
                        offsets: Optional[np.ndarray] = None) -> np.ndarray:
        """Inverse link over margins (+ offsets), like GameModel.predict."""
        if self._loss is None:
            raise ValueError(
                f"task {self.model.task_type!r} has no mean function")
        z = np.asarray(scores, np.float64)
        if offsets is not None:
            z = z + np.asarray(offsets, np.float64)
        return np.asarray(self._loss.mean(jnp.asarray(z)))

    def requests_from_dataset(self, dataset, rows: np.ndarray
                              ) -> Tuple[Dict[str, np.ndarray],
                                         Dict[str, np.ndarray]]:
        """Slice a GameDataset into (features, ids) request form — raw ids
        recovered through the dataset vocab; rows whose entity index is -1
        get a sentinel id no model contains (they stay fixed-effect-only).
        Sparse shards densify per request slice (serving requests are
        small dense rows by construction)."""
        def slice_rows(x):
            if hasattr(x, "tocsr"):  # scipy sparse shard
                return np.asarray(x.tocsr()[rows].todense())
            return np.asarray(x)[rows]

        feats = {s: slice_rows(dataset.feature_shards[s])
                 for s in self.feature_shards}
        ids = {}
        for t in self.entity_types:
            idx = np.asarray(dataset.entity_indices[t])[rows]
            vocab = np.asarray(dataset.entity_vocabs[t], dtype=object)
            raw = vocab[np.maximum(idx, 0)].copy()
            raw[idx < 0] = "\0__unseen__"
            ids[t] = raw
        return feats, ids
