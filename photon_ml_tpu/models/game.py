"""GAME model containers: fixed-effect, random-effect, and the composite.

reference:
  - DatumScoringModel (photon-lib/.../model/DatumScoringModel.scala:32-52)
  - GameModel (photon-lib/.../model/GameModel.scala:32-168): coordinate map,
    total score = sum of sub-scores, consistent task check
  - FixedEffectModel (photon-api/.../model/FixedEffectModel.scala:31)
  - RandomEffectModel (photon-api/.../model/RandomEffectModel.scala:38-290)
  - RandomEffectModelInProjectedSpace (.../RandomEffectModelInProjectedSpace.scala)

Scoring semantics follow the reference: a model's score is ITS margin
contribution only (no base offset — evaluators add score+offset,
Evaluator.scala:35-45), and rows whose entity is unknown to a random-effect
model contribute 0 (the reference's missing-score default).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.data.game_data import GameDataset
from photon_ml_tpu.models.glm import GeneralizedLinearModel
from photon_ml_tpu.ops import losses as L
from photon_ml_tpu.parallel.random_effect import score_by_entity


from photon_ml_tpu.parallel.mesh import pad_and_shard_rows as _sharded_rows


@dataclasses.dataclass
class FixedEffectModel:
    """One global GLM bound to a feature shard (reference:
    FixedEffectModel.scala — the Broadcast wrapper is obsolete: coefficients
    are just a device array, replicated by sharding when distributed)."""

    glm: GeneralizedLinearModel
    feature_shard: str

    @property
    def task_type(self) -> str:
        return type(self.glm).task_type

    def score_dataset(self, dataset: GameDataset, mesh=None) -> jax.Array:
        x = dataset.device_shard(self.feature_shard)
        if mesh is not None:
            from photon_ml_tpu.parallel.fixed_effect import score_fixed_effect
            # key the staged sharded design matrix per (dataset, shard):
            # repeated rescoring (every coordinate update touches the
            # validation set) re-transfers nothing
            return score_fixed_effect(
                self.glm, x, mesh,
                residency_key=("score", id(dataset), self.feature_shard))
        return self.glm.compute_score(x)

    def summary(self) -> str:
        c = self.glm.coefficients.means
        return (f"FixedEffectModel(shard={self.feature_shard}, dim={c.shape[-1]}, "
                f"|w|={float(jnp.linalg.norm(c)):.4g})")


def _lanes_for(dataset: GameDataset, re_type: str,
               entity_ids: np.ndarray) -> np.ndarray:
    """Map the dataset's entity-index column to model lanes by raw id — the
    static-gather replacement for the reference's data-keyBy(REId) ⋈ model
    join (RandomEffectModel.scala:256)."""
    vocab = dataset.entity_vocabs[re_type]
    lookup = {v: i for i, v in enumerate(entity_ids.tolist())}
    vocab_to_lane = np.asarray([lookup.get(v, -1) for v in vocab.tolist()],
                               dtype=np.int64)
    idx = dataset.entity_indices[re_type]
    return np.where(idx >= 0, vocab_to_lane[np.maximum(idx, 0)], -1)


def _device_lanes(dataset: GameDataset, re_type: str,
                  entity_ids: np.ndarray) -> jax.Array:
    """_lanes_for on device, memoized per (dataset, entity vocabulary): the
    lane map is identical across every update's rescoring (models are
    rebuilt per update but share the entity_ids array)."""
    key = ("lanes", re_type)
    hit = dataset._scoring_cache.get(key)
    if hit is not None and hit[0] is entity_ids:
        return hit[1]
    lanes = jnp.asarray(_lanes_for(dataset, re_type, entity_ids))
    dataset._scoring_cache[key] = (entity_ids, lanes)
    return lanes


@dataclasses.dataclass
class RandomEffectModel:
    """Per-entity coefficients in a (possibly projected) local space.

    Like the reference's RandomEffectModelInProjectedSpace, the model stores
    compact local-space coefficients plus the projection back to the global
    shard space; entity identity is carried as raw id strings so the model
    scores datasets with different vocabularies (reference keys the model
    RDD by REId for the same reason)."""

    random_effect_type: str
    feature_shard: str
    task_type: str
    coefficients: jax.Array               # [E, d_local]
    entity_ids: np.ndarray                # [E] raw entity id values
    projection: Optional[np.ndarray]      # [E, d_local] global cols, -1 pad
    global_dim: int
    variances: Optional[jax.Array] = None  # [E, d_local]
    # dense shared random-projection matrix [d_local, d_global] (reference:
    # ProjectionMatrixBroadcast) — exclusive with the index `projection`
    projection_matrix: Optional[jax.Array] = None

    def __post_init__(self):
        # device-resident once: scoring runs every coordinate-descent update
        if self.projection_matrix is not None:
            self.projection_matrix = jnp.asarray(self.projection_matrix)

    @property
    def num_entities(self) -> int:
        return len(self.entity_ids)

    def global_coefficients(self) -> jax.Array:
        """[E, d_global] via scatter (reference:
        IndexMapProjectorRDD.projectCoefficientsRDD) or dense P^T c
        (reference: ProjectionMatrixBroadcast.projectCoefficientsRDD)."""
        if self.projection_matrix is not None:
            return self.coefficients @ self.projection_matrix
        from photon_ml_tpu.parallel.random_effect import scatter_local_to_global
        return scatter_local_to_global(self.coefficients, self.projection,
                                       self.global_dim)

    def lanes_for(self, dataset: GameDataset) -> np.ndarray:
        return _lanes_for(dataset, self.random_effect_type, self.entity_ids)

    def _device_lanes(self, dataset: GameDataset) -> jax.Array:
        return _device_lanes(dataset, self.random_effect_type,
                             self.entity_ids)

    def score_dataset(self, dataset: GameDataset, mesh=None) -> jax.Array:
        from photon_ml_tpu.parallel.random_effect import (
            score_entities_matmul, score_entities_plain,
            score_entities_scatter)
        x = dataset.device_shard(self.feature_shard)
        lanes = self._device_lanes(dataset)
        if mesh is not None:
            n, (x, lanes) = _sharded_rows(
                mesh, x, lanes,
                residency_key=("score", id(dataset), self.feature_shard))
            return score_by_entity(self.global_coefficients(), x, lanes)[:n]
        # single fused program per shape (projection + gather + dot): one
        # compile and one dispatch, not one per op
        if self.projection_matrix is not None:
            return score_entities_matmul(self.coefficients,
                                         self.projection_matrix, x, lanes)
        if self.projection is not None:
            key = ("proj", self.random_effect_type)
            hit = dataset._scoring_cache.get(key)
            if hit is None or hit[0] is not self.projection:
                hit = (self.projection, jnp.asarray(self.projection))
                dataset._scoring_cache[key] = hit
            return score_entities_scatter(self.coefficients, hit[1], x,
                                          lanes, global_dim=self.global_dim)
        return score_entities_plain(self.coefficients, x, lanes)

    def summary(self) -> str:
        return (f"RandomEffectModel(type={self.random_effect_type}, "
                f"shard={self.feature_shard}, entities={self.num_entities}, "
                f"local_dim={self.coefficients.shape[-1]})")


@dataclasses.dataclass
class FactoredRandomEffectModel:
    """Per-entity latent factors [E, k] + a shared latent projection [k, d].

    reference: FactoredRandomEffectModel (photon-api/.../model/
    FactoredRandomEffectModel.scala:33) = modelsInProjectedSpace +
    ProjectionMatrixBroadcast.  Effective per-entity coefficients in the
    original shard space are C @ P — computed lazily for scoring (a single
    [E,k]x[k,d] MXU matmul instead of the reference's per-entity
    projectCoefficients map)."""

    random_effect_type: str
    feature_shard: str
    task_type: str
    latent_coefficients: jax.Array        # [E, k]
    projection: jax.Array                 # [k, d_global]
    entity_ids: np.ndarray                # [E] raw entity id values
    global_dim: int

    @property
    def num_entities(self) -> int:
        return len(self.entity_ids)

    @property
    def latent_dim(self) -> int:
        return self.latent_coefficients.shape[1]

    def global_coefficients(self) -> jax.Array:
        return jnp.matmul(self.latent_coefficients, self.projection,
                          precision=jax.lax.Precision.HIGHEST)

    def to_random_effect_model(self) -> RandomEffectModel:
        """Original-space view (reference: FactoredRandomEffectModel
        .toRandomEffectModel)."""
        return RandomEffectModel(
            random_effect_type=self.random_effect_type,
            feature_shard=self.feature_shard, task_type=self.task_type,
            coefficients=self.global_coefficients(), entity_ids=self.entity_ids,
            projection=None, global_dim=self.global_dim)

    def score_dataset(self, dataset: GameDataset, mesh=None) -> jax.Array:
        if mesh is None:
            from photon_ml_tpu.parallel.random_effect import \
                score_entities_matmul
            return score_entities_matmul(
                self.latent_coefficients, self.projection,
                dataset.device_shard(self.feature_shard),
                _device_lanes(dataset, self.random_effect_type,
                              self.entity_ids))
        return self.to_random_effect_model().score_dataset(dataset, mesh)

    def summary(self) -> str:
        return (f"FactoredRandomEffectModel(type={self.random_effect_type}, "
                f"shard={self.feature_shard}, entities={self.num_entities}, "
                f"latent_dim={self.latent_dim})")


@dataclasses.dataclass
class MatrixFactorizationModel:
    """score(row, col) = rowFactor . colFactor.

    reference: MatrixFactorizationModel (photon-api/.../model/
    MatrixFactorizationModel.scala:36-291) — RDDs of (id, Vector) latent
    factors; here two dense [*, k] arrays + host-side id arrays.  Like the
    reference (modelType = TaskType.NONE), this model is task-agnostic:
    task_type "none" is exempt from GameModel's consistency check."""

    row_effect_type: str
    col_effect_type: str
    row_factors: jax.Array                # [R, k]
    row_ids: np.ndarray                   # [R] raw entity id values
    col_factors: jax.Array                # [C, k]
    col_ids: np.ndarray                   # [C] raw entity id values
    task_type: str = "none"

    @property
    def num_latent_factors(self) -> int:
        """reference: MatrixFactorizationModel.numLatentFactors."""
        if self.row_factors.shape[0]:
            return self.row_factors.shape[1]
        if self.col_factors.shape[0]:
            return self.col_factors.shape[1]
        return 0

    @staticmethod
    def _lanes(dataset: GameDataset, effect_type: str, ids: np.ndarray) -> np.ndarray:
        vocab = dataset.entity_vocabs[effect_type]
        lookup = {v: i for i, v in enumerate(ids.tolist())}
        vocab_to_lane = np.asarray([lookup.get(v, -1) for v in vocab.tolist()],
                                   dtype=np.int64)
        idx = dataset.entity_indices[effect_type]
        return np.where(idx >= 0, vocab_to_lane[np.maximum(idx, 0)], -1)

    def score_dataset(self, dataset: GameDataset, mesh=None) -> jax.Array:
        """rowFactor.colFactor per row; either side unseen -> 0 (reference:
        MatrixFactorizationModel.score inner join — missing pairs default)."""
        rl = jnp.asarray(self._lanes(dataset, self.row_effect_type, self.row_ids))
        cl = jnp.asarray(self._lanes(dataset, self.col_effect_type, self.col_ids))
        n = rl.shape[0]
        if mesh is not None:
            # pad with -1 (unseen) so padding rows score 0
            n, (rl, cl) = _sharded_rows(mesh, rl + 1, cl + 1)
            rl, cl = rl - 1, cl - 1
        ok = (rl >= 0) & (cl >= 0)
        rf = self.row_factors[jnp.maximum(rl, 0)]
        cf = self.col_factors[jnp.maximum(cl, 0)]
        return jnp.where(ok, jnp.sum(rf * cf, axis=-1), 0.0)[:n]

    @staticmethod
    def from_factored(model: FactoredRandomEffectModel,
                      col_effect_type: str,
                      col_ids: np.ndarray) -> "MatrixFactorizationModel":
        """When the factored RE's feature shard is a one-hot indicator of a
        second entity (no intercept), c_e . (P x) == c_e . P[:, col]: rows
        are the RE entities, columns are the projection's columns."""
        if len(col_ids) != model.projection.shape[1]:
            raise ValueError(
                f"col_ids has {len(col_ids)} entries but the projection has "
                f"{model.projection.shape[1]} columns — the feature shard "
                "must be a one-hot column indicator")
        return MatrixFactorizationModel(
            row_effect_type=model.random_effect_type,
            col_effect_type=col_effect_type,
            row_factors=model.latent_coefficients,
            row_ids=model.entity_ids,
            col_factors=model.projection.T,
            col_ids=np.asarray(col_ids))

    def summary(self) -> str:
        return (f"MatrixFactorizationModel(rows={self.row_effect_type}x"
                f"{len(self.row_ids)}, cols={self.col_effect_type}x"
                f"{len(self.col_ids)}, k={self.num_latent_factors})")


CoordinateModel = (FixedEffectModel | RandomEffectModel
                   | FactoredRandomEffectModel | MatrixFactorizationModel)


@dataclasses.dataclass
class GameModel:
    """Ordered coordinate -> model map; total score is the sum.

    reference: GameModel.scala:32-168 incl. the consistent-task check
    (line 163)."""

    coordinates: Dict[str, CoordinateModel]
    task_type: str

    def __post_init__(self):
        for name, m in self.coordinates.items():
            # "none" = task-agnostic (matrix factorization; reference sets
            # modelType = TaskType.NONE for it, MatrixFactorizationModel.scala)
            if m.task_type not in (self.task_type, "none"):
                raise ValueError(
                    f"coordinate {name!r} has task {m.task_type!r}, "
                    f"expected {self.task_type!r} (reference: GameModel task "
                    "consistency check)")

    @property
    def loss(self) -> L.PointwiseLoss:
        return L.TASK_LOSSES[self.task_type]

    def score_dataset(self, dataset: GameDataset, mesh=None) -> jax.Array:
        """Sum of coordinate margins (reference: GameModel.scala:101-112).
        With a mesh, every coordinate scores row-sharded over the data axis
        (the reference's scoring driver is always distributed)."""
        total = jnp.zeros(dataset.num_rows)
        for m in self.coordinates.values():
            total = total + m.score_dataset(dataset, mesh)
        return total

    def predict(self, dataset: GameDataset, mesh=None) -> jax.Array:
        z = self.score_dataset(dataset, mesh)
        if dataset.offsets is not None:
            z = z + jnp.asarray(dataset.offsets)
        return self.loss.mean(z)

    def summary(self) -> str:
        lines = [f"GameModel(task={self.task_type})"]
        lines += [f"  {name}: {m.summary()}" for name, m in self.coordinates.items()]
        return "\n".join(lines)
