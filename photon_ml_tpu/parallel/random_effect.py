"""Random-effect training: vmapped per-entity solves, sharded over entities.

Rebuild of strategy P2 (SURVEY §2.14) — the hard redesign.  The reference
holds `RDD[(REId, LocalDataSet)]` co-partitioned with one optimizer instance
and one GLM per entity, and runs a *local* Breeze solve per entity inside
executor tasks (reference: RandomEffectCoordinate.scala:96-110,
RandomEffectOptimizationProblem.scala:41, SingleNodeOptimizationProblem
.scala:38).  That task-parallel, ragged formulation is hostile to TPUs.

TPU design: entities are grouped at data-prep time into PADDED dense blocks
  x[E, S, d], labels[E, S], mask[E, S]
(S = per-bucket max sample count, capped by the reference's activeData upper
bound, RandomEffectDataConfiguration), and the ENTIRE per-entity LBFGS/TRON
solve runs under vmap: one batched XLA program performing E independent
optimizations in lockstep, sharded over the mesh "data" axis.  Masked rows
contribute nothing (aggregators use where()); entities finish at different
iterations via the while_loop's per-lane convergence flags.  d here is the
per-entity PROJECTED dimension (reference IndexMapProjector, §2.6): the data
layer gathers each entity's observed features into a dense local space, which
is what makes [E, S, d] compact.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from photon_ml_tpu.ops import GLMObjective
from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.optim import OptimizerConfig, RegularizationContext, SolveResult, solve
from photon_ml_tpu.optim.types import LOCKSTEP
from photon_ml_tpu.telemetry import annotate


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class EntityBlocks:
    """Padded per-entity batches — the TPU replacement for
    RDD[(REId, LocalDataSet)] (reference: RandomEffectDataSet.scala:47).

    `entity_mask` marks real (vs padding) entities; `num_samples[e]` counts
    real rows.  Entity ids live host-side in the data layer's
    RandomEffectDataset, not here — blocks are pure device data.
    """

    x: jax.Array                    # [E, S, d]
    labels: jax.Array               # [E, S]
    mask: jax.Array                 # [E, S] 1.0 = real row
    weights: Optional[jax.Array] = None   # [E, S]
    offsets: Optional[jax.Array] = None   # [E, S]

    def tree_flatten(self):
        return (self.x, self.labels, self.mask, self.weights, self.offsets), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def num_entities(self) -> int:
        return self.x.shape[0]

    @property
    def samples_per_entity(self) -> int:
        return self.x.shape[1]

    @property
    def dim(self) -> int:
        return self.x.shape[2]

    @property
    def entity_mask(self) -> jax.Array:
        return (jnp.sum(self.mask, axis=1) > 0).astype(self.x.dtype)

    def with_offsets(self, offsets: jax.Array) -> "EntityBlocks":
        """Residual exchange for coordinate descent (reference:
        DataSet.addScoresToOffsets) — an array assignment, not a shuffle."""
        return dataclasses.replace(self, offsets=offsets)


@functools.lru_cache(maxsize=64)
def _cached_batched_solver(loss: PointwiseLoss, config: OptimizerConfig,
                           reg: RegularizationContext, has_weights: bool,
                           has_offsets: bool, donate: bool = False):
    """Persistent jit-of-vmap per static signature: coordinate-descent
    iterations reuse the compiled batched solve instead of retracing.

    `donate=True` donates x0 [E, d] so the solution aliases its buffer in
    place instead of allocating a fresh coefficient block every coordinate
    update (offsets/feature blocks have no same-shaped output to alias, so
    donating them would free nothing and warn).  A donated x0 is CONSUMED:
    callers must pass a buffer nothing else references (see
    fit_random_effects/donate_buffers).

    The solve `budget` is an UNMAPPED traced operand (one cap/tolerance
    shared by every vmapped entity solve, like the lambda), so a
    per-outer-iteration budget schedule reuses this one compiled program.

    The jitted function's name is the program's: the XLA module is
    `jit_re_bucket_solve`, which is how a profiler trace tells this layer's
    device time from every other program's (see `_cached_solver`).

    An L-BFGS/OWLQN program also counts its own lock step: `lockstep` of
    its result is one int32 row of LOCKSTEP's columns, reduced over the
    lanes inside the program (`_count_lock_step`), so a reader fetches a
    few scalars a run and no [E] array."""

    def solve_one(x, labels, mask, weights, offsets, x0_e, lam, budget):
        obj = GLMObjective(loss, x, labels, weights=weights, offsets=offsets,
                           mask=mask)
        return solve(obj, x0_e, config, reg, lam, budget=budget,
                     lane_axis=_LANES)

    lanes = jax.vmap(
        solve_one, in_axes=(0, 0, 0, 0 if has_weights else None,
                            0 if has_offsets else None, 0, None, None),
        axis_name=_LANES)

    def re_bucket_solve(x, labels, mask, weights, offsets, x0, lam, budget):
        res = lanes(x, labels, mask, weights, offsets, x0, lam, budget)
        if res.lockstep is None:
            return res
        return res._replace(lockstep=_count_lock_step(res, mask))

    return jax.jit(re_bucket_solve, donate_argnums=(5,) if donate else ())


#: the vmap axis of a batched per-entity solve (optim/lbfgs.py `lane_axis`)
_LANES = "lanes"


def _count_lock_step(res: SolveResult, mask: jax.Array) -> jax.Array:
    """[1, len(LOCKSTEP)] int32: the run's row of lock-step counts from its
    lanes' results.  A lane with no row (mesh padding) runs as every lane
    does but is left out of `lanes` and `lane_iterations`; it ends at its
    first trip with one trial, so it raises no maximum."""
    real = jnp.any(mask > 0, axis=1)
    E, S = mask.shape
    ran, needed, passes = (jnp.max(lane) for lane in res.lockstep)
    counts = dict(
        entities=E, samples=S, lanes=jnp.sum(real),
        trips=jnp.max(res.iterations),
        lane_iterations=jnp.sum(jnp.where(real, res.iterations, 0)),
        lockstep_trials=ran, running_trials=needed, data_passes=passes)
    return jnp.stack([jnp.asarray(counts[k], jnp.int32)
                      for k in LOCKSTEP])[None]


def fit_random_effects(
    blocks: EntityBlocks,
    loss: PointwiseLoss,
    mesh: Optional[Mesh] = None,
    x0: Optional[jax.Array] = None,
    config: OptimizerConfig = OptimizerConfig(),
    reg: RegularizationContext = RegularizationContext(),
    reg_weight: jax.Array | float = 0.0,
    donate_buffers: bool = False,
    budget=None,
    cache_key=None,
) -> SolveResult:
    """All per-entity solves as one batched program.

    Returns a SolveResult whose leaves have a leading [E] axis
    (x: [E, d], value: [E], ...).  The reference analogue is the 3-way join +
    per-entity local optimize in RandomEffectCoordinate.updateModel
    (RandomEffectCoordinate.scala:96-110); the regularization-weight plumbing
    matches RandomEffectOptimizationProblem (one lambda shared by all
    entities).

    `donate_buffers=True` donates `x0` to the solve: the buffer is
    CONSUMED (reading it afterwards raises) and the solution reuses it in
    place.  Only pass it when x0 is not referenced elsewhere — the
    coordinate-descent update path qualifies because it copy-guards x0.
    Ignored on the mesh path (device_put can alias its input, so donation
    there could consume a caller-held array).
    """
    E, S, d = blocks.x.shape
    dtype = blocks.x.dtype
    if x0 is None:
        x0 = jnp.zeros((E, d), dtype)
    lam = jnp.asarray(reg_weight, dtype)

    batched = _cached_batched_solver(loss, config, reg,
                                     blocks.weights is not None,
                                     blocks.offsets is not None,
                                     donate=donate_buffers and mesh is None)
    if mesh is None:
        return batched(blocks.x, blocks.labels, blocks.mask,
                       blocks.weights, blocks.offsets, x0, lam, budget)

    # auto-pad the entity axis to a mesh multiple with all-masked lanes
    # (real datasets are rarely device-count multiples); results sliced back.
    # The padded + device_put STATIC blocks (x/labels/mask/weights) stage
    # through the mesh residency layer — one sharded copy per coordinate
    # key, identity-guarded, invalidated per coordinate (game/residency.py
    # eviction hook) — so a warm visit moves only offsets and x0.  A
    # factored coordinate's latent blocks change x every alternation
    # (project_blocks with a refit P): only that field re-stages; its
    # labels/mask/weights entries still hit.
    from photon_ml_tpu.parallel.mesh import DATA_AXIS
    from photon_ml_tpu.parallel.mesh_residency import default_residency
    res_reg = default_residency()
    pad_e = (-E) % mesh.shape[DATA_AXIS]
    key = (cache_key if cache_key is not None
           else ("fit_random_effects", id(blocks.x)))
    with annotate("re/stage_static"):
        x_dev = res_reg.stage_static(key, "x", mesh, blocks.x, 0.0)
        labels_dev = res_reg.stage_static(key, "labels", mesh, blocks.labels,
                                          0.5)
        mask_dev = res_reg.stage_static(key, "mask", mesh, blocks.mask, 0.0)
        weights_dev = res_reg.stage_static(key, "weights", mesh,
                                           blocks.weights, 0.0)
    with annotate("re/stage_update"):
        offsets_dev = res_reg.stage_update(mesh, blocks.offsets, 0.0, key=key,
                                           field="offsets")
        x0_dev = res_reg.stage_update(mesh, x0, 0.0, key=key, field="x0")
    with mesh, annotate("re/dispatch"):
        res = batched(x_dev, labels_dev, mask_dev, weights_dev, offsets_dev,
                      x0_dev, lam, budget)
    if pad_e:
        res = jax.tree_util.tree_map(lambda a: a[:E], res)
    return res


def scatter_local_to_global(coefficients: jax.Array, projection,
                            global_dim: int) -> jax.Array:
    """[E, d_local] local-space coefficients -> [E, d_global] by scattering
    along each entity's projection columns (-1 = padding).  Shared by
    RandomEffectDataset and RandomEffectModel (reference:
    IndexMapProjector.projectCoefficients)."""
    if projection is None:
        return coefficients
    E, dl = coefficients.shape
    proj = jnp.asarray(projection)
    rows = jnp.repeat(jnp.arange(E), dl)
    cols = jnp.maximum(proj, 0).reshape(-1)
    vals = jnp.where(proj >= 0, coefficients, 0.0).reshape(-1)
    return jnp.zeros((E, global_dim), coefficients.dtype).at[rows, cols].add(vals)


def score_entity_blocks(coefficients: jax.Array, blocks: EntityBlocks) -> jax.Array:
    """Margins for every (entity, sample) cell: [E, S] = einsum over d.
    Masked cells score 0.  reference: RandomEffectModel scoring of active
    data (RandomEffectCoordinate.scala:148-165)."""
    scores = jnp.einsum("esd,ed->es", blocks.x, coefficients)
    if blocks.offsets is not None:
        scores = scores + blocks.offsets
    return scores * blocks.mask


@functools.partial(jax.jit, static_argnames=("global_dim",))
def score_entities_scatter(coefficients, projection, x, lanes, *,
                           global_dim: int) -> jax.Array:
    """Index-map-projected per-entity scoring, ONE fused program: scatter to
    global space + entity gather + row dot.  Rescoring runs every
    coordinate update — fusing the chain keeps the cold-start cost at one
    program per shape and each rescore at one dispatch."""
    g = scatter_local_to_global(coefficients, projection, global_dim)
    return score_by_entity(g, x, lanes)


@jax.jit
def score_entities_matmul(coefficients, projection_matrix, x,
                          lanes) -> jax.Array:
    """Dense-projection (random-projection / factored-latent) scoring as one
    fused program: [E,k] @ [k,d] then entity gather + row dot.  The small
    product is taken in float32 (`HIGHEST`): at a TPU's default it rounds
    its operands to bfloat16 and every score with them."""
    table = jnp.matmul(coefficients, projection_matrix,
                       precision=jax.lax.Precision.HIGHEST)
    return score_by_entity(table, x, lanes)


@jax.jit
def score_entities_plain(coefficients, x, lanes) -> jax.Array:
    return score_by_entity(coefficients, x, lanes)


def score_by_entity(coefficients: jax.Array, x: jax.Array,
                    entity_index: jax.Array) -> jax.Array:
    """Score flat rows against their entity's model: one gather + row dot.

    This replaces the reference's keyBy(REId) join of data against the model
    RDD (RandomEffectModel.scala:256, passive-data scoring path
    RandomEffectCoordinate.scala:178-210) with a static gather — the shuffle
    was planned away at data-prep time by materializing `entity_index`.
    Rows with entity_index == -1 (unseen entity) score 0, matching the
    reference's missing-score default (Evaluator.scala:35-45).
    """
    num_entities = coefficients.shape[0]
    if num_entities == 0:
        # empty coefficient table (every entity of this type fell below
        # passive_data_lower_bound): all rows are unseen and score 0.  The
        # general path would clip indices to -1 and gather from a
        # zero-length axis — garbage, not zeros.
        return jnp.zeros(x.shape[0], x.dtype)
    in_range = (entity_index >= 0) & (entity_index < num_entities)
    safe_idx = jnp.clip(entity_index, 0, num_entities - 1)
    w = coefficients[safe_idx]                      # [n, d] gather
    s = jnp.sum(x * w, axis=-1)
    return jnp.where(in_range, s, 0.0)


def random_effect_variances(
    blocks: EntityBlocks, loss: PointwiseLoss, coefficients: jax.Array,
    reg: RegularizationContext = RegularizationContext(),
    reg_weight: jax.Array | float = 0.0,
) -> jax.Array:
    """Per-entity coefficient variances via vmapped Hessian diagonals
    (reference: RandomEffectOptimizationProblem variance path).  Pass the
    same reg/reg_weight used for training so the L2 term enters the
    curvature (few-sample entities are otherwise wildly overestimated)."""
    _, l2_w = reg.split(reg_weight)

    def one(x, labels, mask, weights, offsets, c):
        obj = GLMObjective(loss, x, labels, weights=weights, offsets=offsets,
                           mask=mask, l2_weight=l2_w)
        return 1.0 / (obj.hessian_diagonal(c) + 1e-12)

    return jax.vmap(one, in_axes=(0, 0, 0,
                                  None if blocks.weights is None else 0,
                                  None if blocks.offsets is None else 0,
                                  0))(blocks.x, blocks.labels, blocks.mask,
                                      blocks.weights, blocks.offsets, coefficients)
