"""Distributed fixed-effect GLM training: data parallelism over the mesh.

Rebuild of strategy P1 (SURVEY §2.14): the reference splits the batch across
executors, broadcasts coefficients each iteration, and treeAggregates
gradient/Hv (reference: DistributedGLMLossFunction.scala:49-169,
DistributedOptimizationProblem.scala:43-198, ValueAndGradientAggregator
.scala:235-250).

TPU design: the SAME single-device solve from photon_ml_tpu/optim runs
unchanged — the batch arrays are placed with their leading axis sharded over
the mesh's "data" axis and the initial coefficients replicated, and XLA GSPMD
inserts the psum for every batch-reduction inside the jitted while_loop.
There is no distributed-vs-local objective class split and no per-iteration
host involvement: the entire LBFGS/TRON loop (line searches, CG, convergence
checks) executes on-device with ICI collectives.

For very wide models (the reference's >200k-feature regime), pass
`shard_features=True`: coefficient-space arrays shard over the "feature"
axis, gradients arrive reduce-scattered, and the optimizer's dot products
produce the scalar psums — all inserted by GSPMD from the output sharding
constraint.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.glm import GeneralizedLinearModel, model_for_task
from photon_ml_tpu.ops import GLMObjective
from photon_ml_tpu.optim import OptimizerConfig, RegularizationContext, SolveResult, solve
from photon_ml_tpu.optim.admm import ADMMConfig, ADMMOperands, admm_solve
from photon_ml_tpu.parallel.mesh import DATA_AXIS, FEATURE_AXIS, data_sharding, replicated
from photon_ml_tpu.telemetry import annotate


def pad_batch_to_mesh(objective: GLMObjective, mesh: Mesh) -> GLMObjective:
    """Pad rows to a multiple of the data-axis size, masking the padding.

    The reference never pads (Spark handles ragged partitions); XLA needs
    equal shards.  Padded rows get mask=0, which the aggregators exclude via
    where(), and label 0.5 (a value valid for every loss family so no
    inf/nan can arise even before masking)."""
    n_data = mesh.shape[DATA_AXIS]
    n = objective.labels.shape[0]
    rem = (-n) % n_data
    if rem == 0 and objective.mask is not None:
        return objective
    pad = lambda a, v: None if a is None else jnp.concatenate(
        [a, jnp.full((rem,) + a.shape[1:], v, a.dtype)]) if rem else a
    mask = objective.mask if objective.mask is not None else jnp.ones_like(objective.labels)
    from photon_ml_tpu.ops import features as fops
    return objective.replace(
        x=fops.pad_rows(objective.x, rem), labels=pad(objective.labels, 0.5),
        weights=pad(objective.weights, 0.0), offsets=pad(objective.offsets, 0.0),
        mask=pad(mask, 0.0))


def staged_fixed_effect_x(key, mesh: Mesh, x, residency=None):
    """Memoized padded+sharded design matrix for one coordinate: update and
    score share ONE staged copy (keyed per coordinate), so a warm outer
    iteration never re-transfers the feature block.  Returns (n, x_dev).
    A CSC-carrying PaddedSparse drops its column-sorted stream first (the
    row-interleaved order cannot shard over the data axis) — deferred into
    the staging `build` so a cache hit never rebuilds it."""
    from photon_ml_tpu.ops.features import PaddedSparse
    from photon_ml_tpu.parallel.mesh_residency import default_residency
    res = residency if residency is not None else default_residency()
    build = None
    if isinstance(x, PaddedSparse) and x.has_csc and mesh.size > 1:
        build = x.without_csc
    x_dev = res.stage_static(key, "x", mesh, x, 0.0, build=build)
    return x.shape[0], x_dev


def stage_objective(objective: GLMObjective, mesh: Mesh, key,
                    residency=None) -> GLMObjective:
    """The mesh-resident replacement for `shard_objective`: the STATIC
    arrays (design matrix, labels, weights, mask, normalization) are
    padded + sharded ONCE per coordinate through the residency layer —
    keyed by `key`, invalidated per coordinate — and only the residual
    `offsets` stage per visit (counted warm by TransferStats).  Numerics
    match `shard_objective` exactly: same pads (labels 0.5, everything
    else 0, mask marks real rows), same shardings."""
    from photon_ml_tpu.parallel.mesh_residency import default_residency
    res = residency if residency is not None else default_residency()
    labels = objective.labels
    _, x_dev = staged_fixed_effect_x(key, mesh, objective.x, residency=res)
    labels_dev = res.stage_static(key, "labels", mesh, labels, 0.5)
    weights_dev = res.stage_static(key, "weights", mesh, objective.weights,
                                   0.0)
    # mask: anchored on the mask array when one exists, else derived from
    # the labels (ones over real rows, zero padding)
    if objective.mask is not None:
        mask_dev = res.stage_static(key, "mask", mesh, objective.mask, 0.0)
    else:
        mask_dev = res.stage_static(
            key, "mask", mesh, labels, 0.0,
            build=lambda: np.ones(labels.shape[0],
                                  jax.dtypes.canonicalize_dtype(labels.dtype)))
    norm_dev = res.stage_static(key, "norm", mesh, objective.norm,
                                spec="replicated")
    offsets_dev = res.stage_update(mesh, objective.offsets, 0.0, key=key,
                                   field="offsets")
    return objective.replace(
        x=x_dev, labels=labels_dev, weights=weights_dev,
        offsets=offsets_dev, mask=mask_dev, norm=norm_dev,
        l2_weight=objective.l2_weight)


def shard_objective(objective: GLMObjective, mesh: Mesh) -> GLMObjective:
    """Place the batch with rows sharded over "data" (norm ctx replicated)."""
    from photon_ml_tpu.ops.features import PaddedSparse
    if isinstance(objective.x, PaddedSparse) and objective.x.has_csc \
            and mesh.size > 1:
        # the column-sorted gradient stream interleaves rows, so it cannot
        # shard over the data axis; multi-device solves keep the
        # row-shardable per-shard scatter-add + GSPMD psum formulation
        objective = objective.replace(x=objective.x.without_csc())
    objective = pad_batch_to_mesh(objective, mesh)
    batch_spec = lambda a: None if a is None else jax.device_put(
        a, data_sharding(mesh, a.ndim))
    rep = lambda a: None if a is None else jax.device_put(a, replicated(mesh))
    return objective.replace(
        x=batch_spec(objective.x), labels=batch_spec(objective.labels),
        weights=batch_spec(objective.weights), offsets=batch_spec(objective.offsets),
        mask=batch_spec(objective.mask),
        norm=jax.tree_util.tree_map(rep, objective.norm),
        l2_weight=objective.l2_weight)


@functools.lru_cache(maxsize=64)
def _cached_solver(config: OptimizerConfig, reg: RegularizationContext,
                   donate: bool = False):
    """One persistent jit wrapper per (config, reg): repeated calls — e.g.
    every coordinate-descent outer iteration — reuse the XLA executable
    (loss/shape/sharding changes are handled by jit's own pytree cache).

    `donate=True` donates x0 so the solution can reuse its buffer in
    place.  The donated x0 is CONSUMED — callers must pass a buffer
    nothing else references (FixedEffectCoordinate.update copy-guards the
    live model coefficients before donating).

    `budget` (optim.schedule.SolveBudget) rides in as a TRACED operand:
    one program serves every (iteration cap, tolerance) an inexactness
    schedule produces.  budget=None traces the static-config variant — a
    separate cache entry, not a per-budget retrace.

    The function's name is the program's: the XLA module is `jit_fe_solve`,
    which is how a profiler trace tells this layer's device time from
    every other program's (a module name survives the persistent compile
    cache; a `jax.named_scope` inside the program does not)."""

    def fe_solve(obj, x0, lam, budget=None):
        return solve(obj, x0, config, reg, lam, budget=budget)

    return jax.jit(fe_solve, donate_argnums=(1,) if donate else ())


def fit_fixed_effect(
    objective: GLMObjective,
    x0: jax.Array,
    mesh: Mesh,
    config: OptimizerConfig = OptimizerConfig(),
    reg: RegularizationContext = RegularizationContext(),
    reg_weight: jax.Array | float = 0.0,
    shard_features: bool = False,
    budget=None,
    residency_key=None,
) -> SolveResult:
    """One distributed fixed-effect solve.  Equivalent in role to
    DistributedOptimizationProblem.run (reference line 103-121).

    With `residency_key` (the coordinate-descent path) the objective's
    static arrays stage through the mesh residency layer: padded + sharded
    ONCE per coordinate, so a warm visit moves only offsets and x0.
    Without it (standalone callers) the legacy per-call `shard_objective`
    runs."""
    with annotate("fe/stage"):
        if residency_key is not None:
            from photon_ml_tpu.parallel.mesh_residency import default_residency
            sharded_obj = stage_objective(objective, mesh, residency_key)
            x0 = default_residency().stage_update(
                mesh, x0, spec="feature" if shard_features else "replicated",
                key=residency_key, field="x0")
        else:
            sharded_obj = shard_objective(objective, mesh)
            coef_sharding = (NamedSharding(mesh, P(FEATURE_AXIS))
                             if shard_features else replicated(mesh))
            x0 = jax.device_put(x0, coef_sharding)
    with mesh, annotate("fe/dispatch"):
        return _cached_solver(config, reg)(sharded_obj, x0,
                                           jnp.asarray(reg_weight, x0.dtype),
                                           budget)


# -- consensus-ADMM lane: column-sharded staging + fit -------------------------

def _grid_view(x, num_feature: int, block_width: int):
    """[n, d] dense design -> [n, F, d_F] column-block grid (zero-padded
    columns).  A pure reshape VIEW when d == F * d_F and the source is
    contiguous host numpy — the common case pays no host copy."""
    n, d = x.shape
    d_pad = num_feature * block_width
    if isinstance(x, np.ndarray):
        if d == d_pad and x.flags.c_contiguous:
            return x.reshape(n, num_feature, block_width)
        out = np.zeros((n, d_pad), x.dtype)
        out[:, :d] = x
        return out.reshape(n, num_feature, block_width)
    x = jnp.asarray(x)
    if d != d_pad:
        x = jnp.pad(x, ((0, 0), (0, d_pad - d)))
    return x.reshape(n, num_feature, block_width)


def _fold_x0(x0, num_feature: int, block_width: int):
    """[d] warm start -> [F, d_F] shard grid (zero-padded tail)."""
    d = x0.shape[0]
    d_pad = num_feature * block_width
    if isinstance(x0, np.ndarray):
        out = np.zeros(d_pad, x0.dtype)
        out[:d] = x0
        return out.reshape(num_feature, block_width)
    x0 = jnp.asarray(x0)
    if d != d_pad:
        x0 = jnp.pad(x0, (0, d_pad - d))
    return x0.reshape(num_feature, block_width)


@functools.lru_cache(maxsize=16)
def _cached_gram_eig(mesh: Mesh):
    """Per-shard Gram eigendecomposition G_j = Q_j diag(lam_j) Q_j^T from
    the staged design grid — the transpose-reduction cache that makes the
    ADMM w-update closed form for ANY traced shift.  The Gram itself is
    never stored: only (Q, lam), [F, d_F, d_F] + [F, d_F] sharded over
    "feature" (out_shardings pin this so per-device aggregator memory is
    d_F^2, shrinking quadratically as the feature axis widens:
    tests/test_admm.py::test_per_device_aggregator_shrinks_with_feature_axis).
    Unweighted by construction, so downsampling / per-visit weights never
    invalidate it (they only reweight the z-prox)."""
    out_sh = (NamedSharding(mesh, P(FEATURE_AXIS, None, None)),
              NamedSharding(mesh, P(FEATURE_AXIS, None)))

    def gram_eig(x_grid):
        gram = jnp.einsum("nfa,nfb->fab", x_grid, x_grid)
        lam, q = jnp.linalg.eigh(gram)
        return q, lam

    return jax.jit(gram_eig, out_shardings=out_sh)


def stage_admm_grid(key, mesh: Mesh, x, residency=None):
    """Memoized column-block grid for one coordinate: update and score
    share ONE staged [n_pad, F, d_F] copy (field "x_grid", spec "grid"),
    the ADMM analogue of `staged_fixed_effect_x`.  Returns
    (n, d, block_width, x_grid)."""
    from photon_ml_tpu.parallel.mesh_residency import default_residency
    res = residency if residency is not None else default_residency()
    num_feature = mesh.shape[FEATURE_AXIS]
    n, d = x.shape
    block_width = -(-d // num_feature)
    x_grid = res.stage_static(
        key, "x_grid", mesh, x, 0.0, spec="grid",
        build=lambda: _grid_view(x, num_feature, block_width))
    return n, d, block_width, x_grid


def _stage_admm_operands(objective: GLMObjective, mesh: Mesh, key,
                         residency=None):
    """Stage the ADMM lane's device operands through the residency layer:
    the column-block grid + its Gram eigendecomposition cold (once per
    (coordinate, mesh); derived compute under the "admm.stage" fault
    site), labels/weights/mask via the SAME fields the monolithic lane
    stages (shared cold entries), offsets warm per visit.  Returns
    (ADMMOperands-without-reg-weights as a dict, n, d, d_F)."""
    from photon_ml_tpu.parallel.mesh_residency import default_residency
    res = residency if residency is not None else default_residency()
    labels = objective.labels
    n, d, block_width, x_grid = stage_admm_grid(key, mesh, objective.x,
                                                residency=res)
    q_eig, lam_eig = res.stage_derived(
        key, "gram_eig", mesh, x_grid,
        lambda: _cached_gram_eig(mesh)(x_grid))
    labels_dev = res.stage_static(key, "labels", mesh, labels, 0.5)
    weights_dev = res.stage_static(key, "weights", mesh, objective.weights,
                                   0.0)
    if objective.mask is not None:
        mask_dev = res.stage_static(key, "mask", mesh, objective.mask, 0.0)
    else:
        mask_dev = res.stage_static(
            key, "mask", mesh, labels, 0.0,
            build=lambda: np.ones(labels.shape[0],
                                  jax.dtypes.canonicalize_dtype(labels.dtype)))
    offsets_dev = res.stage_update(mesh, objective.offsets, 0.0, key=key,
                                   field="offsets")
    return dict(x_grid=x_grid, q_eig=q_eig, lam_eig=lam_eig,
                labels=labels_dev, weights=weights_dev, mask=mask_dev,
                offsets=offsets_dev), n, d, block_width


@functools.lru_cache(maxsize=4)
def _cached_kappa():
    # weights * mask fused once per visit (tiny [n] product; padded and
    # downsampled-out rows land at exactly 0 so the z-prox ignores them)
    return jax.jit(lambda w, m: m if w is None else w * m)


def fit_fixed_effect_admm(
    objective: GLMObjective,
    x0: jax.Array,
    mesh: Mesh,
    admm_config: ADMMConfig = ADMMConfig(),
    config: OptimizerConfig = OptimizerConfig(),
    reg: RegularizationContext = RegularizationContext(),
    reg_weight: jax.Array | float = 0.0,
    budget=None,
    polish_budget=None,
    polish: Optional[bool] = None,
    residency_key=None,
) -> SolveResult:
    """One feature-sharded fixed-effect solve on the consensus-ADMM lane
    (optim/admm.py): the design grid column-shards over the mesh's
    "feature" axis AND row-shards over "data" (2-D SPMD), per-shard
    aggregators (Gram eigenbases) stay feature-local, and each iteration
    costs one feature-axis vector psum + one data-axis block psum.

    Requires a DENSE 2-D design block and no normalization context —
    callers (FixedEffectCoordinate) fall back to the monolithic lane
    otherwise.  `budget` follows the SolveBudget discipline for the ADMM
    iterations; `polish` (default: the config's flag) runs the strict
    monolithic solver once afterwards, warm-started from the consensus
    solution under `polish_budget` (None = the optimizer config's statics)
    — exact parity with the host-stepped lane, at the cost of re-staging
    the unsplit design and replicating the full [d] iterate.  Wide-model
    callers set polish=False."""
    if not isinstance(objective.x, (np.ndarray, jnp.ndarray, jax.Array)) \
            or np.ndim(objective.x) != 2:
        raise ValueError(
            "the ADMM lane needs a dense 2-D design block; sparse / "
            "structured FeatureMatrix coordinates use the monolithic lane")
    if objective.norm is not None:
        raise ValueError(
            "the ADMM lane does not compose with normalization contexts "
            "(per-shard Gram caching assumes raw columns); normalize the "
            "data or use the monolithic lane")
    if config.box_lower is not None or config.box_upper is not None \
            or config.constraints is not None:
        raise ValueError("box/named constraints are a monolithic-lane "
                         "feature; the ADMM lane does not project")
    key = residency_key if residency_key is not None else ("admm", "anon")
    staged, n, d, block_width = _stage_admm_operands(
        objective, mesh, key)
    from photon_ml_tpu.parallel.mesh_residency import default_residency
    num_feature = mesh.shape[FEATURE_AXIS]
    w0 = default_residency().stage_update(
        mesh, _fold_x0(x0, num_feature, block_width), spec="feature",
        key=key, field="x0")
    from photon_ml_tpu.optim.schedule import RegWeights
    if isinstance(reg_weight, RegWeights):
        l1_w, l2_w = reg_weight.l1_weight, reg_weight.l2_weight
    else:
        l1_w, l2_w = reg.split(reg_weight)
    dtype = staged["x_grid"].dtype
    with mesh:
        kappa = _cached_kappa()(staged["weights"], staged["mask"])
        ops = ADMMOperands(
            x_grid=staged["x_grid"], q_eig=staged["q_eig"],
            lam_eig=staged["lam_eig"], labels=staged["labels"], kappa=kappa,
            offsets=staged["offsets"], l1_weight=jnp.asarray(l1_w, dtype),
            l2_weight=jnp.asarray(l2_w, dtype))
        result = admm_solve(objective.loss, reg.has_l1, ops, w0,
                            admm_config, budget=budget)
    result = result._replace(x=result.x[:d])
    do_polish = admm_config.polish if polish is None else polish
    if do_polish:
        admm_iterations = result.iterations
        result = fit_fixed_effect(
            objective, result.x, mesh, config, reg, reg_weight,
            shard_features=False, budget=polish_budget,
            residency_key=residency_key)
        result = result._replace(
            iterations=result.iterations + admm_iterations)
    return result


@functools.lru_cache(maxsize=8)
def _cached_admm_scorer():
    def _score(means, x_grid, offsets):
        num_feature, block_width = x_grid.shape[1], x_grid.shape[2]
        d = means.shape[0]
        w = jnp.pad(means, (0, num_feature * block_width - d))
        z = jnp.einsum("nfa,fa->n", x_grid,
                       w.reshape(num_feature, block_width))
        return z if offsets is None else z + offsets
    return jax.jit(_score)


def score_fixed_effect_admm(model: GeneralizedLinearModel, x, mesh: Mesh,
                            offsets: Optional[jax.Array] = None,
                            residency_key=None) -> jax.Array:
    """Sharded margins through the ADMM lane's staged column grid — scoring
    shares the SAME cold x_grid entry the solver staged, so an ADMM
    coordinate never pays for a second (monolithic) design copy just to
    score.  Scores come back sharded over "data", padding sliced off."""
    from photon_ml_tpu.parallel.mesh_residency import default_residency
    res = default_residency()
    key = residency_key if residency_key is not None else ("admm", "anon")
    n, _, _, x_grid = stage_admm_grid(key, mesh, x, residency=res)
    offsets_dev = (None if offsets is None else
                   res.stage_update(mesh, offsets, 0.0, key=key,
                                    field="offsets"))
    with mesh:
        scores = _cached_admm_scorer()(model.coefficients.means, x_grid,
                                       offsets_dev)
    return scores[:n]


@functools.lru_cache(maxsize=8)
def _cached_scorer():
    def _score(means, x, offsets):
        from photon_ml_tpu.ops import features as fops
        z = fops.matvec(x, means)
        return z if offsets is None else z + offsets
    return jax.jit(_score)


def score_fixed_effect(model: GeneralizedLinearModel, x, mesh: Mesh,
                       offsets: Optional[jax.Array] = None,
                       residency_key=None) -> jax.Array:
    """Sharded margin computation (reference: FixedEffectModel scoring via
    broadcast dot product, FixedEffectCoordinate.scala:143-152).  Scores come
    back sharded over "data" — they stay device-resident for coordinate
    descent's residual exchange.  Rows are padded to a mesh multiple and the
    padding sliced off the result.  With `residency_key` the design matrix
    is memoized per key in the mesh residency layer — repeated rescores of
    the same shard re-transfer nothing."""
    from photon_ml_tpu.parallel.mesh import pad_and_shard_rows
    if offsets is None:
        n, (x,) = pad_and_shard_rows(mesh, x, residency_key=residency_key)
    else:
        n, (x, offsets) = pad_and_shard_rows(mesh, x, offsets,
                                             residency_key=residency_key)
    with mesh:
        scores = _cached_scorer()(model.coefficients.means, x, offsets)
    return scores[:n]
