"""Optimizer + regularization configuration and the solve dispatcher.

Rebuild of:
  - OptimizerConfig / OptimizerType / OptimizerFactory
    (photon-api/.../optimization/{OptimizerConfig,OptimizerFactory}.scala)
  - RegularizationContext (photon-api/.../optimization/RegularizationContext.scala:35-124)

One typed dataclass replaces the reference's string mini-DSL; JSON round-trip
lives in the config system (photon_ml_tpu/game/config.py) for model-metadata
reproducibility.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.optim.lbfgs import lbfgs
from photon_ml_tpu.optim.tron import tron
from photon_ml_tpu.optim.types import SolveResult


class OptimizerType(str, enum.Enum):
    """reference: photon-lib/.../optimization/OptimizerType.scala."""

    LBFGS = "lbfgs"
    TRON = "tron"


class RegularizationType(str, enum.Enum):
    """reference: RegularizationContext.scala companion types."""

    NONE = "none"
    L1 = "l1"
    L2 = "l2"
    ELASTIC_NET = "elastic_net"


@dataclasses.dataclass(frozen=True)
class RegularizationContext:
    """Splits a total weight lambda into L1 = alpha*lambda and
    L2 = (1-alpha)*lambda (reference: RegularizationContext.scala:78-86)."""

    reg_type: RegularizationType = RegularizationType.NONE
    elastic_net_alpha: Optional[float] = None

    def __post_init__(self):
        if self.reg_type == RegularizationType.ELASTIC_NET:
            a = self.elastic_net_alpha
            if a is None or not (0.0 <= a <= 1.0):
                raise ValueError(f"elastic_net_alpha must be in [0,1], got {a}")
        elif self.elastic_net_alpha is not None:
            raise ValueError("elastic_net_alpha only valid for ELASTIC_NET")

    def split(self, reg_weight) -> Tuple[jax.Array, jax.Array]:
        """-> (l1_weight, l2_weight)."""
        w = jnp.asarray(reg_weight)
        if self.reg_type == RegularizationType.NONE:
            return jnp.zeros_like(w), jnp.zeros_like(w)
        if self.reg_type == RegularizationType.L1:
            return w, jnp.zeros_like(w)
        if self.reg_type == RegularizationType.L2:
            return jnp.zeros_like(w), w
        a = self.elastic_net_alpha
        return a * w, (1.0 - a) * w

    @property
    def has_l1(self) -> bool:
        return self.reg_type in (RegularizationType.L1, RegularizationType.ELASTIC_NET) and \
            (self.reg_type != RegularizationType.ELASTIC_NET or self.elastic_net_alpha > 0)


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    """(type, max iterations, tolerance, constraints), reference:
    OptimizerConfig.scala:23.  Defaults per optimizer follow
    LBFGS.scala:151-156 / TRON.scala:257-263; `None` means
    use-the-optimizer-default."""

    optimizer: OptimizerType = OptimizerType.LBFGS
    max_iterations: Optional[int] = None
    tolerance: Optional[float] = None
    history: int = 10                     # LBFGS memory
    max_cg_iterations: int = 20           # TRON inner CG cap
    # per-coordinate constraint maps (reference: OptimizationUtils.scala);
    # stored as tuples so the config stays hashable — callers may pass any
    # array-like and solve() converts back to arrays
    box_lower: Optional[tuple] = None
    box_upper: Optional[tuple] = None
    # NAMED-feature constraints in the reference's JSON shape
    # ([{name, term, lowerBound, upperBound}], GLMSuite.scala:206-280);
    # resolved against the shard's IndexMap into box_lower/box_upper at fit
    # time (resolved_constraints()).  Exclusive with positional bounds.
    constraints: Optional[tuple] = None
    # per-iteration coefficient snapshots in SolveResult.coefficient_history
    # (reference: ModelTracker per-iteration models); costs [max_iter+1, d]
    # device memory per solve, so off by default
    track_coefficients: bool = False

    def __post_init__(self):
        for name in ("box_lower", "box_upper"):
            v = getattr(self, name)
            if v is not None and not isinstance(v, tuple):
                # np, not jnp: jnp.asarray would stage the bounds to the
                # device only to sync one element per float() (PH001)
                object.__setattr__(self, name,
                                   tuple(float(e) for e in np.asarray(v)))
        if self.constraints is not None:
            from photon_ml_tpu.optim.constraints import normalize_constraints
            if self.box_lower is not None or self.box_upper is not None:
                raise ValueError(
                    "named constraints and positional box_lower/box_upper "
                    "are exclusive — the constraints RESOLVE to the "
                    "positional bounds")
            object.__setattr__(self, "constraints",
                               normalize_constraints(self.constraints))

    def resolved_constraints(self, index_map) -> "OptimizerConfig":
        """Named constraints -> positional bounds via the feature shard's
        IndexMap (reference: GLMSuite.createConstraintFeatureMap)."""
        if self.constraints is None:
            return self
        from photon_ml_tpu.optim.constraints import resolve_constraints
        if index_map is None:
            raise ValueError(
                "named feature constraints require the dataset to carry an "
                "index map for the coordinate's feature shard (train from "
                "Avro/LIBSVM-with-maps or an npz GameDataset saved with "
                "index maps)")
        lower, upper = resolve_constraints(self.constraints, index_map)
        return dataclasses.replace(self, constraints=None,
                                   box_lower=lower, box_upper=upper)

    def resolved(self) -> "OptimizerConfig":
        # explicit 0 / 0.0 are legitimate (tolerance=0 disables the
        # function-value check, which is a strict `<` in optim/lbfgs.py
        # and optim/streaming.py, and leaves the gradient check an exact
        # zero to find); only None takes the default
        d_iter, d_tol = ((15, 1e-5) if self.optimizer == OptimizerType.TRON
                         else (100, 1e-7))
        return dataclasses.replace(
            self,
            max_iterations=self.max_iterations if self.max_iterations is not None else d_iter,
            tolerance=self.tolerance if self.tolerance is not None else d_tol)


def solve(
    objective: GLMObjective,
    x0: jax.Array,
    config: OptimizerConfig = OptimizerConfig(),
    reg: RegularizationContext = RegularizationContext(),
    reg_weight: jax.Array | float = 0.0,
    budget=None,
    lane_axis=None,
) -> SolveResult:
    """Run one GLM solve: objective + config -> SolveResult.

    The reference equivalent is OptimizerFactory building an Optimizer and
    Optimizer.optimize driving it (Optimizer.scala:172-196).  L2 goes into
    the smooth objective; L1 goes to OWLQN's pseudo-gradient machinery.
    Fully jittable: wrap in jax.jit (or vmap over a batch of objectives for
    per-entity solves) at the call site.

    `budget` (an optim.schedule.SolveBudget) makes the iteration cap and
    tolerance TRACED OPERANDS of the compiled program: the config's
    max_iterations stays the static ceiling (history-buffer size), the loop
    tests the dynamic cap, and a per-outer-iteration budget schedule
    compiles nothing new.  `budget=None` keeps the config's static values,
    which is the identical arithmetic.

    `lane_axis`: the vmap axis of a batched per-entity solve, whose lock
    step an L-BFGS/OWLQN solve then counts (optim/lbfgs.py); TRON ignores it.

    `reg_weight` may be an optim.schedule.RegWeights: then BOTH penalty
    weights ride as traced operands (bypassing `reg.split`'s static
    arithmetic), so a hyperparameter sweep over lambda — or the elastic-net
    mix — re-dispatches one compiled program.  `reg.has_l1` remains the
    static structural flag either way: it decides whether the L1 machinery
    is compiled in at all; a traced l1 of 0 under `has_l1=True` converges
    to the same smooth optimum (to solver tolerance — OWLQN's orthant
    projection stays compiled in and can clip steps mid-path).
    """
    cfg = config.resolved()
    if cfg.constraints is not None:
        raise ValueError(
            "named feature constraints are unresolved — call "
            "config.resolved_constraints(index_map) before solve()")
    from photon_ml_tpu.optim.schedule import RegWeights
    if isinstance(reg_weight, RegWeights):
        l1_w, l2_w = reg_weight.l1_weight, reg_weight.l2_weight
    else:
        l1_w, l2_w = reg.split(reg_weight)
    obj = objective.with_l2(l2_w)
    tolerance = cfg.tolerance if budget is None else budget.tolerance
    iteration_cap = None if budget is None else budget.iteration_cap

    if cfg.optimizer == OptimizerType.TRON:
        if reg.has_l1:
            raise ValueError("TRON supports only L2/none regularization "
                             "(reference: OptimizerFactory constraint)")
        if not objective.loss.twice_differentiable:
            raise ValueError(f"{objective.loss.name} is not twice differentiable; "
                             "use LBFGS (reference: SmoothedHingeLossFunction)")
        if cfg.box_lower is not None or cfg.box_upper is not None:
            raise ValueError("box constraints are an LBFGS feature "
                             "(reference: LBFGS.scala:72)")
        return tron(obj.value_and_gradient, obj.hessian_vector, x0,
                    max_iterations=cfg.max_iterations, tolerance=tolerance,
                    max_cg_iterations=cfg.max_cg_iterations,
                    track_coefficients=cfg.track_coefficients,
                    iteration_cap=iteration_cap)

    lower = None if cfg.box_lower is None else jnp.asarray(cfg.box_lower, x0.dtype)
    upper = None if cfg.box_upper is None else jnp.asarray(cfg.box_upper, x0.dtype)
    # the line search runs on cached margins wherever its trial points are
    # x + t p themselves; OWLQN's orthant projection and the box bend them
    affine_trials = not reg.has_l1 and lower is None and upper is None
    return lbfgs(None if affine_trials else obj.value_and_gradient, x0,
                 max_iterations=cfg.max_iterations, tolerance=tolerance,
                 history=cfg.history,
                 l1_weight=l1_w if reg.has_l1 else None,
                 lower=lower, upper=upper,
                 track_coefficients=cfg.track_coefficients,
                 iteration_cap=iteration_cap,
                 margin_surface=obj if affine_trials else None,
                 lane_axis=lane_axis)
