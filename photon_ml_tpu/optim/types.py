"""Optimizer result/state types and convergence reasons.

Rebuild of the reference's Optimizer state machinery:
  - ConvergenceReason ADT (photon-lib/.../util/ConvergenceReason and
    Optimizer.scala:136-150)
  - OptimizationStatesTracker (photon-lib/.../optimization/
    OptimizationStatesTracker.scala:32-102)

Because solves run entirely inside jit (and often inside vmap, one solve per
random-effect entity), the "tracker" is not a mutable queue but fixed-shape
history arrays carried through the lax.while_loop and returned with the
solution.  Histories are padded with NaN beyond the iteration count.
"""
from __future__ import annotations

import enum
from typing import NamedTuple

import jax
import numpy as np


class ConvergenceReason(enum.IntEnum):
    """int codes so they can live in traced arrays.

    reference: Optimizer.scala:136-150 convergence reasons."""

    NOT_CONVERGED = 0
    MAX_ITERATIONS = 1
    FUNCTION_VALUES_CONVERGED = 2
    GRADIENT_CONVERGED = 3
    LINE_SEARCH_FAILED = 4          # reference: ObjectiveNotImproving
    TRUST_REGION_EXHAUSTED = 5      # TRON: max step-failures (TRON.scala:258)


#: the columns of `SolveResult.lockstep`, one row a run of a batched solve:
#: the program's lanes and samples a lane (its [E, S]), the lanes that hold
#: a row, the trips of the vmapped loop (the most iterations of any lane),
#: the real lanes' own iterations summed, the trial values the device
#: evaluated (a trip's first trial and every run of the batched line
#: search, which runs while ANY lane's condition holds, ended lanes
#: included), those the lanes still running at a trip needed, and the
#: value+gradient passes the device read: the most of any lane (`fg_count`,
#: trips + 2) on cached margins, one a lock-step trial and the first where
#: every trial is a pass (L1, box)
LOCKSTEP = ("entities", "samples", "lanes", "trips", "lane_iterations",
            "lockstep_trials", "running_trials", "data_passes")


class SolveResult(NamedTuple):
    """Solution + the states-tracker table.

    `loss_history[i]` / `gnorm_history[i]` are the objective value and
    gradient norm *entering* iteration i (so index 0 is the initial state,
    matching the reference tracker's convergence table)."""

    x: jax.Array
    value: jax.Array
    gradient_norm: jax.Array
    iterations: jax.Array       # int32
    reason: jax.Array           # int32 ConvergenceReason code
    loss_history: jax.Array     # [max_iter + 1]
    gnorm_history: jax.Array    # [max_iter + 1]
    # [max_iter + 1, d] iterate snapshots when the solve was run with
    # track_coefficients (reference: ModelTracker per-iteration models,
    # photon-api/.../supervised/model/ModelTracker.scala); None otherwise
    coefficient_history: "jax.Array | None" = None
    # TRON only: total Hessian-vector products across all inner CG steps
    # (each is a full data pass — the honest work count for throughput
    # accounting; the reference pays one treeAggregate per Hv, TRON.scala:301)
    hv_count: "jax.Array | None" = None
    # LBFGS/OWLQN only: full value+gradient data passes, two reads of the
    # features each — the honest work count for throughput accounting.
    # Where every trial point of the line search is a fused value+gradient
    # (L1, box, a bare value_and_grad): the initial evaluation and every
    # trial, backtracks included, 1 + ls_trials.  Where the search runs on
    # cached margins (optim/lbfgs.py `margin_surface`): iterations + 2 — the
    # initial evaluation, one direction-margins + gradient pair an
    # iteration, and the refresh of the returned value at the final x —
    # however often the search backtracked
    fg_count: "jax.Array | None" = None
    # LBFGS/OWLQN only: trial points the line search evaluated, first trials
    # and backtracks.  On cached margins a trial reads no features, so
    # 1 - fg_count / (1 + ls_trials) is the share of evaluations that the
    # margins served
    ls_trials: "jax.Array | None" = None
    # LBFGS/OWLQN under a batched per-entity solve only: the lock step's
    # own count of what it ran.  The batched program's result holds int32
    # [runs, len(LOCKSTEP)], one row a run with LOCKSTEP's columns, rows in
    # bucket order (parallel/random_effect.py); inside one of its lanes,
    # before the lanes are reduced, lbfgs's three scalars (optim/lbfgs.py
    # `lane_axis`)
    lockstep: "jax.Array | tuple | None" = None

    @property
    def converged(self) -> jax.Array:
        return (self.reason == ConvergenceReason.FUNCTION_VALUES_CONVERGED) | (
            self.reason == ConvergenceReason.GRADIENT_CONVERGED)

    def summary(self) -> str:
        """Formatted convergence table (reference:
        OptimizationStatesTracker.toString)."""
        it = int(self.iterations)
        lines = [f"{'iter':>5} {'loss':>18} {'|grad|':>14}"]
        loss = np.asarray(self.loss_history)
        gn = np.asarray(self.gnorm_history)
        for i in range(it + 1):
            lines.append(f"{i:>5} {loss[i]:>18.10e} {gn[i]:>14.6e}")
        reason = ConvergenceReason(int(self.reason)).name
        lines.append(f"converged after {it} iterations: {reason}")
        return "\n".join(lines)
