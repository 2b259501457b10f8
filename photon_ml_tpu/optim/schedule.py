"""Inexact inner-solve budgets for block coordinate descent.

The GAME outer loop re-perturbs every coordinate's problem on the next
visit, so paying full-tolerance convergence on early visits is wasted work.
What each coordinate's visits cost on the chip, the factored coordinate's
two halves among them, is on record for the benchmark's cells (PERF.md
sections 5 and 6; `game-ml20m-mf.fit` runs the factored one); no cell runs
under a schedule yet, so what a schedule saves there is not measured.
Running inner solves inexactly early and
tightening geometrically toward the end is the standard cure (Trofimov &
Genkin, arXiv:1611.02101; Snap ML's hierarchical local solvers,
arXiv:1803.06333).

Two pieces:

  * `SolveBudget` — a (iteration cap, tolerance) pair shipped into the
    compiled solver programs as TRACED OPERANDS.  The solvers' history
    buffers stay sized by the static `max_iterations` ceiling and only the
    `lax.while_loop` condition tests the dynamic cap, so sweeping budgets
    across outer iterations compiles NOTHING new (regression-tested in
    tests/test_inexact.py).
  * `SolverSchedule` — the per-outer-iteration policy: small caps + loose
    tolerance on early outer iterations, geometric growth/tightening, and
    the FINAL outer iteration always at the full configured budget so the
    scheduled fit's final objective matches a strict full-solve fit within
    the parity gate.

The schedule is pure host-side arithmetic in (outer_iteration,
num_outer_iterations) — checkpoint resume recomputes identical budgets for
the remaining iterations, so a resumed scheduled fit reproduces the
uninterrupted trajectory bit-for-bit.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class StochasticPlan:
    """One outer iteration's budget for the stochastic streaming lane
    (optim/stochastic.py): how many full passes over the chunk stream to
    run, how many local coordinate-descent epochs each staged chunk gets
    before eviction, and how per-chunk models merge across the stream.

    `merge`:
      - "sequential" (default): chunk k's local solve warm-starts from
        chunk k-1's result — the model flows through the stream (the
        best-converging order when chunks are visited one at a time);
      - "average": every chunk starts from the pass-entry model and the
        per-chunk deltas combine as a row-weighted average (the
        CoCoA/Snap-ML safe merge — the order-independent mode).

    `step_clip` bounds each per-coordinate step for losses WITHOUT a
    global curvature bound (Poisson); None resolves to no clip for
    bounded-curvature losses and 1.0 for unbounded ones."""

    passes: int = 1
    local_epochs: int = 4
    merge: str = "sequential"
    seed: int = 0
    step_clip: Optional[float] = None

    def __post_init__(self):
        if self.passes < 0:
            raise ValueError("passes must be >= 0")
        if self.local_epochs < 1:
            raise ValueError("local_epochs must be >= 1")
        if self.merge not in ("sequential", "average"):
            raise ValueError(f"merge must be 'sequential' or 'average', "
                             f"got {self.merge!r}")


class SolveBudget(NamedTuple):
    """Dynamic inner-solve budget: operands of the compiled solver program
    (NOT trace constants — that is the whole point)."""

    iteration_cap: jax.Array    # int32 scalar, clipped to the static ceiling
    tolerance: jax.Array        # float scalar

    @staticmethod
    def make(iteration_cap: int, tolerance: float) -> "SolveBudget":
        return SolveBudget(jnp.asarray(int(iteration_cap), jnp.int32),
                           jnp.asarray(float(tolerance)))


class RegWeights(NamedTuple):
    """Traced regularization operands — the SolveBudget trick applied to
    lambda itself.  A compiled solve that takes a RegWeights instead of a
    scalar reg_weight receives BOTH penalty weights as program operands, so
    sweeping the total weight OR the elastic-net mixing ratio re-dispatches
    the same executable: changing lambda never retraces, and a candidate
    axis can vmap straight over it.

    The STRUCTURAL choice stays static: `RegularizationContext.has_l1`
    decides at trace time whether the OWLQN pseudo-gradient machinery is
    compiled in.  A RegWeights with nonzero l1_weight handed to a solve
    whose context has `has_l1 == False` is silently ignored — elastic-net
    sweeps that vary the mix must trace against a context with
    `has_l1 == True` (traced l1 == 0 makes OWLQN's pseudo-gradient equal
    the plain gradient, so it converges to the SAME smooth optimum — the
    orthant projection can still clip sign-flipping steps mid-path, so
    iterates match plain LBFGS to solver tolerance, not bit-for-bit)."""

    l1_weight: jax.Array        # float scalar (or [K] under vmap)
    l2_weight: jax.Array        # float scalar (or [K] under vmap)

    @staticmethod
    def make(l1_weight, l2_weight, dtype=None) -> "RegWeights":
        return RegWeights(jnp.asarray(l1_weight, dtype),
                          jnp.asarray(l2_weight, dtype))

    @staticmethod
    def from_context(reg, reg_weight, elastic_net_alpha=None,
                     dtype=None) -> "RegWeights":
        """Split a total weight exactly as `reg.split` would, but with the
        mixing ratio optionally TRACED: `elastic_net_alpha=None` reproduces
        the context's own (static) split arithmetic; passing an alpha makes
        the mix a traced operand (`l1 = a*w`, `l2 = (1-a)*w`)."""
        w = jnp.asarray(reg_weight, dtype)
        if elastic_net_alpha is None:
            l1, l2 = reg.split(w)
            return RegWeights(jnp.asarray(l1, dtype), jnp.asarray(l2, dtype))
        a = jnp.asarray(elastic_net_alpha, w.dtype)
        return RegWeights(a * w, (1.0 - a) * w)


@dataclasses.dataclass(frozen=True)
class SolverSchedule:
    """Per-(outer-iteration) inexactness schedule for the inner solvers.

    On outer iteration t of N:
      - t == N-1 (final): the full configured (max_iterations, tolerance) —
        parity with a strict full-solve fit holds by construction;
      - t < N-1: iteration cap = initial_iterations * iteration_growth**t
        (clipped to the configured max_iterations) and tolerance =
        configured_tolerance * initial_tolerance_factor * tolerance_decay**t
        (floored at the configured tolerance).

    Applied uniformly to fixed-effect, random-effect, and factored-MF
    coordinates (both the latent-space and projection-matrix solves).

    The STOCHASTIC lane (optim/stochastic.py) layers on top for STREAMED
    fixed-effect coordinates: with `stochastic_passes > 0`, every outer
    iteration except the final `stochastic_polish_iterations` runs the
    coarse per-chunk coordinate-descent lane (each staged chunk does
    `stochastic_local_epochs` epochs of local work before eviction, so
    useful work per staged byte goes up by the epoch count) and the
    trailing iterations run the strict host-stepped solver at this
    schedule's budgets — the polish that pins the fixed point.  Resident
    coordinates ignore the stochastic fields (their data never re-stages,
    so there is nothing to amortize).
    """

    initial_iterations: int = 4
    iteration_growth: float = 2.0
    initial_tolerance_factor: float = 1e3
    tolerance_decay: float = 0.1
    # stochastic streaming lane (0 passes = disabled, the pre-existing
    # strict-only behavior)
    stochastic_passes: int = 0
    stochastic_local_epochs: int = 4
    stochastic_merge: str = "sequential"
    stochastic_seed: int = 0
    stochastic_polish_iterations: int = 1
    # feature-axis ADMM lane (optim/admm.py): a scheduled fit runs the
    # monolithic polish only on the final `admm_polish_iterations` outer
    # iterations — early visits are re-perturbed next visit anyway, so
    # polishing them wastes a full strict solve per visit.  The ADMM
    # iteration budgets themselves come from the SAME budget_for
    # (ADMMConfig.resolved() duck-types OptimizerConfig)
    admm_polish_iterations: int = 1

    def __post_init__(self):
        if self.initial_iterations < 1:
            raise ValueError("initial_iterations must be >= 1")
        if self.iteration_growth < 1.0:
            raise ValueError("iteration_growth must be >= 1 (budgets only "
                             "tighten toward the full solve)")
        if self.initial_tolerance_factor < 1.0:
            raise ValueError("initial_tolerance_factor must be >= 1")
        if not 0.0 < self.tolerance_decay <= 1.0:
            raise ValueError("tolerance_decay must be in (0, 1]")
        if self.stochastic_passes < 0:
            raise ValueError("stochastic_passes must be >= 0")
        if self.stochastic_local_epochs < 1:
            raise ValueError("stochastic_local_epochs must be >= 1")
        if self.stochastic_merge not in ("sequential", "average"):
            raise ValueError("stochastic_merge must be 'sequential' or "
                             f"'average', got {self.stochastic_merge!r}")
        if self.stochastic_polish_iterations < 1:
            raise ValueError("stochastic_polish_iterations must be >= 1 "
                             "(the final outer iterations ALWAYS polish "
                             "with the strict solver — parity at the fixed "
                             "point depends on it)")
        if self.admm_polish_iterations < 1:
            raise ValueError("admm_polish_iterations must be >= 1 (an "
                             "ADMM-lane fit with polish enabled always "
                             "polishes its final outer iteration)")

    def plan(self, outer_iteration: int, num_outer_iterations: int,
             max_iterations: int, tolerance: float) -> Tuple[int, float]:
        """Host-side (iteration cap, tolerance) for one outer iteration."""
        if outer_iteration >= num_outer_iterations - 1:
            return max_iterations, tolerance
        cap = int(round(self.initial_iterations
                        * self.iteration_growth ** outer_iteration))
        cap = max(1, min(cap, max_iterations))
        factor = max(self.initial_tolerance_factor
                     * self.tolerance_decay ** outer_iteration, 1.0)
        return cap, tolerance * factor

    def budget_for(self, outer_iteration: int, num_outer_iterations: int,
                   optimizer_config) -> SolveBudget:
        """SolveBudget for one (outer iteration, OptimizerConfig).  The
        returned pair is traced into the solve, so every outer iteration of
        a scheduled fit reuses ONE compiled program per solver."""
        r = optimizer_config.resolved()
        cap, tol = self.plan(outer_iteration, num_outer_iterations,
                             r.max_iterations, r.tolerance)
        return SolveBudget.make(cap, tol)

    def stochastic_plan(self, outer_iteration: int,
                        num_outer_iterations: int
                        ) -> Optional[StochasticPlan]:
        """The stochastic lane's budget for one outer iteration, or None
        when the strict host-stepped solver should run: lane disabled, or
        this is one of the final `stochastic_polish_iterations` outer
        iterations (the polish ALWAYS runs strict, so a fit's final visit
        converges to the same fixed point a strict-only fit would)."""
        if self.stochastic_passes <= 0:
            return None
        polish_from = num_outer_iterations - self.stochastic_polish_iterations
        if outer_iteration >= polish_from:
            return None
        return StochasticPlan(passes=self.stochastic_passes,
                              local_epochs=self.stochastic_local_epochs,
                              merge=self.stochastic_merge,
                              seed=self.stochastic_seed)

    def admm_polish(self, outer_iteration: int,
                    num_outer_iterations: int) -> bool:
        """Whether an ADMM-lane visit on this outer iteration should run
        the monolithic polish (only the final `admm_polish_iterations`
        visits do; an unscheduled fit polishes every visit).  The caller
        still ANDs this with the ADMMConfig's own polish flag — a config
        with polish=False never polishes regardless of schedule."""
        polish_from = num_outer_iterations - self.admm_polish_iterations
        return outer_iteration >= polish_from

    # -- JSON round-trip (game/config.py embeds schedules in model metadata)
    def to_dict(self) -> dict:
        d = {"initial_iterations": self.initial_iterations,
             "iteration_growth": self.iteration_growth,
             "initial_tolerance_factor": self.initial_tolerance_factor,
             "tolerance_decay": self.tolerance_decay}
        # stochastic keys encode only when the lane is enabled, so
        # pre-existing checkpoint fingerprints of strict-only schedules
        # stay byte-identical
        if self.stochastic_passes > 0:
            d.update({
                "stochastic_passes": self.stochastic_passes,
                "stochastic_local_epochs": self.stochastic_local_epochs,
                "stochastic_merge": self.stochastic_merge,
                "stochastic_seed": self.stochastic_seed,
                "stochastic_polish_iterations":
                    self.stochastic_polish_iterations,
            })
        # same only-when-set discipline for the ADMM lane key
        if self.admm_polish_iterations != 1:
            d["admm_polish_iterations"] = self.admm_polish_iterations
        return d

    @staticmethod
    def from_dict(d) -> "SolverSchedule | None":
        if d is None:
            return None
        return SolverSchedule(
            initial_iterations=d.get("initial_iterations", 4),
            iteration_growth=d.get("iteration_growth", 2.0),
            initial_tolerance_factor=d.get("initial_tolerance_factor", 1e3),
            tolerance_decay=d.get("tolerance_decay", 0.1),
            stochastic_passes=d.get("stochastic_passes", 0),
            stochastic_local_epochs=d.get("stochastic_local_epochs", 4),
            stochastic_merge=d.get("stochastic_merge", "sequential"),
            stochastic_seed=d.get("stochastic_seed", 0),
            stochastic_polish_iterations=d.get(
                "stochastic_polish_iterations", 1),
            admm_polish_iterations=d.get("admm_polish_iterations", 1))


@dataclasses.dataclass(frozen=True)
class QuarantineRetrySchedule:
    """Schedule-shaped single-solve budget for quarantine re-runs (GAME
    non-finite solve containment, game/quarantine.py): a diverged
    quasi-Newton solve is usually a line-search/curvature pathology that
    more iterations make WORSE, so the one retry runs at a quarter of the
    configured iteration cap with a 10x looser tolerance — conservative
    steps, early stop.  Duck-types SolverSchedule's `plan`/`budget_for` so
    it rides the existing Coordinate.update(schedule=...) plumbing without
    new solver parameters (and therefore without new traces)."""

    cap_divisor: int = 4
    tolerance_factor: float = 10.0

    def plan(self, outer_iteration: int, num_outer_iterations: int,
             max_iterations: int, tolerance: float) -> Tuple[int, float]:
        return (max(1, max_iterations // self.cap_divisor),
                tolerance * self.tolerance_factor)

    def budget_for(self, outer_iteration: int, num_outer_iterations: int,
                   optimizer_config) -> SolveBudget:
        r = optimizer_config.resolved()
        cap, tol = self.plan(outer_iteration, num_outer_iterations,
                             r.max_iterations, r.tolerance)
        return SolveBudget.make(cap, tol)
