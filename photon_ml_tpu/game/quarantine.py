"""Non-finite solve quarantine: contain a diverged coordinate, don't let it
poison the descent.

A single NaN/Inf inner solve used to be silently terminal: the coordinate's
scores go non-finite, `total = partial + scores` goes non-finite, and every
downstream coordinate then solves against poisoned residual offsets — the
whole fit is garbage from that update on, discovered (if at all) hours
later when someone reads the objective history.  Spark-era Photon ML never
had this failure mode surface the same way (a diverged task was retried
from lineage); the JAX rebuild needs explicit containment.

Three pieces, by design all batched or rare:

  * `guard(new, prev)` — a DEVICE-SIDE health flag (all coefficients
    finite) plus a `where(flag, new, old)` rollback over the coordinate's
    coefficient arrays.  The rollback means a poisoned solve behaves, for
    every downstream consumer, exactly as if the coordinate had been
    FROZEN for the visit: its scores and regularization term recompute
    from the last good coefficients, and the rest of the descent continues
    on finite numbers.  When the solve is healthy, `where(True, new, old)`
    is bitwise `new` — strict/pipelined parity gates are unaffected.  The
    flag itself is a device scalar that rides the existing ONE batched
    `device_get` per outer iteration (combined with objective finiteness),
    so the check adds zero host syncs and — being module-level jits —
    zero fresh traces to a warm fit.
  * `QuarantineMonitor` — the host-side policy, applied when the flag
    lands: record the containment event, RE-RUN the coordinate once at a
    tightened `SolveBudget` (optim.schedule.QuarantineRetrySchedule: a
    quarter of the configured iteration cap, 10x looser tolerance — a
    diverged quasi-Newton solve usually needs fewer, more conservative
    steps, not more); if the re-run also diverges — or the coordinate
    diverges again at a later visit — FREEZE it for the remainder of the
    fit while the other coordinates keep descending.  Every event lands in
    `TrackerSummary.containment`, `solver_diagnostics()`, and the fit
    summary.
  * `poison_model` — the fault-injection hook's corruption (site
    "solve.poison"): multiplies the solve result by NaN so that tests/test_faults.py
    can prove the quarantine recovers the fault-free trajectory.

Objective-only divergence (finite coefficients, non-finite data term) is
caught by the same combined flag; its rollback is host-side at flush time
(the rare path), since by then the scores were already finite.
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from photon_ml_tpu import telemetry

from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.game import (
    FactoredRandomEffectModel, FixedEffectModel, MatrixFactorizationModel,
    RandomEffectModel,
)

logger = logging.getLogger("photon_ml_tpu")


# module-level jits: traced once per coefficient-shape set during the
# warmup fit, zero fresh traces afterwards (tests/test_faults.py gates
# this with the same compile-counting harness as the pipeline suite)

@jax.jit
def _all_finite(arrays) -> jax.Array:
    flags = [jnp.all(jnp.isfinite(a)) for a in arrays]
    out = flags[0]
    for f in flags[1:]:
        out = jnp.logical_and(out, f)
    return out


@jax.jit
def _where_guard(flag, new, old):
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(flag, a, b), new, old)


@jax.jit
def _and_finite(flag, scalar) -> jax.Array:
    return jnp.logical_and(flag, jnp.isfinite(scalar))


def coefficient_arrays(model) -> Optional[Tuple[jax.Array, ...]]:
    """The device arrays a solve writes, per coordinate-model kind; None
    for kinds the descent loop never produces (no guard applied)."""
    if isinstance(model, FixedEffectModel):
        return (model.glm.coefficients.means,)
    if isinstance(model, FactoredRandomEffectModel):
        return (model.latent_coefficients, model.projection)
    if isinstance(model, RandomEffectModel):
        return (model.coefficients,)
    if isinstance(model, MatrixFactorizationModel):
        return (model.row_factors, model.col_factors)
    return None


def _with_coefficient_arrays(model, arrays):
    if isinstance(model, FixedEffectModel):
        (means,) = arrays
        coeffs = Coefficients(means, model.glm.coefficients.variances)
        return FixedEffectModel(model.glm.with_coefficients(coeffs),
                                model.feature_shard)
    if isinstance(model, FactoredRandomEffectModel):
        latent, proj = arrays
        return dataclasses.replace(model, latent_coefficients=latent,
                                   projection=proj)
    if isinstance(model, RandomEffectModel):
        (coeffs,) = arrays
        return dataclasses.replace(model, coefficients=coeffs)
    if isinstance(model, MatrixFactorizationModel):
        rows, cols = arrays
        return dataclasses.replace(model, row_factors=rows, col_factors=cols)
    raise TypeError(f"unknown coordinate model type {type(model)}")


def guard(new_model, prev_model):
    """-> (guarded model, device bool flag).  The guarded model equals
    `new_model` bitwise when every coefficient is finite, `prev_model`'s
    coefficients otherwise.  Unknown model kinds pass through unguarded
    with a constant-True flag."""
    new_arrays = coefficient_arrays(new_model)
    if new_arrays is None:
        return new_model, jnp.asarray(True)
    flag = _all_finite(new_arrays)
    old_arrays = coefficient_arrays(prev_model)
    guarded = _with_coefficient_arrays(
        new_model, _where_guard(flag, new_arrays, old_arrays))
    return guarded, flag


def combine_health(flag, objective_scalar):
    """Coefficient finiteness AND objective finiteness as ONE device bool
    (the scalar that rides the batched outer-iteration fetch)."""
    return _and_finite(flag, objective_scalar)


def poison_model(model):
    """Corrupt a solve result with NaNs (fault-injection site
    "solve.poison").  Deliberately NOT jitted — it only runs under an
    active FaultPlan, and the zero-trace gates run without one."""
    arrays = coefficient_arrays(model)
    if arrays is None:
        return model
    return _with_coefficient_arrays(
        model, tuple(a * jnp.nan for a in arrays))


class QuarantineMonitor:
    """Host-side containment policy + event log.

    Lifecycle per coordinate: healthy -> (divergence) -> rolled back +
    ONE re-run at the tightened budget -> healthy again, OR frozen for the
    remainder of the fit.  A second divergence at any later visit freezes
    immediately (two strikes — a coordinate that diverges repeatedly under
    containment is structurally sick, and freezing it keeps the rest of
    the descent productive)."""

    def __init__(self):
        self.events: List[dict] = []
        self._retried: set = set()
        self._frozen: set = set()

    def is_frozen(self, name: str) -> bool:
        return name in self._frozen

    @property
    def frozen(self) -> List[str]:
        return sorted(self._frozen)

    def _event(self, iteration: int, coordinate: str, action: str,
               **extra) -> dict:
        e = {"iteration": int(iteration), "coordinate": coordinate,
             "action": action, **extra}
        self.events.append(e)
        # containment is observable outside the fit result too: a counter
        # per action in the registry, and — when the tracer is armed — a
        # run-log event correlated by span id with the coordinate visit
        # whose flush discovered the divergence
        telemetry.counter(f"train.quarantine.{action}").inc()
        telemetry.event("quarantine", iteration=int(iteration),
                        coordinate=coordinate, action=action)
        logger.warning("quarantine: iter %d coordinate %-16s %s %s",
                       iteration, coordinate, action, extra or "")
        return e

    def on_divergence(self, iteration: int, coordinate: str) -> str:
        """Policy decision when a non-finite flag lands: 'retry' (first
        strike — caller re-runs once at the tightened budget) or 'freeze'
        (second strike)."""
        self._event(iteration, coordinate, "rolled_back")
        if coordinate in self._retried:
            self._frozen.add(coordinate)
            self._event(iteration, coordinate, "frozen",
                        reason="diverged again after a successful "
                               "quarantine retry")
            return "freeze"
        self._retried.add(coordinate)
        return "retry"

    def on_retry_result(self, iteration: int, coordinate: str,
                        ok: bool, objective: Optional[float] = None) -> None:
        if ok:
            self._event(iteration, coordinate, "retry_ok",
                        objective=objective)
        else:
            self._frozen.add(coordinate)
            self._event(iteration, coordinate, "frozen",
                        reason="quarantine retry at the tightened budget "
                               "also diverged")

    def summary(self) -> Dict[str, object]:
        """Fit-summary block: event list + per-coordinate counts."""
        counts: Dict[str, Dict[str, int]] = {}
        for e in self.events:
            c = counts.setdefault(e["coordinate"], {})
            c[e["action"]] = c.get(e["action"], 0) + 1
        return {"events": list(self.events), "counts": counts,
                "frozen": self.frozen}
