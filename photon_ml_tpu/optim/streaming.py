"""Host-stepped LBFGS / OWLQN / TRON for out-of-core (chunked) objectives.

The resident solvers (optim/lbfgs.py, optim/tron.py) are single
lax.while_loop programs: the ENTIRE solve compiles and runs on device, which
requires the objective's data to be traceable — i.e. device-resident.  A
ChunkedGLMObjective's oracle is a host-driven pass over streamed chunks, so
it cannot live inside a while_loop.  These drivers run the SAME algorithms
with the iteration loop on the host (the Snap ML posture, arXiv:1803.06333:
the host schedules, the accelerator computes):

  * every oracle call (value+gradient, Hessian-vector) is one double-
    buffered pass over the chunk stream — chunk i+1 transfers while chunk i
    computes;
  * optimizer STATE (iterate, gradient, the curvature pairs, CG vectors)
    stays on device; the host only reads back the scalars it branches on
    (line-search acceptance, convergence checks);
  * the update rules, constants, and convergence conditions mirror the
    resident solvers line for line — on a single-chunk plan the streamed
    solve follows the identical arithmetic, and fit-level parity vs the
    resident path is gated at ~1e-6 relative objective (the residual being
    chunk-order float summation).

All jitted helpers here are keyed on [d] shapes and the history length m
only — never on the row count — so the compile-count regression (zero fresh
traces across chunk counts) holds through the whole solve.
"""
from __future__ import annotations

# photonlint: disable-file=PH001 -- host-stepped BY DESIGN: this module IS
# the documented exception to the batched-flush rule; the host reads back
# exactly the scalars it branches on (see module docstring)

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.optim.config import (
    OptimizerConfig, OptimizerType, RegularizationContext,
)
from photon_ml_tpu.optim.lbfgs import (
    _C1, _CURV_EPS, _F_CONV_PERSISTENCE, _MAX_LS, _empty_history,
    _pseudo_gradient, _push, _two_loop,
)
from photon_ml_tpu.optim.tron import (
    _CG_RTOL, _ETA0, _ETA1, _ETA2, _MAX_FAILURES, _SIG1, _SIG2, _SIG3,
)
from photon_ml_tpu.optim.types import ConvergenceReason, SolveResult

ValueAndGrad = Callable[[jax.Array], Tuple[jax.Array, jax.Array]]
HessVec = Callable[[jax.Array, jax.Array], jax.Array]


# -- [d]-shaped jitted steps (one trace per (d, m, dtype), never per n): the
# resident solver's history (optim/lbfgs.py: age order, one array a slot),
# its two-loop and its push, each as one program ---------------------------

@jax.jit
def _direction(steer, hist, num_pairs):
    return -_two_loop(steer, hist, num_pairs)


@jax.jit
def _store_pair(hist, s, yv, sy):
    return _push(hist, True, s, yv, sy)


def _hist(values, length, dtype):
    out = np.full((length,), np.nan)
    out[:len(values)] = values
    return jnp.asarray(out, dtype)


def host_lbfgs(
    value_and_grad: ValueAndGrad,
    x0: jax.Array,
    *,
    max_iterations: int = 100,
    tolerance: float = 1e-7,
    history: int = 10,
    l1_weight: Optional[jax.Array | float] = None,
    lower: Optional[jax.Array] = None,
    upper: Optional[jax.Array] = None,
    iteration_cap: Optional[int] = None,
) -> SolveResult:
    """Host-stepped mirror of optim.lbfgs.lbfgs (same constants, same
    two-loop, same Armijo-on-displacement line search, same convergence
    persistence); `value_and_grad` is typically a ChunkedGLMObjective's
    streamed oracle.  Coefficient tracking is not offered — a streamed
    solve exists precisely because device memory is scarce.

    `iteration_cap`/`tolerance` mirror the resident solver's dynamic
    budget: the loop is host-stepped so varying them never recompiles
    anything (the jitted helpers are keyed on [d] shapes and m only);
    histories stay sized by the static `max_iterations` ceiling so result
    shapes are budget-independent."""
    use_l1 = l1_weight is not None
    use_box = lower is not None or upper is not None
    if use_l1 and use_box:
        raise ValueError("L1 (OWLQN) and box constraints cannot be combined "
                         "(the reference has no such solver either)")
    x0 = jnp.asarray(x0)
    dtype = x0.dtype
    l1 = jnp.asarray(l1_weight, dtype) if use_l1 else None

    def project_box(x):
        if not use_box:
            return x
        if lower is not None:
            x = jnp.maximum(x, lower)
        if upper is not None:
            x = jnp.minimum(x, upper)
        return x

    def box_blocked(x, g):
        blocked = jnp.zeros(x.shape, bool)
        if lower is not None:
            blocked = blocked | ((x <= lower) & (g > 0))
        if upper is not None:
            blocked = blocked | ((x >= upper) & (g < 0))
        return blocked

    def steer_grad(x, g):
        if use_l1:
            return _pseudo_gradient(x, g, l1)
        if use_box:
            return jnp.where(box_blocked(x, g), 0.0, g)
        return g

    def full_value(x):
        v, g = value_and_grad(x)
        if use_l1:
            v = v + jnp.sum(l1 * jnp.abs(x))
        return v, g

    cap = (max_iterations if iteration_cap is None
           else max(1, min(int(iteration_cap), max_iterations)))
    tolerance = float(tolerance)
    x = project_box(x0)
    f, g = full_value(x)
    gnorm = float(jnp.linalg.norm(steer_grad(x, g)))
    gtol = tolerance * max(gnorm, 1.0)

    hist = _empty_history(history, x0.shape[-1], dtype)
    num_pairs = 0
    f_small = 0
    fg_count = 1
    loss_hist = [float(f)]
    gnorm_hist = [gnorm]
    reason = ConvergenceReason.NOT_CONVERGED
    k = 0

    while k < cap and reason == ConvergenceReason.NOT_CONVERGED:
        steer = steer_grad(x, g)
        p = _direction(steer, hist, jnp.asarray(num_pairs, jnp.int32))
        if use_l1:
            p = jnp.where(p * (-steer) > 0, p, 0.0)
            orthant = jnp.where(x != 0, jnp.sign(x), jnp.sign(-steer))
        if use_box:
            p = jnp.where(box_blocked(x, g), 0.0, p)
        dd = float(jnp.dot(steer, p))
        if dd >= 0:  # fall back to steepest descent
            p = -steer
        t = (1.0 / max(float(jnp.linalg.norm(p)), 1.0)
             if num_pairs == 0 else 1.0)

        def trial(t):
            xt = x + t * p
            if use_l1:
                xt = jnp.where(xt * orthant > 0, xt, 0.0)
            return project_box(xt)

        def armijo_ok(xt, ft):
            return bool((ft <= f + _C1 * jnp.dot(steer, xt - x))
                        & jnp.isfinite(ft))

        xt = trial(t)
        ft, gt = full_value(xt)
        fg_count += 1
        ls_ok = armijo_ok(xt, ft)
        ls_n = 0
        while not ls_ok and ls_n < _MAX_LS:
            t *= 0.5
            ls_n += 1
            xt = trial(t)
            ft, gt = full_value(xt)
            fg_count += 1
            ls_ok = armijo_ok(xt, ft)

        s = xt - x
        yv = gt - g
        if use_box:
            bl = box_blocked(xt, gt)
            s = jnp.where(bl, 0.0, s)
            yv = jnp.where(bl, 0.0, yv)
        sy = jnp.dot(s, yv)
        if ls_ok and float(sy) > _CURV_EPS:
            hist = _store_pair(hist, s, yv, sy)
            num_pairs += 1

        if ls_ok:
            gnorm_new = float(jnp.linalg.norm(steer_grad(xt, gt)))
            f_new = float(ft)
            f_prev = float(f)
            # strictly under, as optim/lbfgs.py: tolerance 0 is "off"
            f_small_now = abs(f_prev - f_new) < tolerance * max(
                abs(f_prev), abs(f_new), 1.0)
            f_small = f_small + 1 if f_small_now else 0
            if gnorm_new <= gtol:
                reason = ConvergenceReason.GRADIENT_CONVERGED
            elif f_small >= _F_CONV_PERSISTENCE:
                reason = ConvergenceReason.FUNCTION_VALUES_CONVERGED
            x, f, g, gnorm = xt, ft, gt, gnorm_new
        else:
            reason = ConvergenceReason.LINE_SEARCH_FAILED

        k += 1
        loss_hist.append(float(f))
        gnorm_hist.append(gnorm)

    if reason == ConvergenceReason.NOT_CONVERGED:
        reason = ConvergenceReason.MAX_ITERATIONS
    return SolveResult(
        x=x, value=f, gradient_norm=jnp.asarray(gnorm, dtype),
        iterations=jnp.asarray(k, jnp.int32),
        reason=jnp.asarray(int(reason), jnp.int32),
        loss_history=_hist(loss_hist, max_iterations + 1, dtype),
        gnorm_history=_hist(gnorm_hist, max_iterations + 1, dtype),
        coefficient_history=None,
        fg_count=jnp.asarray(fg_count, jnp.int32))


def host_owlqn(value_and_grad: ValueAndGrad, x0: jax.Array, *, l1_weight,
               max_iterations: int = 100, tolerance: float = 1e-7,
               history: int = 10,
               iteration_cap: Optional[int] = None) -> SolveResult:
    return host_lbfgs(value_and_grad, x0, max_iterations=max_iterations,
                      tolerance=tolerance, history=history,
                      l1_weight=l1_weight, iteration_cap=iteration_cap)


def _host_truncated_cg(hess_vec: HessVec, x, g, delta: float, max_cg: int):
    """Host-stepped mirror of optim.tron._truncated_cg: each Hv is one
    streamed data pass, so every scalar the loop branches on is read back."""
    s = jnp.zeros_like(x)
    r = -g
    d = r
    rr = float(jnp.dot(r, r))
    gnorm = float(jnp.sqrt(jnp.dot(g, g)))
    tol = _CG_RTOL * gnorm
    hs = jnp.zeros_like(x)
    boundary = False
    i = 0
    if np.sqrt(rr) <= tol:
        return s, 0.0, False, 0
    while i < max_cg:
        hd = hess_vec(x, d)
        dhd = float(jnp.dot(d, hd))
        neg_curv = dhd <= 0
        alpha = rr / (1.0 if neg_curv else dhd)
        s_try = s + alpha * d
        outside = float(jnp.dot(s_try, s_try)) > delta * delta
        hit = neg_curv or outside
        if hit:
            dd_ = float(jnp.dot(d, d))
            sd = float(jnp.dot(s, d))
            ss = float(jnp.dot(s, s))
            rad = np.sqrt(max(sd * sd + dd_ * (delta * delta - ss), 0.0))
            step = (rad - sd) / (dd_ if dd_ > 0 else 1.0)
        else:
            step = alpha
        s = s + step * d
        hs = hs + step * hd
        if not hit:
            r = r - alpha * hd
        rr_new = float(jnp.dot(r, r))
        i += 1
        boundary = boundary or hit
        if hit or np.sqrt(rr_new) <= tol:
            break
        beta = rr_new / (rr if rr > 0 else 1.0)
        d = r + beta * d
        rr = rr_new
    return s, float(jnp.dot(s, hs)), boundary, i


def host_tron(
    value_and_grad: ValueAndGrad,
    hess_vec: HessVec,
    x0: jax.Array,
    *,
    max_iterations: int = 15,
    tolerance: float = 1e-5,
    max_cg_iterations: int = 20,
    iteration_cap: Optional[int] = None,
) -> SolveResult:
    """Host-stepped mirror of optim.tron.tron (same eta/sigma constants,
    radius update, and failure cap); `iteration_cap` mirrors the resident
    solver's dynamic budget (host-stepped, so never a recompile)."""
    cap = (max_iterations if iteration_cap is None
           else max(1, min(int(iteration_cap), max_iterations)))
    tolerance = float(tolerance)
    x0 = jnp.asarray(x0)
    dtype = x0.dtype
    f, g = value_and_grad(x0)
    x = x0
    gnorm = float(jnp.linalg.norm(g))
    gtol = tolerance * max(gnorm, 1.0)
    delta = gnorm
    failures = 0
    hv_total = 0
    loss_hist = [float(f)]
    gnorm_hist = [gnorm]
    reason = (ConvergenceReason.GRADIENT_CONVERGED if gnorm <= gtol
              else ConvergenceReason.NOT_CONVERGED)
    k = 0
    while k < cap and reason == ConvergenceReason.NOT_CONVERGED:
        s, shs, hit, cg_n = _host_truncated_cg(hess_vec, x, g, delta,
                                               max_cg_iterations)
        hv_total += cg_n
        gs = float(jnp.dot(g, s))
        pred = -(gs + 0.5 * shs)
        x_try = x + s
        f_try, g_try = value_and_grad(x_try)
        f_try_f = float(f_try)
        actual = float(f) - f_try_f
        rho = (actual / (pred if pred > 0 else 1.0)
               if np.isfinite(f_try_f) else -np.inf)
        snorm = float(jnp.linalg.norm(s))

        accept = rho > _ETA0 and pred > 0 and np.isfinite(f_try_f)
        if rho < _ETA1:
            delta = _SIG1 * min(snorm, delta)
        elif rho > _ETA2 and hit:
            delta = _SIG3 * delta

        if accept:
            x, f, g = x_try, f_try, g_try
            gnorm = float(jnp.linalg.norm(g_try))
            failures = 0
        else:
            failures += 1

        if gnorm <= gtol:
            reason = ConvergenceReason.GRADIENT_CONVERGED
        elif failures >= _MAX_FAILURES:
            reason = ConvergenceReason.TRUST_REGION_EXHAUSTED

        k += 1
        loss_hist.append(float(f))
        gnorm_hist.append(gnorm)

    if reason == ConvergenceReason.NOT_CONVERGED:
        reason = ConvergenceReason.MAX_ITERATIONS
    return SolveResult(
        x=x, value=f, gradient_norm=jnp.asarray(gnorm, dtype),
        iterations=jnp.asarray(k, jnp.int32),
        reason=jnp.asarray(int(reason), jnp.int32),
        loss_history=_hist(loss_hist, max_iterations + 1, dtype),
        gnorm_history=_hist(gnorm_hist, max_iterations + 1, dtype),
        coefficient_history=None,
        hv_count=jnp.asarray(hv_total, jnp.int32))


def solve_streamed(
    objective,
    x0: jax.Array,
    config: OptimizerConfig = OptimizerConfig(),
    reg: RegularizationContext = RegularizationContext(),
    reg_weight: jax.Array | float = 0.0,
    budget=None,
    stochastic=None,
) -> SolveResult:
    """solve() for a ChunkedGLMObjective: same dispatch rules as
    optim.config.solve (L2 into the smooth objective, L1 to OWLQN, TRON
    constraints), driving the host-stepped loops above.

    `budget` (optim.schedule.SolveBudget) overrides the iteration cap and
    tolerance for this solve — the host-stepped loop branches on host
    scalars, so a budget schedule never compiles anything new here by
    construction.

    `stochastic` (optim.schedule.StochasticPlan) routes the solve to the
    COARSE lane instead: `passes` stochastic passes over the chunk
    stream, each staged chunk pinned for `local_epochs` of seeded
    coordinate descent (optim/stochastic.py) — the per-staged-byte-cheap
    mode SolverSchedule uses on early outer iterations, with these strict
    host-stepped solvers as the final polish.  The lane handles smooth
    L2-regularized objectives; L1 (OWLQN) and box-constrained solves fall
    through to the strict lane (their prox/projection structure is the
    host-stepped solver's job)."""
    cfg = config.resolved()
    if cfg.constraints is not None:
        raise ValueError(
            "named feature constraints are unresolved — call "
            "config.resolved_constraints(index_map) before solve_streamed()")
    l1_w, l2_w = reg.split(reg_weight)
    obj = objective.with_l2(l2_w)

    if stochastic is not None and stochastic.passes > 0 \
            and not reg.has_l1 \
            and cfg.box_lower is None and cfg.box_upper is None:
        from photon_ml_tpu.optim.stochastic import solve_stochastic
        return solve_stochastic(obj, x0, stochastic,
                                max_iterations=cfg.max_iterations)
    iteration_cap = None if budget is None else int(budget.iteration_cap)
    tolerance = cfg.tolerance if budget is None else float(budget.tolerance)

    if cfg.optimizer == OptimizerType.TRON:
        if reg.has_l1:
            raise ValueError("TRON supports only L2/none regularization "
                             "(reference: OptimizerFactory constraint)")
        if not objective.loss.twice_differentiable:
            raise ValueError(f"{objective.loss.name} is not twice "
                             "differentiable; use LBFGS")
        if cfg.box_lower is not None or cfg.box_upper is not None:
            raise ValueError("box constraints are an LBFGS feature "
                             "(reference: LBFGS.scala:72)")
        return host_tron(obj.value_and_gradient, obj.hessian_vector, x0,
                         max_iterations=cfg.max_iterations,
                         tolerance=tolerance,
                         max_cg_iterations=cfg.max_cg_iterations,
                         iteration_cap=iteration_cap)

    x0 = jnp.asarray(x0)
    lower = (None if cfg.box_lower is None
             else jnp.asarray(cfg.box_lower, x0.dtype))
    upper = (None if cfg.box_upper is None
             else jnp.asarray(cfg.box_upper, x0.dtype))
    return host_lbfgs(obj.value_and_gradient, x0,
                      max_iterations=cfg.max_iterations,
                      tolerance=tolerance, history=cfg.history,
                      l1_weight=l1_w if reg.has_l1 else None,
                      lower=lower, upper=upper,
                      iteration_cap=iteration_cap)
