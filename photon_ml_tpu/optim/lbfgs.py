"""LBFGS and OWLQN as pure jittable/vmappable lax.while_loop programs.

Role of the reference's LBFGS/OWLQN adaptors over breeze.optimize
(photon-lib/.../optimization/LBFGS.scala:39-156, OWLQN.scala:40-86).  Unlike
the reference — which hands the loop to a JVM library and streams RDD
aggregates per iteration — the whole optimization is one XLA program:

  * runs on-device with zero host round-trips per iteration;
  * vmaps: thousands of independent per-entity solves (random effects)
    batch into one kernel, replacing Spark task-per-entity parallelism
    (reference: SingleNodeOptimizationProblem run inside executor tasks);
  * shard_maps: when the objective's data is sharded over a mesh axis, the
    caller wraps value/grad in psum and this loop is unchanged (fixed
    effects).

Design notes
------------
- Two-loop recursion over a history kept in AGE ORDER, one array a slot
  (`_History`): slot 0 is the newest pair, a stored pair moves every slot
  one older (`_push`, a `where` per slot), and step i of either loop reads
  slot i by a static index.  Why not a ring in an [m, d] buffer: under vmap
  lanes store different numbers of pairs, so a ring's slot differs by lane,
  every read is a gather from and every write a scatter into an [E, m, d]
  buffer, and the TPU tiles that over its two small dimensions (ten times
  its bytes, streamed whole for each row taken: PERF.md, PR 28).  Here every
  leaf is [E, d] like x, g and p.  The push costs a lane that is not
  vmapped a copy of 2 m d numbers an iteration, beside two passes over the
  data.  A pair counter says how many slots are valid; pairs with
  non-positive curvature s.y are skipped (standard safeguard).
- Backtracking Armijo line search on the *actual displacement* so box
  projection (clamp-to-hypercube each trial point, reference:
  OptimizationUtils.scala:40-70 projection used by LBFGS.scala:72) is
  correct: acceptance tests f(P(x+t p)) <= f + c1 g.(P(x+t p) - x).
- OWLQN (l1_weight > 0): Andrew & Gao pseudo-gradient steering, direction
  sign-projection, orthant-constrained trial points, Armijo on
  f + l1*|x|_1.  The l1 weight may be a scalar or per-coordinate array
  (used to exempt the intercept).  L1 is a *traced* value: lambda sweeps
  reuse one compiled program (the reference instead mutates a closure:
  OWLQN.scala:81-86).
- Defaults follow the reference: max_iterations=100, tolerance=1e-7, m=10
  (LBFGS.scala:151-156).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from photon_ml_tpu.optim.types import ConvergenceReason, SolveResult

ValueAndGrad = Callable[[jax.Array], Tuple[jax.Array, jax.Array]]

_C1 = 1e-4          # Armijo sufficient-decrease constant
_MAX_LS = 30        # max backtracking halvings
_CURV_EPS = 1e-12   # curvature-pair acceptance threshold


class _History(NamedTuple):
    """The last m curvature pairs, newest first: slot i of each tuple is the
    pair stored i pushes ago.  One array a slot, so a slot is a static index
    in every lane of a vmapped solve."""
    s: Tuple[jax.Array, ...]     # m displacements, each [d]
    y: Tuple[jax.Array, ...]     # m gradient differences, each [d]
    rho: Tuple[jax.Array, ...]   # m scalars 1/(s.y)


class _State(NamedTuple):
    k: jax.Array            # iteration counter
    x: jax.Array            # [d]
    f: jax.Array            # objective at x (incl. L1 term for OWLQN)
    g: jax.Array            # raw gradient at x (no L1)
    hist: _History          # the pairs, slot 0 the newest
    num_pairs: jax.Array    # pairs stored so far; slot i is valid below it
    f_small: jax.Array      # consecutive sub-tolerance f-changes
    ls_trials: jax.Array    # trial points evaluated: first trials + backtracks
    reason: jax.Array
    loss_hist: jax.Array
    gnorm_hist: jax.Array
    coef_hist: "jax.Array | None"   # [max_iter+1, d] when tracking, else None
    z: "jax.Array | None" = None    # margins at x on the margin path, else None
    # under a lane axis: the trials the lock step ran and those its running
    # lanes needed, summed over the trips this lane ran; else None.  Two
    # scalars and not one [2]: vmapped, a [E, 2] carry is tiled to [E, 128]
    ran: "jax.Array | None" = None
    needed: "jax.Array | None" = None


# In float32 a single step's progress can round to an exact zero f-change
# while the solve is far from done (the value's resolution is ~1.2e-7
# relative; the reference runs in JVM double where this cannot happen).
# Function-value convergence therefore requires this many CONSECUTIVE
# sub-tolerance changes before it is declared.
_F_CONV_PERSISTENCE = 3


def _pseudo_gradient(x, g, l1):
    """OWLQN pseudo-gradient of f + l1*|x|_1 (Andrew & Gao 2007)."""
    gp = g + l1 * jnp.sign(x)
    # at x_i == 0 the subgradient interval is [g-l1, g+l1]; steepest descent:
    lo, hi = g - l1, g + l1
    at_zero = jnp.where(hi < 0, hi, jnp.where(lo > 0, lo, 0.0))
    return jnp.where(x != 0, gp, at_zero)


def _empty_history(m, d, dtype) -> _History:
    zero, zeros = jnp.zeros((), dtype), jnp.zeros((d,), dtype)
    return _History(s=(zeros,) * m, y=(zeros,) * m, rho=(zero,) * m)


def _push(hist: _History, store, s, y, sy) -> _History:
    """Where `store`, (s, y, 1/sy) enters at slot 0, every slot moves one
    older and the oldest leaves; elsewhere every slot keeps its value."""
    def shift(slots, new):
        return tuple(jnp.where(store, younger, slot)
                     for younger, slot in zip((new,) + slots[:-1], slots))

    return _History(s=shift(hist.s, s), y=shift(hist.y, y),
                    rho=shift(hist.rho, 1.0 / jnp.where(store, sy, 1.0)))


def _two_loop(q, hist: _History, num_pairs):
    """Standard two-loop recursion; step i reads slot i, the pair stored i
    pushes ago, which exists once more than i pairs were stored."""
    m = len(hist.rho)
    valid = [num_pairs > i for i in range(m)]
    alphas = []
    for i in range(m):              # newest first
        a = jnp.where(valid[i], hist.rho[i] * jnp.dot(hist.s[i], q), 0.0)
        q = q - a * hist.y[i]
        alphas.append(a)

    # H0 scaling from the newest pair
    sy = jnp.dot(hist.s[0], hist.y[0])
    yy = jnp.dot(hist.y[0], hist.y[0])
    gamma = jnp.where(valid[0] & (yy > 0), sy / jnp.where(yy > 0, yy, 1.0), 1.0)
    r = gamma * q

    for i in reversed(range(m)):    # oldest stored first
        b = jnp.where(valid[i], hist.rho[i] * jnp.dot(hist.y[i], r), 0.0)
        r = r + jnp.where(valid[i], alphas[i] - b, 0.0) * hist.s[i]
    return r


def lbfgs(
    value_and_grad: Optional[ValueAndGrad],
    x0: jax.Array,
    *,
    max_iterations: int = 100,
    tolerance: float = 1e-7,
    history: int = 10,
    l1_weight: Optional[jax.Array | float] = None,
    lower: Optional[jax.Array] = None,
    upper: Optional[jax.Array] = None,
    track_coefficients: bool = False,
    iteration_cap: Optional[jax.Array] = None,
    margin_surface=None,
    lane_axis: Optional[str] = None,
) -> SolveResult:
    """Minimize f (+ optional l1*|x|_1, making this OWLQN) from x0.

    `value_and_grad` must be the SMOOTH part only; L1 is handled in here via
    pseudo-gradients exactly because it is non-smooth (reference:
    OWLQN.scala).  `lower`/`upper` activate per-coordinate box projection
    (reference: LBFGS.scala:72 + OptimizationUtils.scala:40-70); box and L1
    are mutually exclusive, as in the reference.

    `max_iterations` is the STATIC ceiling: it sizes the loss and
    gradient-norm histories and bounds the compiled loop.  `iteration_cap`
    (and `tolerance`) may be TRACED scalars — the loop condition tests the
    dynamic cap, so an inexactness schedule that varies the budget per
    coordinate-descent outer iteration reuses one compiled program
    (optim/schedule.py).

    How a trial point is evaluated.  Given `value_and_grad` every trial,
    the first and each backtrack, evaluates the FUSED value+gradient at
    the trial point: 2 X-reads a trial.  That is the only way when the
    trial point is not `x + t p` (OWLQN's orthant projection, the box) or
    when all the caller has is `value_and_grad`.

    `margin_surface` REPLACES `value_and_grad` (pass None for it; it is
    never called): optim.config.solve passes the GLMObjective when there is
    neither L1 nor a box.  It is an object with `margins(x)`,
    `direction_margins(p)`, `value_from_margins(z, x)` and
    `gradient_from_margins(z, x)` whose margins are affine in x:
    z(x + t p) = z(x) + t u.  The loop then carries z, computes u = X p once
    an iteration (one X-read), evaluates EVERY trial as
    value_from_margins(z + t u, x + t p) with no X-read, and assembles the
    gradient once, at the accepted point (one X-read): 2 X-reads an
    iteration however often the search backtracks.  Under vmap the search
    runs until the LAST lane accepts, so without this one lane at its
    float32 optimum makes every lane re-read its block 10 to 19 times an
    iteration (PERF.md, PR 26).  Direction, trial sequence, Armijo test and
    convergence tests are the same on both paths; iterates differ by the
    rounding of z + t u against X (x + t p).  The value and gradient norm
    RETURNED are recomputed from fresh margins X x at the final x, so a
    drift of the carried z never reaches a caller.

    `lane_axis` names the `jax.vmap` axis this solve runs under in lock step
    with other lanes (parallel/random_effect.py).  There the loop's
    predicate is batched: the body runs for every lane on every trip, the
    search inside it while ANY lane's search condition holds (an ended
    lane's too, on its frozen state), and a select keeps only the running
    lanes' carry, so no lane's own `ls_trials` counts what the device ran.
    Each trip therefore adds to a carried pair the lanes' largest
    backtrack count (`lax.pmax`) and the largest among the lanes still
    running, each plus the first trial; the last lane to end has added
    every trip's.  `lockstep` returns the two and the passes the lock
    step read as this lane saw it: `fg_count` on cached margins (the lane
    that ran most trips read trips + 2), else one a lock-step trial and the
    first.  Nothing else reads it.
    """
    use_l1 = l1_weight is not None
    use_box = lower is not None or upper is not None
    if use_l1 and use_box:
        raise ValueError("L1 (OWLQN) and box constraints cannot be combined "
                         "(the reference has no such solver either)")
    use_margins = margin_surface is not None
    if use_margins and (use_l1 or use_box):
        raise ValueError("the margin surface evaluates trial points x + t p; "
                         "the orthant and box projections are not affine in t")
    d = x0.shape[-1]
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
        """Coordinates pinned at an active bound (descent would exit the box).
        The projected gradient zeroed there is the KKT residual."""
        blocked = jnp.zeros(x.shape, bool)
        if lower is not None:
            blocked = blocked | ((x <= lower) & (g > 0))
        if upper is not None:
            blocked = blocked | ((x >= upper) & (g < 0))
        return blocked

    def steer_grad(x, g):
        """Steering gradient: OWLQN pseudo-gradient under L1; under box
        constraints the PROJECTED gradient, so the two-loop direction lives
        in the free subspace instead of being clipped to a stall by iterate
        projection."""
        if use_l1:
            return _pseudo_gradient(x, g, l1)
        if use_box:
            return jnp.where(box_blocked(x, g), 0.0, g)
        return g

    def full_value(x):
        """Value + gradient of the acceptance objective (smooth + L1 term)."""
        v, g = value_and_grad(x)
        if use_l1:
            v = v + jnp.sum(l1 * jnp.abs(x))
        return v, g

    def at_point(x):
        """(f, g, margins or None) at x from scratch: 2 X-reads."""
        if not use_margins:
            return (*full_value(x), None)
        z = margin_surface.margins(x)
        return (margin_surface.value_from_margins(z, x),
                margin_surface.gradient_from_margins(z, x), z)

    cap = (max_iterations if iteration_cap is None
           else jnp.minimum(jnp.asarray(iteration_cap, jnp.int32),
                            max_iterations))
    x0 = project_box(x0)
    f0, g0, z0 = at_point(x0)
    gnorm0 = jnp.linalg.norm(steer_grad(x0, g0))
    # relative gradient convergence, like breeze's default convergence check
    gtol = tolerance * jnp.maximum(gnorm0, 1.0)

    nan = jnp.asarray(jnp.nan, dtype)
    init = _State(
        k=jnp.asarray(0, jnp.int32),
        x=x0, f=f0, g=g0,
        hist=_empty_history(history, d, dtype),
        num_pairs=jnp.asarray(0, jnp.int32),
        f_small=jnp.asarray(0, jnp.int32),
        ls_trials=jnp.asarray(0, jnp.int32),
        reason=jnp.asarray(ConvergenceReason.NOT_CONVERGED, jnp.int32),
        loss_hist=jnp.full((max_iterations + 1,), nan).at[0].set(f0),
        gnorm_hist=jnp.full((max_iterations + 1,), nan).at[0].set(gnorm0),
        coef_hist=(jnp.full((max_iterations + 1, d), nan).at[0].set(x0)
                   if track_coefficients else None),
        z=z0,
        ran=None if lane_axis is None else jnp.asarray(0, jnp.int32),
        needed=None if lane_axis is None else jnp.asarray(0, jnp.int32),
    )

    def cond(st: _State):
        return (st.k < cap) & (st.reason == ConvergenceReason.NOT_CONVERGED)

    def body(st: _State) -> _State:
        steer = steer_grad(st.x, st.g)
        p = -_two_loop(steer, st.hist, st.num_pairs)
        if use_l1:
            # direction must agree with -pseudo-gradient sign-wise
            p = jnp.where(p * (-steer) > 0, p, 0.0)
            orthant = jnp.where(st.x != 0, jnp.sign(st.x), jnp.sign(-steer))
        if use_box:
            # keep the step in the free subspace: a component against an
            # active bound would be clipped by projection anyway, but leaving
            # it in corrupts the Armijo displacement and the curvature pairs
            p = jnp.where(box_blocked(st.x, st.g), 0.0, p)
        dd = jnp.dot(steer, p)
        # fall back to steepest descent if not a descent direction
        bad = dd >= 0
        p = jnp.where(bad, -steer, p)
        dd = jnp.where(bad, -jnp.dot(steer, steer), dd)

        # first iteration: scale so the first trial step is modest
        t0 = jnp.where(st.num_pairs == 0,
                       1.0 / jnp.maximum(jnp.linalg.norm(p), 1.0), 1.0)

        def trial(t):
            xt = st.x + t * p
            if use_l1:
                xt = jnp.where(xt * orthant > 0, xt, 0.0)
            return project_box(xt)

        def armijo_ok(xt, ft):
            # Armijo on actual displacement (correct under projection)
            return (ft <= st.f + _C1 * jnp.dot(steer, xt - st.x)) & jnp.isfinite(ft)

        if use_margins:
            u = margin_surface.direction_margins(p)

            def evaluate(t):
                """Value at the trial point, from margins; nothing to keep:
                x, z and g at the accepted step are made once, after the
                search, so the search carries per-lane scalars only."""
                xt = trial(t)
                return xt, margin_surface.value_from_margins(st.z + t * u,
                                                             xt), ()
        else:
            def evaluate(t):
                """Fused value+gradient at the trial point, both kept."""
                xt = trial(t)
                ft, gt = full_value(xt)
                return xt, ft, (xt, gt)

        def ls_cond(c):
            t, ls_iter, done, *_ = c
            return (~done) & (ls_iter < _MAX_LS)

        def ls_body(c):
            t, ls_iter, *_ = c
            t = t * 0.5
            xt, ft, kept = evaluate(t)
            return t, ls_iter + 1, armijo_ok(xt, ft), ft, kept

        t0 = jnp.asarray(t0, dtype)
        xt0, ft0, kept0 = evaluate(t0)
        t, ls_n, ls_ok, f_new, kept = lax.while_loop(
            ls_cond, ls_body,
            (t0, jnp.asarray(0, jnp.int32), armijo_ok(xt0, ft0), ft0, kept0))
        ran = needed = None
        if lane_axis is not None:
            ran = st.ran + 1 + lax.pmax(ls_n, lane_axis)
            needed = st.needed + 1 + lax.pmax(jnp.where(cond(st), ls_n, 0),
                                              lane_axis)
        if use_margins:
            x_new = trial(t)
            z_new = st.z + t * u
            g_new = margin_surface.gradient_from_margins(z_new, x_new)
        else:
            (x_new, g_new), z_new = kept, None

        # curvature pair from raw gradients (standard OWLQN choice)
        s = x_new - st.x
        yv = g_new - st.g
        if use_box:
            # restrict the pair to the free subspace at the accepted point:
            # gradient deltas on pinned coordinates are not curvature the
            # free-space two-loop should learn
            bl = box_blocked(x_new, g_new)
            s = jnp.where(bl, 0.0, s)
            yv = jnp.where(bl, 0.0, yv)
        sy = jnp.dot(s, yv)
        store = ls_ok & (sy > _CURV_EPS)
        hist = _push(st.hist, store, s, yv, sy)
        num_pairs = st.num_pairs + jnp.where(store, 1, 0)

        gnorm_new = jnp.linalg.norm(steer_grad(x_new, g_new))
        # convergence checks (reference Optimizer.scala:136-150 reasons).
        # Strictly under: tolerance 0 switches the function-value check off
        # (a float32 objective at its floor repeats exactly, and a fit that
        # states its iteration count must not end on that)
        f_small_now = jnp.abs(st.f - f_new) < tolerance * jnp.maximum(
            jnp.maximum(jnp.abs(st.f), jnp.abs(f_new)), 1.0)
        f_small = jnp.where(f_small_now, st.f_small + 1, 0)
        f_conv = f_small >= _F_CONV_PERSISTENCE
        g_conv = gnorm_new <= gtol
        reason = jnp.where(
            ~ls_ok, ConvergenceReason.LINE_SEARCH_FAILED,
            jnp.where(g_conv, ConvergenceReason.GRADIENT_CONVERGED,
                      jnp.where(f_conv, ConvergenceReason.FUNCTION_VALUES_CONVERGED,
                                ConvergenceReason.NOT_CONVERGED))).astype(jnp.int32)

        # on line-search failure keep the previous iterate
        x_new = jnp.where(ls_ok, x_new, st.x)
        f_new = jnp.where(ls_ok, f_new, st.f)
        g_new = jnp.where(ls_ok, g_new, st.g)
        if use_margins:
            z_new = jnp.where(ls_ok, z_new, st.z)
        gnorm_new = jnp.where(ls_ok, gnorm_new, st.gnorm_hist[st.k])

        k = st.k + 1
        return _State(
            k=k, x=x_new, f=f_new, g=g_new,
            hist=hist, num_pairs=num_pairs,
            f_small=f_small,
            ls_trials=st.ls_trials + 1 + ls_n,  # first trial + backtracks
            reason=reason,
            loss_hist=st.loss_hist.at[k].set(f_new),
            gnorm_hist=st.gnorm_hist.at[k].set(gnorm_new),
            coef_hist=(None if st.coef_hist is None
                       else st.coef_hist.at[k].set(x_new)),
            z=z_new,
            ran=ran,
            needed=needed,
        )

    st = lax.while_loop(cond, body, init)
    reason = jnp.where(st.reason == ConvergenceReason.NOT_CONVERGED,
                       jnp.asarray(ConvergenceReason.MAX_ITERATIONS, jnp.int32),
                       st.reason)
    if use_margins:
        # what is reported comes from fresh margins X x, not the carried z
        value, g, _ = at_point(st.x)
        gnorm_final = jnp.linalg.norm(g)
        fg_count = st.k + 2     # f0/g0, (u, g) an iteration, this refresh
    else:
        value, gnorm_final = st.f, st.gnorm_hist[st.k]
        fg_count = st.ls_trials + 1     # f0/g0 and every trial
    lockstep = None
    if lane_axis is not None:
        lockstep = (st.ran, st.needed,
                    fg_count if use_margins else st.ran + 1)
    return SolveResult(x=st.x, value=value, gradient_norm=gnorm_final,
                       iterations=st.k, reason=reason,
                       loss_history=st.loss_hist, gnorm_history=st.gnorm_hist,
                       coefficient_history=st.coef_hist,
                       fg_count=fg_count, ls_trials=st.ls_trials,
                       lockstep=lockstep)


def owlqn(value_and_grad: ValueAndGrad, x0: jax.Array, *, l1_weight,
          max_iterations: int = 100, tolerance: float = 1e-7,
          history: int = 10,
          iteration_cap: Optional[jax.Array] = None) -> SolveResult:
    """L1/elastic-net solver (reference: OWLQN.scala:40-86).  The L2 part of
    elastic net lives in the smooth objective; only L1 comes through here."""
    return lbfgs(value_and_grad, x0, max_iterations=max_iterations,
                 tolerance=tolerance, history=history, l1_weight=l1_weight,
                 iteration_cap=iteration_cap)
