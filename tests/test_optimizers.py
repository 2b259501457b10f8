"""Optimizer correctness: closed-form quadratics, GLM fits vs scipy,
L1 sparsity, box constraints, jit/vmap compatibility.

Mirrors the reference's optimizer suite (photon-lib/src/test/.../optimization/
{OptimizerTest,LBFGSTest,OWLQNTest}.scala against TestObjective closed forms),
plus TPU-specific requirements the reference never had: the whole solve must
run under jit and vmap.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from scipy.optimize import minimize

from photon_ml_tpu.ops import (
    LOGISTIC, POISSON, SMOOTHED_HINGE, SQUARED, GLMObjective,
)
from photon_ml_tpu.optim import (
    ConvergenceReason, OptimizerConfig, OptimizerType, RegularizationContext,
    RegularizationType, lbfgs, solve, tron,
)
from tests.synthetic import make_glm_data


def _quad(center, scales):
    """f(x) = 0.5 sum scales_i (x_i - center_i)^2 — the reference's
    TestObjective style closed form."""
    center = jnp.asarray(center)
    scales = jnp.asarray(scales)

    def vg(x):
        return 0.5 * jnp.sum(scales * (x - center) ** 2), scales * (x - center)

    def hv(x, v):
        return scales * v

    return vg, hv


def test_lbfgs_quadratic_exact():
    vg, _ = _quad([1.0, -2.0, 3.0], [1.0, 4.0, 0.5])
    res = lbfgs(vg, jnp.zeros(3))
    np.testing.assert_allclose(res.x, [1.0, -2.0, 3.0], atol=1e-5)
    assert int(res.reason) in (ConvergenceReason.GRADIENT_CONVERGED,
                               ConvergenceReason.FUNCTION_VALUES_CONVERGED)
    # tracker: loss history is monotone non-increasing over recorded iters
    lh = np.asarray(res.loss_history)[: int(res.iterations) + 1]
    assert np.all(np.diff(lh) <= 1e-12)


def test_tron_quadratic_exact():
    vg, hv = _quad([1.0, -2.0, 3.0], [1.0, 4.0, 0.5])
    res = tron(vg, hv, jnp.zeros(3))
    np.testing.assert_allclose(res.x, [1.0, -2.0, 3.0], atol=1e-6)


@pytest.mark.parametrize("opt", [OptimizerType.LBFGS, OptimizerType.TRON])
@pytest.mark.parametrize("loss,task", [(LOGISTIC, "logistic"), (SQUARED, "linear"),
                                       (POISSON, "poisson")])
def test_glm_fit_matches_scipy(opt, loss, task, rng):
    x, y, w, _ = make_glm_data(rng, n=300, d=8, task=task, weight_range=(0.5, 2.0))
    obj = GLMObjective(loss, jnp.asarray(x), jnp.asarray(y),
                       weights=jnp.asarray(w), l2_weight=0.1)
    res = solve(obj, jnp.zeros(8), OptimizerConfig(optimizer=opt),
                RegularizationContext(RegularizationType.L2), 0.1)

    ref = minimize(lambda c: tuple(np.asarray(v) for v in
                                   obj.value_and_gradient(jnp.asarray(c))),
                   np.zeros(8), jac=True, method="L-BFGS-B",
                   options={"ftol": 1e-14, "gtol": 1e-10})
    # same optimum, loss parity well within the 1e-4 target
    assert abs(float(res.value) - ref.fun) / max(1.0, abs(ref.fun)) < 1e-6
    np.testing.assert_allclose(res.x, ref.x, rtol=1e-3, atol=1e-4)


def test_owlqn_produces_sparse_solution(rng):
    x, y, _, _ = make_glm_data(rng, n=400, d=20, task="logistic")
    # make half the features pure noise
    x[:, 10:19] = rng.normal(size=(400, 9)) * 0.01
    obj = GLMObjective(LOGISTIC, jnp.asarray(x), jnp.asarray(y))
    res = solve(obj, jnp.zeros(20), OptimizerConfig(),
                RegularizationContext(RegularizationType.L1), 5.0)
    assert int(jnp.sum(res.x == 0.0)) >= 5, "L1 at lambda=5 should zero noise features"

    # sanity: the L1 objective value must beat the zero vector
    l1_obj = float(obj.value(res.x) + 5.0 * jnp.sum(jnp.abs(res.x)))
    assert l1_obj < float(obj.value(jnp.zeros(20)))


def test_owlqn_matches_unregularized_when_lambda_zero(rng):
    x, y, _, _ = make_glm_data(rng, n=200, d=6, task="logistic")
    obj = GLMObjective(LOGISTIC, jnp.asarray(x), jnp.asarray(y), l2_weight=0.05)
    a = lbfgs(obj.value_and_gradient, jnp.zeros(6))
    b = lbfgs(obj.value_and_gradient, jnp.zeros(6), l1_weight=0.0)
    np.testing.assert_allclose(a.value, b.value, rtol=1e-8)


def test_elastic_net_split(rng):
    x, y, _, _ = make_glm_data(rng, n=200, d=10, task="logistic")
    obj = GLMObjective(LOGISTIC, jnp.asarray(x), jnp.asarray(y))
    reg = RegularizationContext(RegularizationType.ELASTIC_NET, elastic_net_alpha=0.5)
    res = solve(obj, jnp.zeros(10), OptimizerConfig(), reg, 2.0)
    # elastic net with alpha=.5, lambda=2: l1=1, l2=1 — compare against
    # solving the same composite directly
    res2 = lbfgs(obj.with_l2(1.0).value_and_gradient, jnp.zeros(10), l1_weight=1.0)
    np.testing.assert_allclose(res.value, res2.value, rtol=1e-10)


def test_box_constraints_respected_and_optimal(rng):
    x, y, _, _ = make_glm_data(rng, n=300, d=5, task="linear")
    obj = GLMObjective(SQUARED, jnp.asarray(x), jnp.asarray(y), l2_weight=0.01)
    lower = jnp.asarray([-0.1, -0.1, -0.1, -0.1, -0.1])
    upper = jnp.asarray([0.1, 0.1, 0.1, 0.1, 0.1])
    res = lbfgs(obj.value_and_gradient, jnp.zeros(5), lower=lower, upper=upper)
    assert bool(jnp.all(res.x >= lower - 1e-12)) and bool(jnp.all(res.x <= upper + 1e-12))

    ref = minimize(lambda c: tuple(np.asarray(v) for v in
                                   obj.value_and_gradient(jnp.asarray(c))),
                   np.zeros(5), jac=True, method="L-BFGS-B",
                   bounds=[(-0.1, 0.1)] * 5, options={"ftol": 1e-14})
    assert float(res.value) <= ref.fun * (1 + 1e-5) + 1e-8


def test_solve_under_jit_and_vmap(rng):
    """The TPU contract: whole solves compile and batch.  This is what
    replaces the reference's per-entity executor tasks."""
    d = 4
    xs, ys = [], []
    for _ in range(8):
        x, y, _, _ = make_glm_data(rng, n=50, d=d, task="logistic")
        xs.append(x); ys.append(y)
    xb = jnp.asarray(np.stack(xs))   # [8, 50, d]
    yb = jnp.asarray(np.stack(ys))

    def solve_one(x, y):
        obj = GLMObjective(LOGISTIC, x, y, l2_weight=0.1)
        return lbfgs(obj.value_and_gradient, jnp.zeros(d), max_iterations=50)

    batched = jax.jit(jax.vmap(solve_one))(xb, yb)
    assert batched.x.shape == (8, d)
    # each batched solve must match its standalone solve (one compiled
    # program for the eight rows, not one per closure)
    solve_alone = jax.jit(solve_one)
    for i in range(8):
        single = solve_alone(xb[i], yb[i])
        np.testing.assert_allclose(batched.x[i], single.x, rtol=1e-6, atol=1e-8)

    # TRON under vmap too
    def tron_one(x, y):
        obj = GLMObjective(LOGISTIC, x, y, l2_weight=0.1)
        return tron(obj.value_and_gradient, obj.hessian_vector, jnp.zeros(d))

    tb = jax.jit(jax.vmap(tron_one))(xb, yb)
    np.testing.assert_allclose(tb.x, batched.x, rtol=1e-3, atol=1e-4)


def test_tron_rejects_l1_and_nonsmooth(rng):
    from photon_ml_tpu.ops import SMOOTHED_HINGE
    x, y, _, _ = make_glm_data(rng, n=50, d=3, task="logistic")
    obj = GLMObjective(LOGISTIC, jnp.asarray(x), jnp.asarray(y))
    with pytest.raises(ValueError):
        solve(obj, jnp.zeros(3), OptimizerConfig(optimizer=OptimizerType.TRON),
              RegularizationContext(RegularizationType.L1), 1.0)
    obj_h = GLMObjective(SMOOTHED_HINGE, jnp.asarray(x), jnp.asarray(y))
    with pytest.raises(ValueError):
        solve(obj_h, jnp.zeros(3), OptimizerConfig(optimizer=OptimizerType.TRON))


def test_smoothed_hinge_with_box_constraints(rng):
    """BASELINE config #3: smoothed-hinge SVM with box-constrained coefs."""
    from photon_ml_tpu.ops import SMOOTHED_HINGE
    x, y, _, _ = make_glm_data(rng, n=300, d=6, task="hinge")
    obj = GLMObjective(SMOOTHED_HINGE, jnp.asarray(x), jnp.asarray(y), l2_weight=0.01)
    cfg = OptimizerConfig(box_lower=jnp.full(6, -0.5), box_upper=jnp.full(6, 0.5))
    res = solve(obj, jnp.zeros(6), cfg)
    assert bool(jnp.all(jnp.abs(res.x) <= 0.5 + 1e-12))
    assert float(res.value) < float(obj.value(jnp.zeros(6)))


def test_coefficient_history_tracking(rng):
    """track_coefficients snapshots every iterate (reference: ModelTracker
    per-iteration models); the last snapshot equals the solution and the
    history reproduces the loss table."""
    import jax.numpy as jnp
    from photon_ml_tpu.ops import TASK_LOSSES, GLMObjective
    from tests.synthetic import make_glm_data

    x, y, _, _ = make_glm_data(rng, n=300, d=6)
    obj = GLMObjective(TASK_LOSSES["logistic_regression"],
                       jnp.asarray(x), jnp.asarray(y))
    for opt in (OptimizerType.LBFGS, OptimizerType.TRON):
        cfg = OptimizerConfig(optimizer=opt, max_iterations=30,
                              track_coefficients=True)
        res = solve(obj, jnp.zeros(6), cfg,
                    RegularizationContext(RegularizationType.L2), 0.1)
        hist = np.asarray(res.coefficient_history)
        it = int(res.iterations)
        assert hist.shape[1] == 6
        np.testing.assert_allclose(hist[it], np.asarray(res.x), rtol=1e-7)
        # snapshot i re-evaluates to the recorded loss (accepted iterates)
        l2 = 0.1
        for i in (0, it):
            w = hist[i]
            z = x @ w
            nll = np.logaddexp(0, -np.where(y > 0.5, 1, -1) * z).sum() \
                + 0.5 * l2 * w @ w
            np.testing.assert_allclose(nll, np.asarray(res.loss_history)[i],
                                       rtol=1e-5)
        # default: no history
        res2 = solve(obj, jnp.zeros(6), OptimizerConfig(optimizer=opt),
                     RegularizationContext(RegularizationType.L2), 0.1)
        assert res2.coefficient_history is None


def test_lbfgs_fg_count_counts_every_evaluation():
    """fg_count = initial eval + first trial per iteration + every
    line-search backtrack; it is the honest data-pass count for
    throughput accounting (round-3 bench treated backtracks as free)."""
    import numpy as np

    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.normal(size=(100, 10)))
    b = jnp.asarray(rng.normal(size=100))

    def f(x):
        r = A @ x - b
        return 0.5 * jnp.sum(r * r), A.T @ r

    calls = []

    def counted(x):
        calls.append(1)
        return f(x)

    res = lbfgs(counted, jnp.zeros(10), max_iterations=50)
    # traced once -> can't compare against `calls`; instead check the
    # structural invariant: at least 1 + iterations evaluations, and the
    # count is exact on a rerun with an eval-counting pure_callback-free
    # reference: iterations first trials + initial + backtracks
    assert int(res.fg_count) >= int(res.iterations) + 1
    assert int(res.fg_count) <= int(res.iterations) * (1 + 30) + 1


# -- line search on cached margins (PR 26) ------------------------------------
# solve() hands lbfgs the objective's margin surface when there is neither L1
# nor a box; lbfgs(obj.value_and_gradient, ...) is the generic path, every
# trial a fused value+gradient.  Same algorithm, two ways to evaluate a trial.

_L2 = RegularizationContext(RegularizationType.L2)
_L1 = RegularizationContext(RegularizationType.L1)
_MARGIN_LOSSES = {"logistic": (LOGISTIC, "logistic"), "squared": (SQUARED, "linear"),
                  "poisson": (POISSON, "poisson"),
                  "smoothed_hinge": (SMOOTHED_HINGE, "hinge")}


def _margin_objective(loss_name, features, variant, dtype, n=240, d=7):
    """Every variant carries weights, offsets, a mask and a normalisation
    (the ones it does not vary are 1, 0, 1 and the identity, which change
    no float), so the three are ONE pytree structure and share a compiled
    program: only loss, feature format and dtype retrace."""
    from photon_ml_tpu.ops.features import PaddedSparse
    from photon_ml_tpu.ops.normalization import NormalizationContext
    loss, task = _MARGIN_LOSSES[loss_name]
    rng = np.random.default_rng(11)
    x, y, _, _ = make_glm_data(rng, n=n, d=d, task=task)
    x[rng.uniform(size=x.shape) < 0.4] = 0.0       # something to be sparse about
    x[:, -1] = 1.0
    weights, offsets, mask = np.ones(n), np.zeros(n), np.ones(n)
    factors, shifts = np.ones(d), np.zeros(d)
    if variant == "weights_offsets_mask":
        weights = rng.uniform(0.5, 2.0, n)
        offsets = 0.3 * rng.normal(size=n)
        mask = rng.uniform(size=n) < 0.8
    elif variant == "normalised":
        factors = rng.uniform(0.5, 2.0, d); factors[-1] = 1.0
        shifts = 0.2 * rng.normal(size=d); shifts[-1] = 0.0
    xd = jnp.asarray(x, dtype)
    xf = PaddedSparse.from_dense(xd) if features == "padded_sparse" else xd
    return GLMObjective(
        loss, xf, jnp.asarray(y, dtype), weights=jnp.asarray(weights, dtype),
        offsets=jnp.asarray(offsets, dtype), mask=jnp.asarray(mask, dtype),
        norm=NormalizationContext(jnp.asarray(factors, dtype),
                                  jnp.asarray(shifts, dtype), d - 1))


_MARGIN_LAM = 1.0


@jax.jit
def _margin_and_generic_solves(o, x0, cap, tol):
    """(solve on cached margins, the generic lbfgs) of one objective: a
    module-level jit, so the cases that differ only in data share it."""
    from photon_ml_tpu.optim.schedule import SolveBudget
    return (solve(o, x0, OptimizerConfig(), _L2, _MARGIN_LAM,
                  budget=SolveBudget(cap, tol)),
            lbfgs(o.with_l2(jnp.asarray(_MARGIN_LAM, x0.dtype))
                  .value_and_gradient, x0, tolerance=tol, iteration_cap=cap))


@jax.jit
def _least_curvature(o, x):
    return jnp.linalg.eigvalsh(jax.hessian(o.value)(x))[0]


@pytest.mark.parametrize("dtype", ["float64", "float32"])
@pytest.mark.parametrize("variant", ["plain", "weights_offsets_mask", "normalised"])
@pytest.mark.parametrize("features", ["dense", "padded_sparse"])
@pytest.mark.parametrize("loss_name", list(_MARGIN_LOSSES))
def test_margin_line_search_matches_generic_path(loss_name, features, variant, dtype):
    dtype = jnp.dtype(dtype)
    obj = _margin_objective(loss_name, features, variant, dtype)
    x0 = jnp.zeros(obj.dim, dtype)
    l2 = obj.with_l2(jnp.asarray(_MARGIN_LAM, dtype))

    def both(o, cap, tol):
        return _margin_and_generic_solves(o, x0, cap, tol)

    def counted(on_margins, generic):
        assert int(on_margins.fg_count) == int(on_margins.iterations) + 2
        assert int(generic.fg_count) == int(generic.ls_trials) + 1

    if dtype == jnp.float64:
        on_margins, generic = both(obj, 100, jnp.asarray(1e-7, dtype))
        counted(on_margins, generic)
        k_m, k_g = int(on_margins.iterations), int(generic.iterations)
        assert int(on_margins.reason) == int(generic.reason)
        assert abs(k_m - k_g) <= 1
        np.testing.assert_allclose(on_margins.x, generic.x, rtol=1e-9, atol=1e-12)
        k = min(k_m, k_g) + 1
        np.testing.assert_allclose(on_margins.loss_history[:k],
                                   generic.loss_history[:k], rtol=1e-9)
        assert int(on_margins.ls_trials) == int(generic.ls_trials) + k_m - k_g
        return
    # float32, while f still resolves the steps (the easiest of these
    # problems sits at its floor after five iterations): the iterates agree
    zero = jnp.asarray(0.0, dtype)
    on_margins, generic = both(obj, 4, zero)
    counted(on_margins, generic)
    assert int(on_margins.iterations) == int(generic.iterations) == 4
    assert float(jnp.linalg.norm(on_margins.x - generic.x)) <= (
        1e-4 * float(jnp.linalg.norm(generic.x)))
    # float32, cap 100, run down to its floor (8 to 21 iterations, then f
    # stops changing).  The returned value is held to fresh margins X x (the
    # exit refresh) and to the generic path's; x to the ball that float32's
    # f cannot see into: one ulp of f decides which trial is accepted, on
    # either path, so each stops within sqrt(2 ulp(f) / lambda_min) of the
    # optimum.  ISSUE 26's 1e-4 of |x| is inside that ball for 23 of these
    # 24 problems (readings up to 6.5e-5); logistic x padded_sparse x
    # normalised reads 1.1e-3 where the ball is 1.4e-3: there the margin
    # path's f stops changing at iteration 13, the generic path's at 15.
    on_margins, generic = both(obj, 100, zero)
    counted(on_margins, generic)
    fresh = float(l2.value(on_margins.x))
    assert abs(float(on_margins.value) - fresh) <= 1e-5 * abs(fresh)
    assert abs(float(on_margins.value) - float(generic.value)) <= 1e-6 * abs(fresh)
    l2_f64 = _margin_objective(
        loss_name, features, variant,
        jnp.dtype("float64")).with_l2(jnp.asarray(_MARGIN_LAM))
    curvature = float(_least_curvature(
        l2_f64, jnp.asarray(generic.x, jnp.float64)))
    ball = np.sqrt(2 * np.spacing(np.float32(fresh)) / curvature)
    assert float(jnp.linalg.norm(on_margins.x - generic.x)) <= 2 * ball


@pytest.mark.parametrize("n,d,weakest_column,lam", [
    (400, 20, 1e-2, 1e-3), (600, 30, 1e-2, 1e-2), (2000, 40, 10 ** -1.5, 1e-2)])
def test_carried_margins_do_not_drift_over_a_long_float32_solve(
        n, d, weakest_column, lam):
    """A solve that uses all of its 100 iterations in float32 (columns
    scaled over decades, so L-BFGS is still descending at the cap): at
    EVERY iterate the value and gradient norm the loop carries, made from
    z + t u a hundred times over, are those of fresh margins X x.  Readings:
    value within 2.5e-7 of f (4 ulp), gradient norm within 2.4e-7 of the
    first one.  x itself is no yardstick here: unconverged, the two paths
    are 4e-3 to 1e-2 of |x| apart along the weak columns while their
    values agree to 3e-4."""
    rng = np.random.default_rng(5)
    x, y, _, _ = make_glm_data(rng, n=n, d=d, task="logistic")
    x = x * np.logspace(0, np.log10(weakest_column), d)
    dtype = jnp.float32
    obj = GLMObjective(LOGISTIC, jnp.asarray(x, dtype), jnp.asarray(y, dtype))
    l2 = obj.with_l2(jnp.asarray(lam, dtype))
    cfg = OptimizerConfig(max_iterations=100, tolerance=0.0,
                          track_coefficients=True)
    on_margins = jax.jit(
        lambda o: solve(o, jnp.zeros(d, dtype), cfg, _L2, lam))(obj)
    generic = jax.jit(lambda o: lbfgs(o.value_and_gradient, jnp.zeros(d, dtype),
                                      tolerance=0.0))(l2)
    k = int(on_margins.iterations)
    assert k >= 50      # reads 100, the cap
    fresh_f, fresh_g = jax.vmap(l2.value_and_gradient)(
        on_margins.coefficient_history)
    fresh_gnorm = np.linalg.norm(np.asarray(fresh_g), axis=1)
    np.testing.assert_allclose(on_margins.loss_history, fresh_f, rtol=1e-5)
    np.testing.assert_allclose(on_margins.gnorm_history, fresh_gnorm, rtol=0,
                               atol=1e-5 * fresh_gnorm[0])
    # the returned value is the refreshed one (batched and single
    # evaluations sum in different orders: an ulp or two apart)
    np.testing.assert_allclose(on_margins.value, fresh_f[k], rtol=1e-6)
    # and it descends as far: no worse than the generic path by 1e-3 of f
    assert float(on_margins.value) <= float(generic.value) * (1 + 1e-3)


@pytest.mark.parametrize("path", ["margins", "generic", "host"])
def test_tolerance_zero_switches_the_function_value_check_off(path):
    """A float32 solve run far past its floor: the objective repeats
    exactly three times and more in a row, which a test of `<=` at
    tolerance 0 ended on (FUNCTION_VALUES_CONVERGED after 3 repeats); with
    the check strict the solve uses the iterations it was given. The
    default tolerance still ends the same solve early."""
    from photon_ml_tpu.optim.streaming import host_lbfgs
    rng = np.random.default_rng(11)
    x, y, _, _ = make_glm_data(rng, n=400, d=6, task="logistic")
    dtype, lam, cap = jnp.float32, 5.0, 60
    obj = GLMObjective(LOGISTIC, jnp.asarray(x, dtype), jnp.asarray(y, dtype))
    l2 = obj.with_l2(jnp.asarray(lam, dtype))
    x0 = jnp.zeros(6, dtype)

    def run(tolerance):
        if path == "margins":
            cfg = OptimizerConfig(max_iterations=cap, tolerance=tolerance)
            return jax.jit(lambda o: solve(o, x0, cfg, _L2, lam))(obj)
        minimize_ = lbfgs if path == "generic" else host_lbfgs
        return minimize_(l2.value_and_gradient, x0, max_iterations=cap,
                         tolerance=tolerance)

    fixed = run(0.0)
    assert int(fixed.iterations) == cap
    assert int(fixed.reason) == ConvergenceReason.MAX_ITERATIONS
    repeats = np.diff(np.asarray(fixed.loss_history)[:cap + 1]) == 0
    assert (repeats[:-2] & repeats[1:-1] & repeats[2:]).any()
    default = run(1e-7)
    assert int(default.iterations) < cap
    assert int(default.reason) in (ConvergenceReason.FUNCTION_VALUES_CONVERGED,
                                   ConvergenceReason.GRADIENT_CONVERGED)


def _while_eqns(jaxpr, depth=0):
    """(nesting depth among `while`s, eqn) for every while in a jaxpr."""
    for eqn in jaxpr.eqns:
        is_while = eqn.primitive.name == "while"
        if is_while:
            yield depth, eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _while_eqns(sub, depth + is_while)


@pytest.mark.parametrize("path,reads_x", [("margins", False), ("l1", True),
                                          ("box", True)])
def test_line_search_body_reads_features_only_off_the_margin_path(path, reads_x):
    """The inner `while` is the line search.  On the margin path no operand
    of the feature block's shape goes into it; under L1 and under a box the
    fused value+gradient of every trial does."""
    S, d = 37, 5      # unlike every other shape of the solve ([d], [101])
    obj = _margin_objective("logistic", "dense", "plain", jnp.float64, n=S, d=d)
    cfg, reg = OptimizerConfig(), _L2
    if path == "l1":
        reg = _L1
    elif path == "box":
        cfg = OptimizerConfig(box_lower=(-0.5,) * d, box_upper=(0.5,) * d)
    jaxpr = jax.make_jaxpr(
        lambda o, x0: solve(o, x0, cfg, reg, 0.5))(obj, jnp.zeros(d)).jaxpr
    whiles = list(_while_eqns(jaxpr))
    assert [depth for depth, _ in whiles] == [0, 1]   # the solve, its search
    search = whiles[1][1]
    shapes = {v.aval.shape for v in search.invars}
    body = search.params["body_jaxpr"].jaxpr
    shapes |= {v.aval.shape for e in body.eqns for v in e.invars
               if hasattr(v, "aval")}
    assert ((S, d) in shapes) == reads_x
    assert (S,) in shapes     # margins, labels: the search does run in there


def test_vmapped_margin_search_one_hard_lane_costs_no_feature_reads(rng):
    """64 easy lanes and one whose first step overshoots a thousandfold:
    the batch backtracks as long as that lane does, on margins alone."""
    S, d, lanes = 40, 4, 65
    xs, ys = [], []
    for _ in range(lanes):
        x, y, _, _ = make_glm_data(rng, n=S, d=d, task="logistic")
        xs.append(x); ys.append(y)
    xs[17] = xs[17] * np.array([1e3, 1.0, 1e-2, 1.0])
    xb, yb = jnp.asarray(np.stack(xs)), jnp.asarray(np.stack(ys))

    def solve_one(x, y):
        return solve(GLMObjective(LOGISTIC, x, y), jnp.zeros(d),
                     OptimizerConfig(), _L2, 0.1)

    batched = jax.jit(jax.vmap(solve_one))(xb, yb)
    fg, ls, its = (np.asarray(batched.fg_count), np.asarray(batched.ls_trials),
                   np.asarray(batched.iterations))
    assert fg.max() == its.max() + 2
    assert ls.max() > fg.max()
    assert ls.argmax() == 17
    np.testing.assert_array_equal(fg, its + 2)
    single = jax.jit(solve_one)
    for i in (0, 16, 18, 64):
        one = single(xb[i], yb[i])
        assert int(one.iterations) == its[i] and int(one.ls_trials) == ls[i]
        assert int(one.reason) == int(batched.reason[i])
        np.testing.assert_allclose(batched.x[i], one.x, rtol=1e-9, atol=1e-12)


def test_l1_and_box_solves_run_the_generic_path_bit_for_bit(rng):
    """solve() under L1 and under a box is lbfgs on the bare fused
    value+gradient, the code those paths ran before the margin surface."""
    x, y, _, _ = make_glm_data(rng, n=200, d=6, task="logistic")
    obj = GLMObjective(LOGISTIC, jnp.asarray(x), jnp.asarray(y))
    lo, hi = (-0.2,) * 6, (0.3,) * 6
    pairs = [
        (solve(obj, jnp.zeros(6), OptimizerConfig(), _L1, 2.0),
         lbfgs(obj.with_l2(jnp.zeros(())).value_and_gradient, jnp.zeros(6),
               l1_weight=jnp.asarray(2.0))),
        (solve(obj, jnp.zeros(6), OptimizerConfig(box_lower=lo, box_upper=hi),
               _L2, 1.0),
         lbfgs(obj.with_l2(jnp.asarray(1.0)).value_and_gradient, jnp.zeros(6),
               lower=jnp.asarray(lo), upper=jnp.asarray(hi))),
    ]
    for via_solve, bare in pairs:
        for a, b in zip(jax.tree_util.tree_leaves(via_solve),
                        jax.tree_util.tree_leaves(bare)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(via_solve.fg_count) == int(via_solve.ls_trials) + 1
    with pytest.raises(ValueError, match="not affine"):
        lbfgs(obj.value_and_gradient, jnp.zeros(6), l1_weight=1.0,
              margin_surface=obj)


# -- the history in age order, one array a slot (PR 28) ------------------------
# optim/lbfgs.py keeps the last m pairs newest first in m [d] arrays and shifts
# them by `where` on a push.  The oracle is the ring it replaced: [m, d]
# buffers, slot num_pairs % m, the two-loop reading (num_pairs - 1 - i) % m.

def _ring_two_loop(q, s_buf, y_buf, rho, num_pairs, m):
    q = q.copy()
    alphas = np.zeros(m)
    stored = min(num_pairs, m)
    for i in range(stored):
        j = (num_pairs - 1 - i) % m
        alphas[i] = rho[j] * (s_buf[j] @ q)
        q = q - alphas[i] * y_buf[j]
    gamma = 1.0
    if num_pairs > 0:
        jn = (num_pairs - 1) % m
        yy = y_buf[jn] @ y_buf[jn]
        if yy > 0:
            gamma = (s_buf[jn] @ y_buf[jn]) / yy
    r = gamma * q
    for i in reversed(range(stored)):
        j = (num_pairs - 1 - i) % m
        b = rho[j] * (y_buf[j] @ r)
        r = r + (alphas[i] - b) * s_buf[j]
    return r


@functools.lru_cache(maxsize=None)
def _jitted_history_ops():
    """(push, two-loop direction) under jit, wrapped ONCE: the cases of one
    (m, d) differ only in what they push and share the two programs."""
    from photon_ml_tpu.optim.lbfgs import _push, _two_loop
    return jax.jit(_push), jax.jit(_two_loop)


def _push_schedule(pushes, m):
    """`pushes` stored pairs, with a skipped pair before the first, in the
    middle, and (where the ring wraps) just as slot 0 is about to be reused."""
    flags = [True] * pushes
    skips = {0, pushes // 2} | ({m} if pushes > m else set())
    for at in sorted(skips, reverse=True):
        flags.insert(at, False)
    return flags


@pytest.mark.parametrize("pushes", ["none", "fewer", "exactly_m", "wrapped"])
@pytest.mark.parametrize("d", [1, 21])
@pytest.mark.parametrize("m", [1, 3, 10])
def test_age_ordered_history_matches_a_ring_buffer_two_loop(m, d, pushes):
    from photon_ml_tpu.optim.lbfgs import _empty_history
    n = {"none": 0, "fewer": m - 1, "exactly_m": m, "wrapped": 2 * m + 3}[pushes]
    rng = np.random.default_rng(1000 * m + 10 * d + n)
    hist = _empty_history(m, d, jnp.float64)
    push, direction = _jitted_history_ops()
    s_buf, y_buf, rho, num_pairs = np.zeros((m, d)), np.zeros((m, d)), np.zeros(m), 0

    def same_direction():
        q = rng.normal(size=d)
        want = _ring_two_loop(q, s_buf, y_buf, rho, num_pairs, m)
        got = direction(jnp.asarray(q), hist, jnp.asarray(num_pairs, jnp.int32))
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)

    same_direction()
    for store in _push_schedule(n, m):
        s = rng.normal(size=d)
        y = rng.uniform(0.5, 2.0, size=d) * s + 0.05 * rng.normal(size=d)
        sy = s @ y      # a skipped pair is a real pair: only `store` keeps it out
        hist = push(hist, jnp.asarray(store), jnp.asarray(s), jnp.asarray(y),
                    jnp.asarray(sy))
        if store:
            slot = num_pairs % m
            s_buf[slot], y_buf[slot], rho[slot] = s, y, 1.0 / sy
            num_pairs += 1
        same_direction()
    assert num_pairs == n
    assert all(leaf.shape == (d,) for leaf in hist.s + hist.y)
    assert all(leaf.shape == () for leaf in hist.rho)


def _huber_lane(center, scales):
    """sum_j scales_j huber(x_j - center_j): beyond 1 of the centre the
    gradient is constant, so a step that stays out there has y = 0 exactly
    and its pair is skipped."""
    def vg(x):
        r = x - center
        quad = jnp.abs(r) <= 1.0
        return (jnp.sum(scales * jnp.where(quad, 0.5 * r * r, jnp.abs(r) - 0.5)),
                scales * jnp.where(quad, r, jnp.sign(r)))
    return vg


def test_vmapped_lanes_that_store_different_numbers_of_pairs_match_their_own_solves():
    d, lanes, far = 6, 5, 3
    rng = np.random.default_rng(3)
    centers = rng.uniform(-0.8, 0.8, size=(lanes, d))
    centers[far] = rng.uniform(4.0, 6.0, size=d)   # flat for its first steps
    scales = rng.uniform(0.5, 3.0, size=(lanes, d))
    centers, scales = jnp.asarray(centers, jnp.float32), jnp.asarray(scales, jnp.float32)

    def solve_one(c, a):
        return lbfgs(_huber_lane(c, a), jnp.zeros(d, jnp.float32),
                     max_iterations=60, tolerance=1e-5, track_coefficients=True)

    batched = jax.jit(jax.vmap(solve_one))(centers, scales)
    single = jax.jit(solve_one)
    stored = []
    for i in range(lanes):
        one = single(centers[i], scales[i])
        k = int(one.iterations)
        assert k == int(batched.iterations[i])
        assert int(one.ls_trials) == int(batched.ls_trials[i])
        assert int(one.reason) == int(batched.reason[i])
        np.testing.assert_allclose(batched.x[i], one.x, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(one.x, centers[i], atol=1e-4)
        xs = np.asarray(one.coefficient_history[:k + 1], np.float64)
        gs = np.asarray(jax.vmap(lambda x: _huber_lane(centers[i], scales[i])(x)[1])(
            one.coefficient_history[:k + 1]), np.float64)
        sy = np.sum(np.diff(xs, axis=0) * np.diff(gs, axis=0), axis=1)
        stored.append((int(np.sum(sy > 1e-12)), k))
    pairs, its = zip(*stored)
    assert pairs[far] < its[far]                 # the far lane did skip
    assert all(p == k for j, (p, k) in enumerate(stored) if j != far)
    assert len(set(pairs)) > 1                   # and the lanes differ


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_vmapped_solve_body_indexes_no_history_and_holds_no_slot_axis():
    """Under vmap every lane has its own pair count, so anything indexed by
    it is a per-lane gather or scatter.  The loop body of the vmapped solve
    has those on the [E, max_iterations + 1] loss and gradient-norm
    histories alone, and no array with a slot axis beside lanes and width."""
    E, S, d, m, cap = 6, 11, 5, 3, 17      # no two shapes of the solve alike
    objs = [_margin_objective("logistic", "dense", "plain", jnp.float32, n=S, d=d)
            for _ in range(E)]
    xb = jnp.stack([o.x for o in objs])
    yb = jnp.stack([o.labels for o in objs])
    cfg = OptimizerConfig(max_iterations=cap, history=m)

    def solve_one(x, y):
        return solve(GLMObjective(LOGISTIC, x, y), jnp.zeros(d, jnp.float32),
                     cfg, _L2, 0.5)

    jaxpr = jax.make_jaxpr(jax.vmap(solve_one))(xb, yb).jaxpr
    (depth, loop), *_ = _while_eqns(jaxpr)
    assert depth == 0
    body = loop.params["body_jaxpr"].jaxpr
    shapes = {v.aval.shape for e in _eqns(body) for v in (*e.invars, *e.outvars)
              if hasattr(v, "aval")}
    assert (E, d) in shapes and (E, S, d) in shapes     # it is the solve's body
    assert not [s for s in shapes if len(s) >= 3 and m in s[1:]]
    assert not [s for s in shapes if sorted(s) == sorted((E, m))]
    indexed = [(e.primitive.name, e.invars[0].aval.shape) for e in _eqns(body)
               if e.primitive.name.startswith(("gather", "scatter", "dynamic_"))]
    assert indexed, "loss_hist and gnorm_hist are written at a per-lane k"
    assert {shape for _, shape in indexed} == {(E, cap + 1)}, indexed
