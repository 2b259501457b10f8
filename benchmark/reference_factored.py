"""Plain float64 NumPy reference for a GAME model with a FACTORED random
effect: its margins, its objective, the alternation that fits the factored
coordinate, and the certificates of its two halves.

Nothing here imports JAX or photon_ml_tpu. Beside the fixed effect and the
plain per-entity tables of `reference_game`, the model has one coordinate
whose per-entity coefficients are factored:

    z_i = x_global_i . w + sum_t x_t_i . table_t[lane_t_i]
          + x_f_i . (C P)[lane_f_i]

with `C` [E, k] the entities' latent factors and `P` [k, d] ONE projection
shared by all entities. Its penalty is l2_c / 2 |C|^2 + l2_p / 2 |P|^2.

The coordinate's update is upstream Photon ML's alternation
(`FactoredRandomEffectCoordinate.scala:100-160`): per inner iteration, (a)
every entity's `c_e` is refitted on its own rows with the features projected
into the latent space, `P x_i`, a k-dimensional GLM; (b) `P` is refitted as
ONE GLM of k d coefficients over all active rows, whose design row is the
Kronecker product `kron(c_e, x_i)`. The rows are MATERIALISED here, as the
upstream materialises them (`kroneckerProductFeaturesAndCoefficients`); the
program never does (`ops/features.py::KroneckerDesign`), which is what makes
this reference independent of it.

Departures from `FactoredRandomEffectCoordinate.scala`, all of them:
- which rows train an entity and at what weight is DATA, as in
  `reference_game`: the program's reservoir draws them, the reference is
  handed them;
- each half is solved to its float64 optimum by damped Newton steps
  (`reference_game.newton_solve`), where the upstream runs its configured
  optimiser under an iteration cap and a tolerance. Under an L2 weight above
  zero each half is strictly convex and has one optimum, so a program that
  solves each half to ITS optimum has to land here, whatever its optimiser;
- the first projection `P0` and the first factors are given, not drawn: the
  upstream draws a Gaussian `P0` (`ProjectionMatrix.scala:95-125`), the
  program does too and may warm-start it, and the alternation's result
  depends on it (the joint problem is not convex);
- no intercept is kept apart (`isKeepingInterceptTerm = false`) and the
  refit of `P` is not down-sampled (`runWithSampling` with no sampler).
"""
from __future__ import annotations

import numpy as np

from benchmark.reference import _pool, logloss, sigmoid
from benchmark.reference_game import (game_margins, newton_solve,
                                      objective_of)

#: rows of materialised Kronecker products held at once (k d float64 each)
KRON_BLOCK = 200_000


def factored_table(factors, projection, operands=None) -> np.ndarray:
    """`C P` [E, d], every entity's coefficients in the feature space, in
    float64. With `operands` (`bfloat16`) the product rounds its operands by
    it and sums in float32: the lower-precision control."""
    if operands is None:
        return np.asarray(factors, np.float64) @ np.asarray(projection,
                                                            np.float64)
    return (operands(factors) @ operands(projection)).astype(np.float32)


def factored_margins(x_global, w, tables, factored, operands=None):
    """z of every row in float64. `tables` as `reference_game.game_margins`
    takes them; `factored` is (x_entity, lanes, C, P)."""
    x_entity, lanes, factors, projection = factored
    return game_margins(
        x_global, w, list(tables) + [
            (x_entity, lanes, factored_table(factors, projection, operands),
             None)], operands=operands)


def factored_objective(margins, y, w, tables, l2_fixed, factors, projection,
                       l2_factors, l2_projection, penalise_projection=True):
    """sum_i logloss(z_i, y_i) + the penalties of all coordinates, the
    factored one's on C AND on P, from the margins z of ALL rows.
    `penalise_projection=False` leaves the P term out: what a report that
    forgot it would print, for the test of the check."""
    c = np.asarray(factors, np.float64)
    p = np.asarray(projection, np.float64)
    penalty = 0.5 * l2_factors * float((c * c).sum())
    if penalise_projection:
        penalty += 0.5 * l2_projection * float((p * p).sum())
    return objective_of(margins, y, w, tables, l2_fixed) + penalty


def kron_rows(factor_rows, x_rows) -> np.ndarray:
    """[n, k d]: row i is kron(c_i, x_i), the design of the projection's
    refit, materialised (entry a * d + b is c_i[a] * x_i[b], which is
    P[a, b]'s multiplier in c_i . P x_i)."""
    c = np.asarray(factor_rows, np.float64)
    x = np.asarray(x_rows, np.float64)
    return (c[:, :, None] * x[:, None, :]).reshape(len(c), -1)


def solve_latent(x, y, rows, lanes, weights, offsets, projection, l2,
                 factors0) -> np.ndarray:
    """Half (a): every entity's latent factors at the float64 optimum of its
    own subproblem on the rows that train it, with the features projected
    through `projection`. `rows`, `lanes`, `weights` [m] are the active
    cells (row id, entity lane, weight); `offsets` [n] the other
    coordinates' scores of every row."""
    x_latent = np.asarray(x, np.float64)[rows] @ np.asarray(
        projection, np.float64).T
    by_lane = np.argsort(lanes, kind="stable")
    cuts = np.searchsorted(np.asarray(lanes)[by_lane],
                           np.arange(len(factors0) + 1))
    out = np.array(factors0, np.float64)
    for lane in range(len(out)):
        pick = by_lane[cuts[lane]:cuts[lane + 1]]
        mine = np.asarray(rows)[pick]
        out[lane] = newton_solve(
            x_latent[pick], np.asarray(y, np.float64)[mine],
            np.asarray(weights, np.float64)[pick],
            np.asarray(offsets, np.float64)[mine], l2, out[lane])
    return out


def latent_certificate(x, y, cells, offsets, projection, l2, factors) -> dict:
    """How far are the entities' latent factors from the optimum of half
    (a)? `cells` is [(lane, rows, weight)]: an entity's lane, the rows that
    train it and their weight; `projection` the P its solve ran UNDER (the
    one the update started from, not the one it returns); `offsets` [n] the
    other coordinates' scores. Each entity's subproblem f_e, on its rows
    with the features projected through P, is l2-strongly convex, so from
    its float64 optimum v_e (`newton_solve`, started at c_e)
    f_e(v_e) - |g_e(v_e)|^2 / (2 l2) is a proven lower bound of its least
    value; `gap` is the sum over the entities of f_e(c_e) less that bound,
    over the sum of f_e(c_e), `median_gap` the median entity's own share, and
    `worst_distance` the largest |c_e - v_e| / max(|c_e|, 1)."""
    p = np.asarray(projection, np.float64)
    values, gaps, worst = [], [], 0.0
    for lane, rows, weight in cells:
        x_latent = np.asarray(x[rows], np.float64) @ p.T
        labels = np.asarray(y[rows], np.float64)
        off = np.asarray(offsets[rows], np.float64)
        weights = np.full(len(rows), weight, np.float64)
        c = np.asarray(factors[lane], np.float64)
        v = newton_solve(x_latent, labels, weights, off, l2, c)

        def value(at):
            return float(weights @ logloss(x_latent @ at + off, labels)
                         + 0.5 * l2 * at @ at)
        g = x_latent.T @ (weights * (sigmoid(x_latent @ v + off) - labels)) \
            + l2 * v
        values.append(value(c))
        gaps.append(values[-1] - value(v) + float(g @ g) / (2.0 * l2))
        worst = max(worst, float(np.linalg.norm(c - v))
                    / max(float(np.linalg.norm(c)), 1.0))
    return {"entities": len(cells), "f": sum(values),
            "gap": sum(gaps) / sum(values),
            "median_gap": float(np.median(np.divide(gaps, values))),
            "worst_distance": worst}


def solve_projection(x, y, rows, lanes, weights, offsets, factors, l2,
                     projection0) -> np.ndarray:
    """Half (b): the projection at the float64 optimum of ONE GLM over the
    materialised Kronecker rows of all active cells. For sizes a test runs:
    the design is [m, k d] float64, whole."""
    k, d = np.shape(projection0)
    design = kron_rows(np.asarray(factors, np.float64)[lanes],
                       np.asarray(x, np.float64)[rows])
    flat = newton_solve(design, np.asarray(y, np.float64)[rows], weights,
                        np.asarray(offsets, np.float64)[rows], l2,
                        np.asarray(projection0, np.float64).reshape(-1))
    return flat.reshape(k, d)


def alternate(x, y, rows, lanes, weights, offsets, factors0, projection0,
              l2_factors, l2_projection, inner_iterations=1):
    """The coordinate's update from (`factors0`, `projection0`) under
    `offsets`: `inner_iterations` rounds of (a) then (b), each to its
    optimum. Returns (C, P)."""
    c, p = np.array(factors0, np.float64), np.array(projection0, np.float64)
    for _ in range(inner_iterations):
        c = solve_latent(x, y, rows, lanes, weights, offsets, p, l2_factors,
                         c)
        p = solve_projection(x, y, rows, lanes, weights, offsets, c,
                             l2_projection, p)
    return c, p


def projection_pass(x, y, rows, lanes, weights, offsets, factors, projection,
                    l2, hessian=False):
    """(f, g) of the projection refit's objective at `projection`, float64:
    f(P) = sum_cells weight logloss(kron(c, x) . vec(P) + offset, y)
    + l2 / 2 |P|^2 over the active cells, g its gradient [k, d]; with
    `hessian` also H [k d, k d]. The Kronecker rows are materialised
    KRON_BLOCK cells at a time, on a few threads, so this runs at the
    benchmark's real size."""
    p = np.asarray(projection, np.float64)
    flat = p.reshape(-1)
    rows, lanes = np.asarray(rows), np.asarray(lanes)
    weights = np.asarray(weights, np.float64)
    c = np.asarray(factors, np.float64)

    def one(lo):
        mine = rows[lo:lo + KRON_BLOCK]
        design = kron_rows(c[lanes[lo:lo + KRON_BLOCK]], x[mine])
        z = design @ flat + np.asarray(offsets[mine], np.float64)
        labels = np.asarray(y[mine], np.float64)
        w = weights[lo:lo + KRON_BLOCK]
        prob = sigmoid(z)
        h = (design.T @ (design * (w * prob * (1.0 - prob))[:, None])
             if hessian else None)
        return (float(w @ logloss(z, labels)),
                design.T @ (w * (prob - labels)), h)

    stack, pool = _pool()
    with stack:
        parts = list(pool.map(one, range(0, len(rows), KRON_BLOCK)))
    f = 0.5 * l2 * float(flat @ flat) + sum(part[0] for part in parts)
    g = (l2 * flat + sum(part[1] for part in parts)).reshape(p.shape)
    if not hessian:
        return f, g
    return f, g, l2 * np.eye(len(flat)) + sum(part[2] for part in parts)


def projection_certificate(x, y, rows, lanes, weights, offsets, factors,
                           projection, l2, rel, newton_steps=3) -> dict:
    """Is f(P) within `rel` * f(P) of the optimum of the projection's refit?
    `reference.certify_logistic`'s argument, on the Kronecker rows: f is
    l2-strongly convex, so f(P) - f* <= |g|^2 / (2 l2) (`bound`, over f:
    `direct_gap`). That bound is loose by the refit's condition number (its
    curvature is a sum over millions of cells, l2 is 1), so where it cannot
    decide, Newton steps from P lead to a point v whose own bound is tight,
    and f(v) - |g(v)|^2 / (2 l2) is a proven lower bound on f*; `gap` is
    f(P) less the best such bound, over f(P)."""
    args = (x, y, rows, lanes, weights, offsets, factors)
    f, g = projection_pass(*args, projection, l2)
    out = {"f": f, "gnorm": float(np.sqrt((g * g).sum())),
           "bound": float((g * g).sum()) / (2.0 * l2), "newton_steps": 0}
    out["direct_gap"] = gap = slack = out["bound"]
    v = np.asarray(projection, np.float64)
    while (gap > rel * abs(f) and slack > 0.01 * rel * abs(f)
           and out["newton_steps"] < newton_steps):
        fv, gv, hv = projection_pass(*args, v, l2, hessian=True)
        step = np.linalg.solve(hv, gv.reshape(-1)).reshape(v.shape)
        t = 1.0
        while t > 1e-3 and projection_pass(*args, v - t * step, l2)[0] > fv:
            t *= 0.5            # a Newton step from far off can overshoot
        v = v - t * step
        fv, gv = projection_pass(*args, v, l2)
        slack = float((gv * gv).sum()) / (2.0 * l2)
        out["newton_steps"] += 1
        out["f_star_lower"] = max(fv - slack,
                                  out.get("f_star_lower", -np.inf))
        gap = f - out["f_star_lower"]
    out["direct_gap"] /= abs(f)
    out["gap"] = gap / abs(f)
    out["ok"] = bool(np.isfinite(f) and gap <= rel * abs(f))
    return out
