"""Plain float64 reference for an L2-regularised logistic regression over a
sparse design matrix: margins, the objective, its gradient, Hessian-vector
products, a Newton-CG fit, the certificate that bounds how far a model is
from the optimum, an L-BFGS fit of a stated iteration count (and its
lower-precision control), and the AUC by rank.

SciPy CSR and NumPy only: nothing here imports JAX or photon_ml_tpu, and no
layout of the program under test (its padded rows, its column-sorted view)
is known here. The model is

    f(w) = sum_i logloss(x_i . w + offset_i, y_i) + 0.5 l2 |w|^2

A matrix is converted once (`as_float64`), because SciPy multiplies a
float32 matrix by a float64 vector through a float64 copy of the matrix it
makes anew on every call.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

from benchmark.reference import logloss, sigmoid


def as_float64(x) -> sp.csr_matrix:
    """`x` as canonical float64 CSR (duplicates summed, as any reader of a
    sparse row would). The index arrays are shared where SciPy allows."""
    x = sp.csr_matrix(x, dtype=np.float64)
    x.sum_duplicates()
    return x


def margins(x: sp.csr_matrix, w, offsets=None) -> np.ndarray:
    z = x @ np.asarray(w, np.float64)
    return z if offsets is None else z + np.asarray(offsets, np.float64)


def objective_of(z: np.ndarray, y, w, l2: float) -> float:
    """f from margins that were computed already."""
    w = np.asarray(w, np.float64)
    return float(logloss(z, np.asarray(y, np.float64)).sum()
                 + 0.5 * l2 * (w @ w))


def value_and_gradient(x: sp.csr_matrix, y, w, l2: float, offsets=None):
    w = np.asarray(w, np.float64)
    y = np.asarray(y, np.float64)
    z = margins(x, w, offsets)
    return (objective_of(z, y, w, l2),
            x.T @ (sigmoid(z) - y) + l2 * w)


def hessian_vector(x: sp.csr_matrix, w, l2: float, offsets=None):
    """v -> (X^T diag(p (1 - p)) X + l2 I) v at w."""
    p = sigmoid(margins(x, w, offsets))
    curvature = p * (1.0 - p)

    def product(v):
        return x.T @ (curvature * (x @ v)) + l2 * v

    return product


def conjugate_gradient(product, b, iterations: int, rel: float = 1e-10):
    """(approximate solution of A s = b for a positive definite A given as
    `product`, iterations run). Any s will do for the callers here: it only
    steers a step whose landing point is judged on its own."""
    s = np.zeros_like(b)
    r = b.copy()
    p = r.copy()
    rr = float(r @ r)
    stop = rel * rel * rr
    done = 0
    while done < iterations and rr > stop:
        ap = product(p)
        alpha = rr / float(p @ ap)
        s += alpha * p
        r -= alpha * ap
        rr, rr_old = float(r @ r), rr
        p = r + (rr / rr_old) * p
        done += 1
    return s, done


def suboptimality_bound(g: np.ndarray, l2: float) -> float:
    """f(w) - f* <= |grad f(w)|^2 / (2 l2) for an l2-strongly convex f."""
    return float(g @ g) / (2.0 * l2)


def newton_cg_step(x, y, v, fv, gv, l2: float, offsets, cg_iterations: int,
                   cg_rel: float = 1e-10, least_step: float = 1e-3):
    """(v', f(v'), grad f(v'), CG iterations run) one damped Newton-CG step
    on from v: the CG solution of H s = -g, halved until f does not rise.
    v' is None where no step of at least `least_step` lowers f."""
    step, ran = conjugate_gradient(hessian_vector(x, v, l2, offsets), -gv,
                                   cg_iterations, cg_rel)
    t = 1.0
    while t >= least_step:
        f_try, g_try = value_and_gradient(x, y, v + t * step, l2, offsets)
        if f_try <= fv:
            return v + t * step, f_try, g_try, ran
        t *= 0.5
    return None, fv, gv, ran


def certify(x: sp.csr_matrix, y, w, l2: float, rel: float, offsets=None,
            newton_steps: int = 3, cg_iterations: int = 8) -> dict:
    """Is f(w) within `rel` * f(w) of the optimum? `rel_gap` is a proven
    upper bound on (f(w) - f*) / f(w), whatever the answer.

    First by the strong-convexity bound at w itself. Where that cannot
    decide (l2 is far below the data's own curvature, so the bound is
    loose by the condition number), Newton-CG steps from w lead to a point
    v with a small gradient. Every v gives a valid lower bound on the
    optimum, f(v) - |grad f(v)|^2 / (2 l2), in float64; the gap from f(w)
    to the best of them is what is reported. A step is kept only if it
    lowers f, so a poor CG solve costs tightness, never validity."""
    f, g = value_and_gradient(x, y, w, l2, offsets)
    out = {"f": f, "gnorm": float(np.linalg.norm(g)),
           "bound": suboptimality_bound(g, l2), "newton_steps": 0,
           "cg_iterations": 0}
    out["gap"] = out["bound"]
    v, fv, gv = np.asarray(w, np.float64), f, g
    slack = out["bound"]
    # stop when the answer is yes, or when v's own bound is so tight that
    # another step could not turn a no into a yes
    while (out["gap"] > rel * abs(f) and slack > 0.01 * rel * abs(f)
           and out["newton_steps"] < newton_steps):
        v_new, fv, gv, ran = newton_cg_step(x, y, v, fv, gv, l2, offsets,
                                            cg_iterations)
        out["cg_iterations"] += ran
        if v_new is None:
            break
        v = v_new
        slack = suboptimality_bound(gv, l2)
        out["newton_steps"] += 1
        out["f_star_lower"] = max(fv - slack,
                                  out.get("f_star_lower", -np.inf))
        out["gap"] = f - out["f_star_lower"]
    out["rel_gap"] = out["gap"] / abs(f)
    out["ok"] = bool(np.isfinite(f) and out["gap"] <= rel * abs(f))
    return out


def fit(x: sp.csr_matrix, y, l2: float, offsets=None, rel: float = 1e-12,
        newton_steps: int = 50, cg_iterations: int = 200) -> np.ndarray:
    """The optimum by damped Newton-CG from zero, to |grad|^2 / (2 l2) <=
    rel * f (or to where float64 finds no lower f): the float64 model the
    small CPU tests compare the program's with. Not for the real size."""
    w = np.zeros(x.shape[1])
    f, g = value_and_gradient(x, y, w, l2, offsets)
    for _ in range(newton_steps):
        if suboptimality_bound(g, l2) <= rel * abs(f):
            break
        w_new, f, g, _ = newton_cg_step(x, y, w, f, g, l2, offsets,
                                        cg_iterations, cg_rel=1e-12,
                                        least_step=1e-6)
        if w_new is None:
            break
        w = w_new
    return w


def lbfgs_fit(x: sp.csr_matrix, y, l2: float, iterations: int,
              operands=None) -> np.ndarray:
    """The model `iterations` L-BFGS iterations from zero lead to (SciPy's
    L-BFGS-B, ten pairs, no stopping rule but the count). With `operands`
    (a rounding, `reference_game.bfloat16`) it is the lower-precision
    control of a SOLVE: every pass rounds the operands of both products by
    it (the matrix and w before X w, the per-row derivative before X^T u)
    and sums them in float32; what is not a product stays float64."""
    from scipy.optimize import fmin_l_bfgs_b
    y = np.asarray(y, np.float64)
    if operands is None:
        def value_grad(w):
            return value_and_gradient(x, y, w, l2)
    else:
        low = sp.csr_matrix((operands(x.data), x.indices, x.indptr),
                            shape=x.shape)

        def value_grad(w):
            z = (low @ operands(w)).astype(np.float64)
            g = (low.T @ operands(sigmoid(z) - y)).astype(np.float64)
            return objective_of(z, y, w, l2), g + l2 * w
    return fmin_l_bfgs_b(value_grad, np.zeros(x.shape[1]), m=10, factr=0.0,
                         pgtol=0.0, maxiter=iterations,
                         maxfun=4 * iterations)[0]


def auc(scores, y) -> float:
    """Area under the ROC curve by ranks (Mann-Whitney), ties at half."""
    scores = np.asarray(scores, np.float64)
    positive = np.asarray(y) > 0.5
    order = np.argsort(scores, kind="stable")
    sorted_scores = scores[order]
    # mean rank (1-based) of each run of equal scores
    starts = np.flatnonzero(np.r_[True, sorted_scores[1:]
                                  != sorted_scores[:-1]])
    ends = np.r_[starts[1:], len(scores)]
    mean_rank = np.repeat(0.5 * (starts + ends + 1), ends - starts)
    ranks = np.empty(len(scores))
    ranks[order] = mean_rank
    n_pos = int(positive.sum())
    n_neg = len(scores) - n_pos
    return float((ranks[positive].sum() - 0.5 * n_pos * (n_pos + 1))
                 / (n_pos * n_neg))


def build_counts(x) -> dict:
    """What a padded row-sparse copy of `x` has to hold, counted from the
    CSR alone: stored non-zeros (duplicates summed, explicit zeros not
    counted), the widest row, and the slots of a rows x width table that
    hold no non-zero."""
    x = as_float64(x)
    x.eliminate_zeros()
    per_row = np.diff(x.indptr)
    width = max(int(per_row.max()), 1) if len(per_row) else 1
    return {"rows": int(x.shape[0]), "cols": int(x.shape[1]),
            "nnz": int(x.nnz), "ell_width": width,
            "padded_slots": int(x.shape[0] * width - x.nnz)}
