"""Plain float64 NumPy references, independent of the program under test.

Nothing here imports JAX or photon_ml_tpu. `selftest.py` checks this file on
tiny problems; the builders call it after the measured window to decide
`correct`.
"""
from __future__ import annotations

import contextlib
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

SUB_ROWS = 262_144          # rows converted to float64 at a time


def _workers() -> int:
    # never all the cores os.cpu_count() names: on a shared host that is the
    # whole machine's, and each worker holds a quarter GB of float64 rows
    return max(1, min(8, (os.cpu_count() or 2) - 1))


def _pool():
    """Row blocks on a few threads, each block's BLAS calls on one thread:
    blocks in parallel with a threaded BLAS inside each are three times
    slower than this (8 cores, PR 24)."""
    stack = contextlib.ExitStack()
    try:
        from threadpoolctl import threadpool_limits
        stack.enter_context(threadpool_limits(1))
    except ImportError:
        pass
    return stack, stack.enter_context(ThreadPoolExecutor(_workers()))


class PackedBits:
    """A matrix of 0s and 1s held as packed bits (np.packbits along the
    columns); a row slice comes out as uint8, which any float type holds
    exactly."""

    def __init__(self, packed: np.ndarray, width: int):
        self.packed, self.width = packed, width
        self.shape = (packed.shape[0], width)

    def __getitem__(self, rows):
        return np.unpackbits(self.packed[rows], axis=1, count=self.width)


def _row_blocks(n: int, step: int = SUB_ROWS):
    return [(lo, min(lo + step, n)) for lo in range(0, n, step)]


def logloss(z: np.ndarray, y: np.ndarray) -> np.ndarray:
    """Pointwise logistic loss for labels in {0, 1}, stable for large |z|."""
    return np.maximum(z, 0.0) - y * z + np.log1p(np.exp(-np.abs(z)))


def sigmoid(z: np.ndarray) -> np.ndarray:
    e = np.exp(-np.abs(z))
    return np.where(z >= 0, 1.0 / (1.0 + e), e / (1.0 + e))


def _jobs(x_chunks):
    return [(c, lo, hi) for c, x in enumerate(x_chunks)
            for lo, hi in _row_blocks(x.shape[0])]


def logistic_pass(x_chunks, y_chunks, w, lam: float):
    """f and grad f of  sum logloss(x w, y) + 0.5 lam |w|^2  in float64, over
    a list of row chunks of any float type."""
    w = np.asarray(w, np.float64)

    def one(job):
        c, lo, hi = job
        x = np.asarray(x_chunks[c][lo:hi], np.float64)
        y = np.asarray(y_chunks[c][lo:hi], np.float64)
        z = x @ w
        return float(logloss(z, y).sum()), x.T @ (sigmoid(z) - y)

    f, g = 0.5 * lam * float(w @ w), lam * w
    stack, pool = _pool()
    with stack:
        for fi, gi in pool.map(one, _jobs(x_chunks)):
            f, g = f + fi, g + gi
    return f, g


def logistic_hessian(x_chunks, w, lam: float):
    """The Hessian at w with its blocks multiplied in float32: it only steers
    a Newton step, whose landing point is then judged in float64."""
    w32 = np.asarray(w, np.float32)

    def one(job):
        c, lo, hi = job
        x = np.asarray(x_chunks[c][lo:hi], np.float32)
        p = sigmoid(x @ w32)
        return (x.T @ (x * (p * (1.0 - p))[:, None])).astype(np.float64)

    stack, pool = _pool()
    with stack:
        return lam * np.eye(len(w32)) + sum(pool.map(one, _jobs(x_chunks)))


def suboptimality_bound(g: np.ndarray, lam: float) -> float:
    """f(w) - f* <= |grad f(w)|^2 / (2 lam) for a lam-strongly convex f."""
    return float(g @ g) / (2.0 * lam)


def certify_logistic(x_chunks, y_chunks, w, lam: float, rel: float,
                     newton_steps: int = 3, hessian=None) -> dict:
    """Is f(w) within `rel` * |f(w)| of the optimum?

    First by the strong-convexity bound at w itself. Where that bound is too
    loose to decide (lam is far below the data's own curvature), Newton steps
    from w lead to a point v whose own bound is tight, which gives a lower
    bound on f*: f(v) - |grad f(v)|^2 / (2 lam), both in float64. The gap from
    f(w) to it is then tested directly. `hessian(v)` may come from elsewhere
    (the device): it only steers the step."""
    if hessian is None:
        def hessian(v):
            return logistic_hessian(x_chunks, v, lam)
    f, g = logistic_pass(x_chunks, y_chunks, w, lam)
    out = {"f": f, "gnorm": float(np.linalg.norm(g)),
           "bound": suboptimality_bound(g, lam), "newton_steps": 0}
    out["gap"] = out["bound"]
    v, gv, slack = np.asarray(w, np.float64), g, out["bound"]
    # stop when the answer is yes, or when v's own bound is so tight that
    # another step could not turn a no into a yes
    while (out["gap"] > rel * abs(f) and slack > 0.01 * rel * abs(f)
           and out["newton_steps"] < newton_steps):
        v = v - np.linalg.solve(hessian(v), gv)
        fv, gv = logistic_pass(x_chunks, y_chunks, v, lam)
        slack = suboptimality_bound(gv, lam)
        out["newton_steps"] += 1
        # every such v gives a valid lower bound on f*; keep the best
        out["f_star_lower"] = max(fv - slack,
                                  out.get("f_star_lower", -np.inf))
        out["gap"] = f - out["f_star_lower"]
    out["rel_gap"] = out["gap"] / abs(f)
    out["ok"] = bool(np.isfinite(f) and out["gap"] <= rel * abs(f))
    return out


def glmix_objective(x_global, x_entity, lanes, y, w, table, l2_fixed: float,
                    l2_entity: float) -> float:
    """sum logloss(x_global w + x_entity . table[lane], y)
    + 0.5 l2_fixed |w|^2 + 0.5 l2_entity |table|^2  in float64; a row whose
    lane is negative (an entity with no model) gets the fixed effect alone."""
    w = np.asarray(w, np.float64)
    table = np.asarray(table, np.float64)

    def one(block):
        lo, hi = block
        z = np.asarray(x_global[lo:hi], np.float64) @ w
        lane = np.asarray(lanes[lo:hi])
        known = lane >= 0
        rows = table[np.where(known, lane, 0)]
        z += known * np.einsum("nd,nd->n",
                               np.asarray(x_entity[lo:hi], np.float64), rows)
        return float(logloss(z, np.asarray(y[lo:hi], np.float64)).sum())

    stack, pool = _pool()
    with stack:
        data = sum(pool.map(one, _row_blocks(len(y))))
    return (data + 0.5 * l2_fixed * float(w @ w)
            + 0.5 * l2_entity * float((table * table).sum()))


def same_to(a, b, rel: float) -> bool:
    """max |a - b| <= rel * max |b|, both finite."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if a.shape != b.shape or not (np.isfinite(a).all()
                                  and np.isfinite(b).all()):
        return False
    return bool(np.max(np.abs(a - b), initial=0.0)
                <= rel * max(np.max(np.abs(b), initial=0.0), 1e-300))
