"""Plain float64 NumPy reference for a GAME model with any number of
per-entity random effects: its objective, the optimality certificate of one
entity's subproblem, and the whole block coordinate descent.

Nothing here imports JAX or photon_ml_tpu. The model is

    z_i = x_global_i . w  +  sum_t  x_t_i . table_t[lane_t_i]

with logistic loss and an L2 weight per coordinate. A row whose lane in
table t is negative gets no term from that table (an entity with no model,
or a row the program discarded).

One departure from a textbook fit: WHICH rows train an entity, and with what
weight, is data here and not a decision. The program caps an entity at
`active_data_upper_bound` rows by a reservoir draw of its own and rescales
the kept rows' weights by count / cap; the reference is handed the row sets
and weights the program used, so the two solve the same subproblems.
"""
from __future__ import annotations

import numpy as np

from benchmark.reference import _pool, _row_blocks, logloss, sigmoid


def bfloat16(values) -> np.ndarray:
    """`values` rounded to the nearest bfloat16 (ties to even), as float32:
    the precision below float32 on the chip, where a float32 dot at the
    default matmul precision rounds its operands so and sums in float32."""
    bits = np.ascontiguousarray(values, np.float32).view(np.uint32)
    return ((bits + 0x7FFF + ((bits >> 16) & 1))
            & np.uint32(0xFFFF0000)).view(np.float32)


def game_margins(x_global, w, tables, operands=None) -> np.ndarray:
    """z of every row, in float64. `tables` is a list of (x_entity, lanes,
    table, l2). With `operands` (`bfloat16`) it is the lower-precision
    control instead: every dot product rounds its operands by it and sums
    in float32."""
    dtype = np.float64 if operands is None else np.float32
    rounded = (lambda a: a) if operands is None else operands
    w = rounded(np.asarray(w, dtype))

    def one(block):
        lo, hi = block
        z = rounded(np.asarray(x_global[lo:hi], dtype)) @ w
        for x_entity, lanes, table, _ in tables:
            lane = np.asarray(lanes[lo:hi])
            known = lane >= 0
            rows = rounded(np.asarray(table, dtype)[np.where(known, lane, 0)])
            z += known * np.einsum(
                "nd,nd->n", rounded(np.asarray(x_entity[lo:hi], dtype)), rows)
        return z

    stack, pool = _pool()
    with stack:
        return np.concatenate(list(pool.map(
            one, _row_blocks(len(x_global))))).astype(np.float64)


def objective_of(margins, y, w, tables, l2_fixed: float) -> float:
    """sum_i logloss(z_i, y_i) + 0.5 l2_fixed |w|^2 + sum_t 0.5 l2_t
    |table_t|^2 in float64, from the margins z of ALL rows."""
    w = np.asarray(w, np.float64)
    penalty = 0.5 * l2_fixed * float(w @ w)
    for _, _, table, l2 in tables:
        table = np.asarray(table, np.float64)
        penalty += 0.5 * l2 * float((table * table).sum())
    return float(logloss(np.asarray(margins, np.float64),
                         np.asarray(y, np.float64)).sum()) + penalty


def game_objective(x_global, w, tables, y, l2_fixed: float) -> float:
    """The regularised negative log-likelihood over ALL rows, in float64."""
    return objective_of(game_margins(x_global, w, tables), y, w, tables,
                        l2_fixed)


def entity_certificate(x_rows, y, weights, offsets, coef, l2: float) -> dict:
    """ONE entity's subproblem  f(c) = sum_i weights_i logloss(x_i . c +
    offsets_i, y_i) + 0.5 l2 |c|^2  at `coef`, in float64: its gradient's
    norm, and `distance_bound` = |grad| / l2, which bounds |coef - c*|
    because f is l2-strongly convex (l2 |c - c*| <= |grad f(c)|)."""
    x = np.asarray(x_rows, np.float64)
    c = np.asarray(coef, np.float64)
    z = x @ c + np.asarray(offsets, np.float64)
    g = x.T @ (np.asarray(weights, np.float64)
               * (sigmoid(z) - np.asarray(y, np.float64))) + l2 * c
    gnorm = float(np.linalg.norm(g))
    return {"gnorm": gnorm, "distance_bound": gnorm / l2}


def newton_solve(x, y, weights, offsets, l2: float, c0, steps: int = 50,
                 operands=None):
    """The float64 optimum of the subproblem of `entity_certificate`, by
    Newton steps with halving until f falls (logistic Newton from a far
    start can overshoot). Stops when the step no longer moves f.

    With `operands` (`bfloat16`) it is the lower-precision control instead:
    every dot product rounds its operands by it and sums in float32, as do
    the value and the step."""
    dtype = np.float64 if operands is None else np.float32
    x, y, weights, offsets = (np.asarray(a, dtype)
                              for a in (x, y, weights, offsets))
    c = np.array(c0, dtype)

    def dot(a, b):
        return a @ b if operands is None else operands(a) @ operands(b)

    def value(v):
        return float(dot(weights, logloss(dot(x, v) + offsets, y))
                     + dtype(0.5 * l2) * dot(v, v))

    f = value(c)
    for _ in range(steps):
        p = sigmoid(dot(x, c) + offsets)
        g = dot(x.T, weights * (p - y)) + dtype(l2) * c
        h = (dot(x.T * (weights * p * (1 - p)), x)
             + l2 * np.eye(len(c), dtype=dtype))
        step = np.linalg.solve(h, g)
        t = dtype(1.0)
        while t > 1e-8 and value(c - t * step) > f:
            t *= dtype(0.5)
        new_f = value(c - t * step)
        if new_f >= f:          # nothing left to gain at this resolution
            break
        c, f = c - t * step, new_f
        if np.linalg.norm(g) <= 1e-13 * max(1.0, abs(f)):
            break
    return c


def entity_distance(x_rows, y, weights, offsets, coef, l2: float) -> dict:
    """A rigorous and TIGHT bound on |coef - c*| for one entity: Newton
    steps from `coef` lead to a point v whose own certificate is at
    float64's floor, and |coef - c*| <= |coef - v| + |grad f(v)| / l2.

    `entity_certificate` at `coef` itself (`direct_bound`) is rigorous too,
    but loose by the subproblem's condition number, lambda_max / l2: 50 to
    100 for an entity of 512 rows, so a float32 solve 1e-3 from its optimum
    reads 3e-2 there (PERF.md section 6, PR 27)."""
    v = newton_solve(x_rows, y, weights, offsets, l2, coef)
    at_v = entity_certificate(x_rows, y, weights, offsets, v, l2)
    return {"distance": float(np.linalg.norm(
                np.asarray(coef, np.float64) - v)) + at_v["distance_bound"],
            "direct_bound": entity_certificate(
                x_rows, y, weights, offsets, coef, l2)["distance_bound"]}


def fit_game(x_global, y, l2_fixed: float, entities: dict, order,
             outer_iterations: int) -> dict:
    """Block coordinate descent from a zero model: `order` names the
    coordinates of one sweep ("fixed" is the global one, every other name a
    key of `entities`), each solved to its float64 optimum against the other
    coordinates' current scores, `outer_iterations` sweeps.

    `entities[name]` is a dict: `x` [n, d] the entity-side features of every
    row; `lanes` [n] the table row that SCORES each row, negative for none
    (active and passive rows carry their entity's lane, discarded ones -1);
    `active_rows`, `active_lanes`, `active_weights` [k] the rows that TRAIN
    each lane and their weights, as the program used them; `num_entities`;
    `l2`. Returns `w`, `tables` {name: [E, d]} and `objective_history`, one
    value after every coordinate update."""
    n = len(y)
    y = np.asarray(y, np.float64)
    xg = np.asarray(x_global, np.float64)
    w = np.zeros(xg.shape[1])
    tables = {name: np.zeros((e["num_entities"], np.shape(e["x"])[1]))
              for name, e in entities.items()}
    scores = {name: np.zeros(n) for name in order}
    ones = np.ones(n)

    def as_tables():
        return [(e["x"], e["lanes"], tables[name], e["l2"])
                for name, e in entities.items()]

    history = []
    for _ in range(outer_iterations):
        for name in order:
            offsets = sum(s for other, s in scores.items() if other != name)
            if name == "fixed":
                w = newton_solve(xg, y, ones, offsets, l2_fixed, w)
                scores[name] = xg @ w
            else:
                e = entities[name]
                x = np.asarray(e["x"], np.float64)
                rows = np.asarray(e["active_rows"])
                by_lane = np.argsort(e["active_lanes"], kind="stable")
                cuts = np.searchsorted(np.asarray(e["active_lanes"])[by_lane],
                                       np.arange(e["num_entities"] + 1))
                for lane in range(e["num_entities"]):
                    pick = by_lane[cuts[lane]:cuts[lane + 1]]
                    mine = rows[pick]
                    tables[name][lane] = newton_solve(
                        x[mine], y[mine],
                        np.asarray(e["active_weights"])[pick], offsets[mine],
                        e["l2"], tables[name][lane])
                lanes = np.asarray(e["lanes"])
                known = lanes >= 0
                scores[name] = known * np.einsum(
                    "nd,nd->n", x, tables[name][np.where(known, lanes, 0)])
            history.append(game_objective(xg, w, as_tables(), y, l2_fixed))
    return {"w": w, "tables": tables, "objective_history": history}
