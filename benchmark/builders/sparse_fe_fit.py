"""Role "fit" for a wide sparse fixed effect alone: L2 logistic regression by
L-BFGS over a seeded replica of a hashed click log's shape, one `scipy.sparse`
CSR shard handed to `GameEstimator` as `cli.train` would hand it a LIBSVM
file. Fitting, recording and the set-up around them are `game_fit.py`'s; the
float64 side of `check` is `benchmark/reference_sparse.py`.

The work of a fit is fixed by the configuration: `max_iterations` L-BFGS
iterations at tolerance 0, so a fit is `max_iterations + 2` data passes on
every machine, and whether the model it returns is good enough is decided by
the float64 certificate here, not by the solver's own stopping rule (which
on this chip's float32 objective stops after 27, 34, 37 or 38 passes of one
problem: PERF.md section 6, PR 31).
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse as sp

from benchmark import reference, reference_sparse
from benchmark.builders.game_fit import GameFit
from benchmark.reference_game import bfloat16

# The limits of `check`, each with its readings at the real size (PERF.md
# section 6, PR 32, has the runs): what a sound fit reads at the most over
# eight seeds on the chip, and what the limit is there to refuse. The
# lower-precision control is the reference's own: `lower_precision_control`,
# a 30-iteration L-BFGS fit with bfloat16 operands in both products of every
# pass, scored with bfloat16 operands. SCORES is the limit that refuses it.
#: |program's score - float64 margin| <= this * max(|margin|, 1), every
#: training row. A float32 sum of 39 products reads 3.7e-7 to 4.6e-7; the
#: control reads 8.3e-3.
SCORES = 1e-4
#: |reported objective - float64 objective of the returned model| <= this
#: * objective. A float32 sum of 2.85M terms with the chip's log1p (good to
#: 2.6e-4) reads 2.4e-6 to 2.6e-6; the control reads 8.6e-7 (an objective is
#: a sum in which the roundings cancel); a report without the penalty would
#: read 2e-3.
OBJECTIVE = 1e-4
#: (f(w) - proven lower bound of f*) / f(w) <= this, by
#: `reference_sparse.certify`, which takes Newton-CG steps wherever the
#: strong-convexity bound at the model itself is over a third of the limit.
#: It is what makes a fixed iteration count a fit. Sound fits read 6.9e-5 to
#: 3.2e-4 (two of eight took a step). It refuses a fit that stopped far
#: from the optimum: cut to 15 iterations reads 3.7e-3, to 3 in the
#: rehearsal 1e-2. It does NOT tell 30 iterations from 20 (6.2e-4 after
#: three steps) or 25 (1.2e-4 after one): the pass count does
#: (`work_fixed`). Nor does it refuse the control, whose bfloat16 solve
#: still ends 1.8e-4 from the optimum: a model that close IS a fit, and
#: the control's scores are what is wrong with it.
GAP = 1e-3
#: |validation AUC reported - float64 AUC by rank of the float64 margins of
#: the held-out rows|. The program's float32 scores order all but a few of
#: 4e9 pairs as float64 does: reads 4.9e-9 to 6.6e-8; the control reads
#: 1.4e-7 (3.5e-6 where its solve is cut to 20 iterations). Where the
#: held-out rows are few (the rehearsal's 1,000: one pair of 2e5 is 5e-6)
#: the limit is AUC_PAIRS swapped or tied pairs.
AUC = 1e-6
AUC_PAIRS = 4


def field_vocabularies(p) -> list:
    """Tokens a field: `numeric_fields` fields of `numeric_buckets` tokens
    (a count or a value in log-spaced buckets), then the categorical fields
    with the cardinalities the configuration lists, each capped at
    `categorical_cap` (rarer tokens share one token, as a click log is
    cleaned before it is hashed)."""
    return ([p["numeric_buckets"]] * p["numeric_fields"]
            + [max(min(int(v), p["categorical_cap"]),
                   p.get("categorical_floor", 1))
               for v in p["categorical_cardinalities"]])


def make_shape(columns, p):
    """What `shape_seed` fixes, the same under every run seed: for each
    field the cumulative popularity of its tokens (a power law in the
    token's rank) and the column each token hashes to (one hash space for
    all fields, so tokens collide as in any hashed log), and the planted
    truth, a weight a column and a bias."""
    shape = np.random.default_rng(p["shape_seed"])
    fields = []
    for size in field_vocabularies(p):
        weight = (np.arange(size) + p["power_law_shift"]) ** -p["power_law"]
        cdf = np.cumsum(weight / weight.sum())
        cdf[-1] = 1.0
        fields.append((cdf, shape.integers(0, columns, size, dtype=np.int32)))
    truth = (p["truth_scale"] * shape.standard_normal(columns)
             ).astype(np.float32)
    return fields, truth


def draw_rows(fields, n, seed):
    """[n, fields] int32 columns, sorted within a row, no column twice in a
    row: one token a field from the field's popularity, hashed. A row in
    which two fields' tokens collide (under 0.1% of rows) is drawn again,
    so every row has exactly as many non-zeros as fields and every seed
    runs the same shapes."""
    def draw(f, rows, round_):
        rng = np.random.default_rng([seed, f, round_])
        cdf, column_of = fields[f]
        return column_of[np.searchsorted(cdf, rng.random(rows))]

    cols = np.empty((n, len(fields)), np.int32)

    def fill(f):
        cols[:, f] = draw(f, n, 0)

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(fill, range(len(fields))))
    cols.sort(axis=1)
    redrawn, round_ = 0, 0
    bad = np.flatnonzero((cols[:, 1:] == cols[:, :-1]).any(axis=1))
    while len(bad):
        round_ += 1
        redrawn += len(bad)
        again = np.stack([draw(f, len(bad), round_)
                          for f in range(len(fields))], axis=1)
        again.sort(axis=1)
        cols[bad] = again
        bad = bad[(again[:, 1:] == again[:, :-1]).any(axis=1)]
    return cols, redrawn


def as_csr(cols, columns):
    """The rows as float32 CSR with value 1.0 a non-zero. The rows are
    sorted and hold no column twice, which SciPy is told and need not
    check."""
    n, k = cols.shape
    x = sp.csr_matrix((np.ones(n * k, np.float32), cols.reshape(-1),
                       np.arange(0, n * k + 1, k, dtype=np.int32)),
                      shape=(n, columns))
    x.has_sorted_indices = True
    x.has_canonical_format = True
    return x


def make_clicks(n_train, n_val, columns, seed, p):
    """(x_train, y_train, x_val, y_val, counts): rows and labels from
    `seed` over the shape `p["shape_seed"]` fixes."""
    t0 = time.perf_counter()
    fields, truth = make_shape(columns, p)
    t1 = time.perf_counter()
    cols, redrawn = draw_rows(fields, n_train + n_val, seed)
    z = p["truth_bias"] + truth[cols].sum(axis=1, dtype=np.float64)
    rng = np.random.default_rng([seed, len(fields)])
    y = (rng.random(len(z)) < reference.sigmoid(z)).astype(np.float32)
    counts = {"tokens": int(sum(len(c) for c, _ in fields)),
              "hashed_columns": int(len(np.unique(np.concatenate(
                  [column_of for _, column_of in fields])))),
              "rows_redrawn": int(redrawn),
              "label_rate": float(y[:n_train].mean()),
              "shape_s": t1 - t0, "rows_s": time.perf_counter() - t1}
    return (as_csr(cols[:n_train], columns), y[:n_train],
            as_csr(cols[n_train:], columns), y[n_train:], counts)


class SparseFeFit(GameFit):
    SHARD, COORDINATE = "global", "fixed"

    def __init__(self, config, seed, chips):
        import jax
        from photon_ml_tpu.data.game_data import build_game_dataset
        from photon_ml_tpu.game import (FixedEffectCoordinateConfig,
                                        GameTrainingConfig,
                                        GLMOptimizationConfig)
        from photon_ml_tpu.optim import (OptimizerConfig,
                                         RegularizationContext,
                                         RegularizationType)
        from photon_ml_tpu.parallel import make_mesh

        p = config["params"]
        n_val = int(round(p["validation_share"] * config["rows"]))
        n_train = config["rows"] - n_val
        x, y, x_val, y_val, counts = make_clicks(
            n_train, n_val, config["columns"], seed, p)
        assert x.nnz == n_train * config["nonzeros_per_row"]
        self.train = build_game_dataset(y, {self.SHARD: x})
        self.val = build_game_dataset(y_val, {self.SHARD: x_val})
        self.train_rows = n_train
        self.l2 = float(config["l2_weight"])
        self.max_iterations = int(config["max_iterations"])
        self.cfg = GameTrainingConfig(
            task_type="logistic_regression",
            coordinates={self.COORDINATE: FixedEffectCoordinateConfig(
                self.SHARD, GLMOptimizationConfig(
                    optimizer=OptimizerConfig(
                        max_iterations=self.max_iterations,
                        tolerance=float(config["tolerance"])),
                    regularization=RegularizationContext(
                        RegularizationType.L2),
                    regularization_weight=self.l2))},
            updating_sequence=[self.COORDINATE],
            num_outer_iterations=p["outer_iterations"], seed=seed)
        # a one-device mesh is what cli.train runs by default (--mesh auto):
        # the shard carries its column-sorted view, the solve is jit_fe_solve
        self.mesh = make_mesh(devices=jax.devices()[:chips])
        self.last = None
        self.info = dict(counts, train_rows=n_train, validation_rows=n_val,
                         columns=config["columns"], nnz=int(x.nnz),
                         itemsize=int(x.dtype.itemsize))

    def fit(self):
        result = super().fit()
        # the program's own counters of the shard's build, as the FIRST fit
        # reported them (it packed the shard; a later fit of the same
        # dataset reports pack_s 0). A commit that has none leaves it out
        built = result.coordinate_build.get(self.COORDINATE)
        if built and "fe_build" not in self.info:
            self.info["fe_build"] = built
        return result

    def record(self, result) -> dict:
        out = super().record(result)
        tracker = result.descent.trackers[f"0/{self.COORDINATE}"]
        out.update(
            passes=tracker.data_passes, iterations=tracker.iterations,
            ended_by=sorted(tracker.reasons),
            pack_s=result.coordinate_build.get(self.COORDINATE, {}).get(
                "pack_s"),
            w=np.asarray(result.descent.model.coordinates[
                self.COORDINATE].glm.coefficients.means))
        return out

    def _float64_side(self):
        """(training matrix, validation matrix) as the reference holds
        them, made once a check."""
        return (reference_sparse.as_float64(
            self.train.feature_shards[self.SHARD]),
                reference_sparse.as_float64(
            self.val.feature_shards[self.SHARD]))

    def lower_precision_control(self):
        """(w, training scores, validation scores, objective) as the
        reference computes them in the precision below the configuration's:
        the model its own L-BFGS reaches in `max_iterations` iterations
        with bfloat16 operands and float32 sums in both products of every
        pass (`reference_sparse.lbfgs_fit`), that model's margins computed
        the same way, and the objective summed from them in float32.
        `check(records, control=...)` has to refuse it."""
        w = reference_sparse.lbfgs_fit(
            self._float64_side()[0], self.train.response, self.l2,
            self.max_iterations, operands=bfloat16)
        low = bfloat16(w)                       # float32 holding bfloat16s

        def scores(dataset):
            x = dataset.feature_shards[self.SHARD]
            x = sp.csr_matrix((bfloat16(x.data), x.indices, x.indptr),
                              shape=x.shape)
            return (x @ low).astype(np.float32)

        train = scores(self.train)
        objective = float(
            reference.logloss(train, self.train.response).sum(
                dtype=np.float32)
            + np.float32(0.5 * self.l2) * (low * low).sum(dtype=np.float32))
        return w, train, scores(self.val), objective

    def check(self, records, control=None) -> dict:
        """`correct`, on the model the last fit of the window returned (or
        on `control`, see `lower_precision_control`), by results alone. The
        limits and their reasons are at the top of this file.

        - the program's scores of all training rows under that model are
          its float64 margins (SCORES), and the objective it reports is the
          float64 objective of that model (OBJECTIVE);
        - that objective is within GAP of a proven lower bound of the
          optimum (`reference_sparse.certify`);
        - the validation AUC it reports is the float64 AUC by rank of the
          held-out rows' float64 margins (AUC);
        - the history does not rise, held against the zero model's n log 2;
        - every fit of the window ended by MAX_ITERATIONS after exactly
          `max_iterations + 2` data passes, and returned the same finite
          coefficients (1e-6)."""
        if self.last is None:
            return {"ok": False, "why": "the last fit of the window failed"}
        t0 = time.perf_counter()
        model = self.last.descent.model
        history = records[-1]["objective_history"]
        x, x_val = self._float64_side()
        if control is None:
            w = np.asarray(records[-1]["w"], np.float64)
            scores = np.asarray(model.score_dataset(self.train), np.float64)
            reported = history[-1]
            auc_reported = records[-1]["validation"]["AUC"]
        else:
            w, scores, val_scores, reported = control
            auc_reported = reference_sparse.auc(val_scores, self.val.response)
        margins = reference_sparse.margins(x, w)
        ours = reference_sparse.objective_of(margins, self.train.response, w,
                                             self.l2)
        t1 = time.perf_counter()
        # asked for a third of the limit, so that it takes its Newton-CG
        # step well before the bound at w itself could refuse a sound fit
        certificate = reference_sparse.certify(x, self.train.response, w,
                                               self.l2, GAP / 3)
        t2 = time.perf_counter()
        auc = reference_sparse.auc(reference_sparse.margins(x_val, w),
                                   self.val.response)
        steps = [self.train_rows * np.log(2.0)] + history
        out = {
            "objective_seconds": t1 - t0, "certificate_seconds": t2 - t1,
            "scores_gap": float((np.abs(scores - margins)
                                 / np.maximum(np.abs(margins), 1.0)).max()),
            "objective_float64": ours, "objective_reported": reported,
            "objective_rel_gap": abs(ours - reported) / abs(ours),
            "certificate": certificate,
            "auc_float64": auc, "auc_reported": auc_reported,
            "auc_gap": abs(auc - auc_reported),
            "passes": [r["passes"] for r in records],
            "ended_by": sorted({e for r in records for e in r["ended_by"]}),
            # not part of `ok`: a commit without the counter reads None
            "repacked_s": [r["pack_s"] for r in records],
            "history_not_rising": all(
                b <= a + 1e-6 * abs(a) for a, b in zip(steps, steps[1:])),
            "work_fixed": all(
                r["passes"] == self.max_iterations + 2
                and r["ended_by"] == ["MAX_ITERATIONS"] for r in records),
            "fits_agree": all(
                reference.same_to(r["w"], records[0]["w"], 1e-6)
                and reference.same_to(r["objective_history"],
                                      records[0]["objective_history"], 1e-6)
                for r in records),
            "at_optimum": bool(certificate["rel_gap"] <= GAP),
            "finite": bool(np.isfinite(w).all()),
        }
        out["scores_match"] = bool(out["scores_gap"] <= SCORES)
        out["objective_matches"] = bool(out["objective_rel_gap"] <= OBJECTIVE)
        positives = float(np.sum(self.val.response > 0.5))
        out["auc_matches"] = bool(out["auc_gap"] <= max(
            AUC, AUC_PAIRS / (positives * (len(self.val.response)
                                           - positives))))
        out["seconds"] = time.perf_counter() - t0
        out["ok"] = all(v for v in out.values() if isinstance(v, bool))
        return out


def build(config, seed, chips):
    return SparseFeFit(config, seed, chips)
