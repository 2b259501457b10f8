"""Role "fit" for a GLMix model: fixed effect + per-user random effect,
logistic, over a seeded replica of a MovieLens corpus's shape statistics.

The generator follows `photon_ml_tpu/data/synthetic_bench.py`
(`make_movielens_like`: lognormal user activity, lognormal item popularity,
multi-hot genres, one-hot user buckets, a planted mixed-effect truth) at the
sizes the configuration file gives, in float32 and in row blocks on a few
threads, because every run of every check pays for it. The training
configuration is `bench.py`'s `_game_setup(mode="glmix")`.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import reference

N_USER_FEATS = 1 + 7 + 4        # gender, 7 age buckets, 4 occupation buckets
BLOCK = 500_000


def make_ratings(n_train, n_val, users, items, genres, seed, shape_seed):
    """(user_ids, response, x_global, x_user): `n_train` training ratings,
    then `n_val` validation ratings.

    How many ratings each user has, in either part, and the global truth come
    from `shape_seed`, which the configuration fixes: they are the corpus's
    shape, and with them every per-user bucket has the same size under every
    seed, so every seed runs the same programs over the same amount of work.
    `seed` decides which user has which activity, the order of the rows, the
    items, the per-user and per-item truth and the labels."""
    shape = np.random.default_rng(shape_seed)
    user_prop = shape.lognormal(0.0, 1.1, users)
    user_prop /= user_prop.sum()
    counts = [shape.multinomial(n, user_prop) for n in (n_train, n_val)]
    w_global = (shape.normal(size=genres + N_USER_FEATS + 1) * 0.8
                ).astype(np.float32)

    rng = np.random.default_rng(seed)
    who = rng.permutation(users).astype(np.int32)
    user_ids = np.concatenate([rng.permutation(np.repeat(who, c))
                               for c in counts])
    rows = n_train + n_val
    item_prop = rng.lognormal(0.0, 1.4, items)
    item_ids = rng.choice(items, size=rows,
                          p=item_prop / item_prop.sum()).astype(np.int32)
    item_genres = (rng.uniform(size=(items, genres))
                   < 2.0 / genres).astype(np.float32)
    user_feats = np.zeros((users, N_USER_FEATS), np.float32)
    user_feats[:, 0] = rng.uniform(size=users) < 0.28
    user_feats[np.arange(users), 1 + rng.integers(0, 7, users)] = 1.0
    user_feats[np.arange(users), 8 + rng.integers(0, 4, users)] = 1.0

    d_global, d_user = genres + N_USER_FEATS + 1, genres + 1
    w_user = rng.normal(size=(users, d_user)).astype(np.float32)
    w_item = (rng.normal(size=(items, N_USER_FEATS + 1)) * 0.5
              ).astype(np.float32)
    draw = rng.random(rows, np.float32)

    # what depends on one entity alone is computed once per entity
    z_item = item_genres @ w_global[:genres] + w_item[:, -1]
    z_user = (user_feats @ w_global[genres:-1] + w_user[:, -1]
              + w_global[-1])
    x_global = np.empty((rows, d_global), np.float32)
    x_user = np.empty((rows, d_user), np.float32)
    response = np.empty(rows, np.float32)

    def block(lo):
        hi = min(lo + BLOCK, rows)
        u, i = user_ids[lo:hi], item_ids[lo:hi]
        ig, uf = item_genres[i], user_feats[u]
        x_global[lo:hi, :genres] = ig
        x_global[lo:hi, genres:-1] = uf
        x_global[lo:hi, -1] = 1.0
        x_user[lo:hi, :genres] = ig
        x_user[lo:hi, -1] = 1.0
        z = (z_item[i] + z_user[u]
             + np.einsum("nd,nd->n", ig, w_user[u, :genres])
             + np.einsum("nd,nd->n", uf, w_item[i, :-1]))
        response[lo:hi] = draw[lo:hi] < 1.0 / (1.0 + np.exp(-z))

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(block, range(0, rows, BLOCK)))
    return user_ids, response, x_global, x_user


class GameFit:
    def __init__(self, config, seed, chips):
        import jax
        from photon_ml_tpu.data.game_data import build_game_dataset
        from photon_ml_tpu.game import (FixedEffectCoordinateConfig,
                                        GameTrainingConfig,
                                        GLMOptimizationConfig,
                                        RandomEffectCoordinateConfig)
        from photon_ml_tpu.optim import (OptimizerConfig,
                                         RegularizationContext,
                                         RegularizationType)
        from photon_ml_tpu.parallel import make_mesh

        p = config["params"]
        n_val = int(round(p["validation_share"] * config["rows"]))
        n_train = config["rows"] - n_val
        user_ids, response, x_global, x_user = make_ratings(
            n_train, n_val, config["users"], config["items"],
            config["genres"], seed, p["shape_seed"])
        assert x_global.shape[1] == config["global_width"]
        assert x_user.shape[1] == config["per_user_width"]
        ds = build_game_dataset(response, {"global": x_global,
                                           "per_user": x_user},
                                entity_ids={"userId": user_ids})
        # slices are views and cost no copy
        self.train = ds.subset(slice(0, n_train))
        self.val = ds.subset(slice(n_train, ds.num_rows))
        del ds, x_global, x_user
        self.train_rows = self.train.num_rows

        l2 = RegularizationContext(RegularizationType.L2)

        def opt(weight):
            return GLMOptimizationConfig(
                optimizer=OptimizerConfig(
                    max_iterations=p["max_iterations"]),
                regularization=l2, regularization_weight=weight)

        self.l2_fixed, self.l2_user = p["l2_fixed"], p["l2_per_user"]
        self.cfg = GameTrainingConfig(
            task_type="logistic_regression",
            coordinates={
                "fixed": FixedEffectCoordinateConfig(
                    "global", opt(self.l2_fixed)),
                "perUser": RandomEffectCoordinateConfig(
                    "userId", "per_user", opt(self.l2_user),
                    active_data_upper_bound=p["active_data_upper_bound"]),
            },
            updating_sequence=["fixed", "perUser"],
            num_outer_iterations=p["outer_iterations"], seed=seed)
        # a one-device mesh is what cli.train runs by default (--mesh auto)
        self.mesh = make_mesh(devices=jax.devices()[:chips])
        self.last = None
        self.info = {"train_rows": self.train_rows,
                     "validation_rows": self.val.num_rows,
                     "users": int(len(self.train.entity_vocabs["userId"]))}

    @staticmethod
    def _arrays(model):
        return [m.glm.coefficients.means if hasattr(m, "glm")
                else m.coefficients for m in model.coordinates.values()]

    def fit(self):
        """One whole fit from a cold model, to the model's coefficients on
        the device and the objective history as Python floats."""
        import jax
        from photon_ml_tpu.game import GameEstimator
        from photon_ml_tpu.parallel import mesh_residency
        # Free the last fit's blocks before this one builds its own. The
        # process-wide mesh registry pins every fit's staged blocks (a FIFO
        # of 256 entries, 2.3 GB a fit here): without clear() the fourth fit
        # in one process runs out of device memory (PERF.md, PR 24).
        self.last = None
        mesh_residency.clear()
        result = GameEstimator(self.cfg, mesh=self.mesh).fit(
            self.train, validation_dataset=self.val,
            evaluator_specs=["AUC"])
        jax.block_until_ready(self._arrays(result.descent.model))
        self.last = result
        return result

    def record(self, result) -> dict:
        timings = result.descent.timings
        return {"objective_history": [float(v) for v in
                                      result.objective_history],
                "timings": {k: float(v) for k, v in timings.items()},
                "host_blocked_s": float(timings.host_blocked_total()),
                "validation": {k: float(v) for k, v in
                               result.validation.items()}}

    def check(self, records) -> dict:
        """The objective the program reports is the objective of the model
        it returns (float64, all training rows), the history does not rise,
        and every fit of the window gave the same history."""
        import time
        if self.last is None:
            return {"ok": False, "why": "the last fit of the window failed"}
        t0 = time.perf_counter()
        model = self.last.descent.model
        fixed, per_user = model.coordinates["fixed"], \
            model.coordinates["perUser"]
        w = np.asarray(fixed.glm.coefficients.means, np.float64)
        table = np.asarray(per_user.global_coefficients(), np.float64)
        ours = reference.glmix_objective(
            self.train.feature_shards["global"],
            self.train.feature_shards["per_user"],
            per_user.lanes_for(self.train), self.train.response, w, table,
            self.l2_fixed, self.l2_user)
        seconds = time.perf_counter() - t0
        first = records[0]["objective_history"]
        last = records[-1]["objective_history"]
        rel = abs(ours - last[-1]) / abs(ours)
        out = {
            "seconds": seconds, "objective_float64": ours, "objective_reported": last[-1],
            "objective_rel_gap": rel,
            "objective_matches": bool(rel <= 1e-4),
            "history_not_rising": all(
                b <= a + 1e-6 * abs(a) for a, b in zip(last, last[1:])),
            "fits_agree": all(reference.same_to(
                r["objective_history"], first, 1e-6) for r in records),
            "finite": bool(np.isfinite(w).all() and np.isfinite(table).all()),
        }
        out["ok"] = all(out[k] for k in ("objective_matches",
                                         "history_not_rising", "fits_agree",
                                         "finite"))
        return out


def build(config, seed, chips):
    return GameFit(config, seed, chips)
