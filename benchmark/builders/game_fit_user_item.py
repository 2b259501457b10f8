"""Role "fit" for the GLMix model as the paper states it: fixed effect +
per-user random effect (on item features) + per-item random effect (on user
features), logistic, over a seeded replica of a MovieLens corpus's shape
statistics. The training configuration is `bench.py`'s
`_game_setup(mode="convex")`; fitting, recording and the set-up around them
are `game_fit.py`'s.

It does not reuse `game_fit.make_ratings` because that draws item popularity
from the run seed, so the per-item bucket shapes would differ by seed and
every seed would compile its own programs; and from a lognormal that is far
less skewed than the corpus's ratings per movie, which this generator takes
from the configuration file (`params.item_count_quantiles`).
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark import reference, reference_game
from benchmark.builders.game_fit import BLOCK, N_USER_FEATS, GameFit

#: items whose subproblem `check` certifies, spread over all buckets
CERTIFIED_ITEMS = 512
# The limits of `check`, each between two readings on the chip at the real
# size (PERF.md section 6, PR 27, has the runs): what a sound fit reads at
# the most, and what the limit is there to refuse.
#: |program's score - float64 margin| <= this * max(|margin|, 1), every
#: training row. Float32 sums of 33 + 21 + 13 products read 7e-7; the
#: reference's margins with bfloat16 operands and float32 sums, the
#: precision below the configuration's, read 2.3e-2. It is the one limit
#: that control fails: the chip's float32 log1p is good to 2.6e-4 only, so
#: a float32 solve there stops as far from its optimum as a bfloat16 one
#: (both read up to 1e-2 below), and an objective is a sum in which the
#: roundings cancel (4.5e-6 for the control).
SCORES = 1e-4
#: |reported objective - float64 objective| <= this * objective. A float32
#: sum of 7.6M terms reads 1.1e-6 to 1.6e-6 (the chip's log1p again: 6e-8
#: on the CPU); a report without the per-item penalty would read 6e-3.
OBJECTIVE = 1e-4
#: |coef - optimum| <= this * max(|coef|, 1) for each certified item, by
#: `reference_game.entity_distance`. A float32 solve stops when f stops
#: changing: the worst item of 512 reads 1.1e-3 to 1.2e-2 over the seeds
#: (1.2e-3 at the most on the CPU, whose log1p is good to 2e-7). With the
#: count / cap rescale dropped in the program it reads 1.8e-1, under the
#: offsets of the sweep before 2.6e-1, with no offsets 8.1e-1. ISSUE 27
#: asked for 2e-3 on the direct bound |grad| / l2: that bound reads 4e-2 to
#: 3e-1 on a correct fit (loose by the condition number).
CERTIFICATE = 5e-2
#: no update of a random effect raises the objective by more than this,
#: relative. A capped random effect trains on a reweighted sample of its
#: entities' rows while the objective counts them all, so its update can
#: raise it, and perItem's last one does in every run: by 1.6e-3 to 1.8e-3
#: at the real size and up to 1.2e-2 at the rehearsal's (the fewer capped
#: items, the less their sampling errors cancel; it grows as the cap
#: falls). A perItem that never sees the other coordinates' scores reads
#: 7.8e-2 at the rehearsal's size and 1.1e-1 at a twentieth of the real one
#: (CPU); at the real size on the chip that solve goes non-finite, the
#: program's quarantine rolls it back, and CERTIFICATE refuses the fit.
RISE = 3e-2


def quantile_values(knots, n):
    """`n` values, in rising order, of the quantile function that is
    log-linear between `knots` ([[share, value], ...]), at the middles of
    `n` equal shares."""
    share, value = np.asarray(knots, np.float64).T
    return np.exp(np.interp((np.arange(n) + 0.5) / n, share, np.log(value)))


def make_ratings(n_train, n_val, users, items, genres, seed, shape_seed,
                 item_count_quantiles):
    """(user_ids, item_ids, response, x_global, x_user, x_item): `n_train`
    training ratings, then `n_val` validation ratings, with the truth
    `game_fit.make_ratings` plants.

    How many ratings each user AND each item has, in either part, and the
    global truth come from `shape_seed`, which the configuration fixes, so
    every bucket of either random effect has the same size under every
    seed. An item's share of the ratings follows `item_count_quantiles`,
    the corpus's ratings per movie (the configuration file says where each
    knot is from). `seed` decides which user has which activity, which item
    has which popularity, who rates what, the order of the rows, the
    per-user and per-item truths and the labels."""
    shape = np.random.default_rng(shape_seed)
    user_prop = shape.lognormal(0.0, 1.1, users)
    user_prop /= user_prop.sum()
    user_counts = [shape.multinomial(n, user_prop) for n in (n_train, n_val)]
    w_global = (shape.normal(size=genres + N_USER_FEATS + 1) * 0.8
                ).astype(np.float32)
    item_prop = quantile_values(item_count_quantiles, items)
    item_prop /= item_prop.sum()
    item_counts = [shape.multinomial(n, item_prop) for n in (n_train, n_val)]

    rng = np.random.default_rng(seed)
    who = rng.permutation(users).astype(np.int32)
    which = rng.permutation(items).astype(np.int32)
    user_ids = np.concatenate([rng.permutation(np.repeat(who, c))
                               for c in user_counts])
    item_ids = np.concatenate([rng.permutation(np.repeat(which, c))
                               for c in item_counts])
    rows = n_train + n_val
    item_genres = (rng.uniform(size=(items, genres))
                   < 2.0 / genres).astype(np.float32)
    user_feats = np.zeros((users, N_USER_FEATS), np.float32)
    user_feats[:, 0] = rng.uniform(size=users) < 0.28
    user_feats[np.arange(users), 1 + rng.integers(0, 7, users)] = 1.0
    user_feats[np.arange(users), 8 + rng.integers(0, 4, users)] = 1.0

    d_global, d_user, d_item = (genres + N_USER_FEATS + 1, genres + 1,
                                N_USER_FEATS + 1)
    w_user = rng.normal(size=(users, d_user)).astype(np.float32)
    w_item = (rng.normal(size=(items, d_item)) * 0.5).astype(np.float32)
    draw = rng.random(rows, np.float32)

    # what depends on one entity alone is computed once per entity
    z_item = item_genres @ w_global[:genres] + w_item[:, -1]
    z_user = (user_feats @ w_global[genres:-1] + w_user[:, -1]
              + w_global[-1])
    x_global = np.empty((rows, d_global), np.float32)
    x_user = np.empty((rows, d_user), np.float32)
    x_item = np.empty((rows, d_item), np.float32)
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
        x_item[lo:hi, :-1] = uf
        x_item[lo:hi, -1] = 1.0
        z = (z_item[i] + z_user[u]
             + np.einsum("nd,nd->n", ig, w_user[u, :genres])
             + np.einsum("nd,nd->n", uf, w_item[i, :-1]))
        response[lo:hi] = draw[lo:hi] < 1.0 / (1.0 + np.exp(-z))

    with ThreadPoolExecutor(8) as pool:
        list(pool.map(block, range(0, rows, BLOCK)))
    return user_ids, item_ids, response, x_global, x_user, x_item


def sample_lanes(sizes, total, rng):
    """[(bucket, lanes within it)]: `total` entities in all (or every one,
    where there are fewer), an equal share from each bucket and what a
    small bucket cannot give from the others in turn."""
    take = [min(size, total // len(sizes)) for size in sizes]
    for b in np.argsort(sizes)[::-1]:
        take[b] += min(sizes[b] - take[b], total - sum(take))
    return [(b, np.sort(rng.choice(sizes[b], take[b], replace=False)))
            for b in range(len(sizes)) if take[b]]


class GameFitUserItem(GameFit):
    SHARDS = {"perUser": ("userId", "per_user"),
              "perItem": ("itemId", "per_item")}

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
        user_ids, item_ids, response, x_global, x_user, x_item = \
            make_ratings(n_train, n_val, config["users"], config["items"],
                         config["genres"], seed, p["shape_seed"],
                         p["item_count_quantiles"])
        assert x_global.shape[1] == config["global_width"]
        assert x_user.shape[1] == config["per_user_width"]
        assert x_item.shape[1] == config["per_item_width"]
        ds = build_game_dataset(
            response, {"global": x_global, "per_user": x_user,
                       "per_item": x_item},
            entity_ids={"userId": user_ids, "itemId": item_ids})
        self.train = ds.subset(slice(0, n_train))     # views, no copy
        self.val = ds.subset(slice(n_train, ds.num_rows))
        del ds, x_global, x_user, x_item
        self.train_rows = self.train.num_rows

        l2 = RegularizationContext(RegularizationType.L2)

        def opt(weight):
            return GLMOptimizationConfig(
                optimizer=OptimizerConfig(
                    max_iterations=p["max_iterations"]),
                regularization=l2, regularization_weight=weight)

        self.l2 = {"fixed": p["l2_fixed"], "perUser": p["l2_per_user"],
                   "perItem": p["l2_per_item"]}
        coordinates = {"fixed": FixedEffectCoordinateConfig(
            "global", opt(self.l2["fixed"]))}
        for name, (entity, shard) in self.SHARDS.items():
            coordinates[name] = RandomEffectCoordinateConfig(
                entity, shard, opt(self.l2[name]),
                active_data_upper_bound=p["active_data_upper_bound"])
        self.cfg = GameTrainingConfig(
            task_type="logistic_regression", coordinates=coordinates,
            updating_sequence=["fixed", "perUser", "perItem"],
            num_outer_iterations=p["outer_iterations"], seed=seed)
        # a one-device mesh is what cli.train runs by default (--mesh auto)
        self.mesh = make_mesh(devices=jax.devices()[:chips])
        self.seed = seed
        self.last = None
        self.info = {"train_rows": self.train_rows,
                     "validation_rows": self.val.num_rows,
                     "users": int(len(self.train.entity_vocabs["userId"])),
                     "items": int(len(self.train.entity_vocabs["itemId"]))}

    def fit(self):
        result = super().fit()
        # the program's own counters of each random-effect coordinate's
        # build; a commit that has none leaves the key out
        built = getattr(result, "coordinate_build", None)
        if built:
            self.info["coordinates"] = built
        return result

    def _blocks_of(self, name):
        """The coordinate's per-entity blocks as the last fit built them
        (the program memoizes them on the dataset, so this builds nothing)."""
        from photon_ml_tpu.data.batching import build_random_effect_dataset
        return build_random_effect_dataset(
            self.train, self.cfg.coordinates[name].data_config(self.cfg.seed))

    def _returned(self):
        """(w, {name: table}, {name: lanes}) of the model the last fit
        returned, in float64 and in each shard's own feature space. A row's
        lane is the table row of its entity, whether the row trained it or
        was passive: counted here from the model's entity order, not taken
        from the program's blocks (the configuration discards no row)."""
        model = self.last.descent.model.coordinates
        w = np.asarray(model["fixed"].glm.coefficients.means, np.float64)
        tables, lanes = {}, {}
        for name, (entity, _) in self.SHARDS.items():
            tables[name] = np.asarray(model[name].global_coefficients(),
                                      np.float64)
            red = self._blocks_of(name)
            assert not len(red.discarded_rows)
            lane_of = np.full(len(self.train.entity_vocabs[entity]), -1)
            lane_of[red.entity_ids] = np.arange(len(tables[name]))
            lanes[name] = lane_of[self.train.entity_indices[entity]]
        return w, tables, lanes

    def _tables(self, tables, lanes):
        return [(self.train.feature_shards[shard], lanes[name], tables[name],
                 self.l2[name]) for name, (_, shard) in self.SHARDS.items()]

    def _sampled_items(self):
        """(samples per entity of its bucket, lane, training rows, their
        weight, the program's weights of the block's cells) of a seeded
        sample of items from every bucket. The rows are those the program's
        reservoir kept. The weight is counted HERE: an item with more
        training rows than the cap has count / cap on each kept row, any
        other 1."""
        red = self._blocks_of("perItem")
        counts = np.bincount(self.train.entity_indices["itemId"])
        cap = self.cfg.coordinates["perItem"].active_data_upper_bound
        rng = np.random.default_rng(self.seed)
        for b, picked in sample_lanes([bk.num_entities for bk in red.buckets],
                                      CERTIFIED_ITEMS, rng):
            bucket = red.buckets[b]
            program_weights = np.asarray(bucket.blocks.weights[picked],
                                         np.float64)
            for k, lane in enumerate(bucket.lane_start + picked):
                row_ids = bucket.row_ids[picked[k]]
                yield (bucket.samples_per_entity, int(lane),
                       row_ids[row_ids >= 0],
                       max(counts[red.entity_ids[lane]] / cap, 1.0),
                       program_weights[k][row_ids >= 0])

    def _item_subproblem(self, rows, weight, w, tables, lanes, operands=None):
        """(x, y, weights, offsets) of one item's subproblem on `rows`:
        the offsets are what the fixed and per-user coefficients give, in
        float64, or with `operands` as the lower-precision control computes
        a dot product (`reference_game.newton_solve`)."""
        shards = self.train.feature_shards
        if operands is None:
            def dots(x, table):
                return np.einsum("nd,nd->n", np.asarray(x, np.float64), table)
        else:
            def dots(x, table):
                return (operands(x) * operands(table)).sum(
                    axis=1, dtype=np.float32)
        offsets = (dots(shards["global"][rows], w[None, :])
                   + dots(shards["per_user"][rows],
                          tables["perUser"][lanes["perUser"][rows]]))
        return (shards["per_item"][rows], self.train.response[rows],
                np.full(len(rows), weight), offsets)

    def _certify_items(self, w, tables, lanes) -> dict:
        """Over the sampled items: the largest of `entity_distance`'s bound
        on the distance from the item's coefficients to the float64 optimum
        of its subproblem, over max(|coef|, 1), in all and by the bucket's
        samples per entity; and `weights_gap`, the largest relative distance
        of the program's own block weights from those counted here."""
        worst = {"items": 0, "worst": 0.0, "worst_lane": -1,
                 "direct_bound_worst": 0.0, "worst_by_samples": {},
                 "weights_gap": 0.0}
        for samples, lane, rows, weight, program_weights in \
                self._sampled_items():
            coef = tables["perItem"][lane]
            got = reference_game.entity_distance(
                *self._item_subproblem(rows, weight, w, tables, lanes),
                coef, self.l2["perItem"])
            scale = max(float(np.linalg.norm(coef)), 1.0)
            worst["items"] += 1
            worst["weights_gap"] = max(worst["weights_gap"], float(
                np.abs(program_weights / weight - 1.0).max()))
            worst["direct_bound_worst"] = max(
                worst["direct_bound_worst"], got["direct_bound"] / scale)
            worst["worst_by_samples"][samples] = max(
                worst["worst_by_samples"].get(samples, 0.0),
                got["distance"] / scale)
            if got["distance"] / scale > worst["worst"]:
                worst.update(worst=got["distance"] / scale, worst_lane=lane)
        return worst

    def lower_precision_control(self):
        """(model, scores, objective) as the reference computes them in the
        precision below the configuration's, bfloat16 operands and float32
        sums in every dot product: the returned model with the sampled
        items' coefficients from a Newton solve so computed (offsets too),
        started at the returned coefficients; that model's margins; and the
        objective summed from them in float32. `check(records, control=...)`
        has to refuse it."""
        w, tables, lanes = self._returned()
        low = reference_game.bfloat16
        tables = dict(tables, perItem=tables["perItem"].copy())
        for _, lane, rows, weight, _ in self._sampled_items():
            tables["perItem"][lane] = reference_game.newton_solve(
                *self._item_subproblem(rows, weight, w, tables, lanes,
                                       operands=low),
                self.l2["perItem"], tables["perItem"][lane], operands=low)
        scores = reference_game.game_margins(
            self.train.feature_shards["global"], w,
            self._tables(tables, lanes), operands=low)
        response = np.asarray(self.train.response, np.float32)
        objective = float(
            reference.logloss(scores.astype(np.float32), response).sum(
                dtype=np.float32)
            + sum(np.float32(0.5 * l2) * (low(t) * low(t)).sum(
                dtype=np.float32) for t, l2 in
                [(w, self.l2["fixed"])] + [(tables[n], self.l2[n])
                                           for n in self.SHARDS]))
        return (w, tables, lanes), scores, objective

    def check(self, records, control=None) -> dict:
        """`correct`, all on the model the last fit of the window returned
        (or on `control`, see `lower_precision_control`). The limits and
        their reasons are at the top of this file.

        - the program's scores of all training rows under that model are
          its float64 margins (SCORES), and the objective reported last is
          `reference_game.game_objective` of it (OBJECTIVE);
        - no update of the fixed effect raises the objective (1e-6
          relative; the first is held against the zero model's n log 2): it
          trains on every row at weight 1, so its subproblem IS the
          objective. No update of a random effect raises it by more than
          RISE. Every fit of the window gave the same history to 1e-6 (one
          program, one dataset);
        - perItem is visited last, so each item's coefficients are the
          optimum of its own subproblem under the final offsets, at the
          weights its count and the cap give: CERTIFIED_ITEMS sampled items
          hold CERTIFICATE, and the program's block weights are those
          weights;
        - every coefficient is finite."""
        if self.last is None:
            return {"ok": False, "why": "the last fit of the window failed"}
        t0 = time.perf_counter()
        last = records[-1]["objective_history"]
        if control is None:
            w, tables, lanes = self._returned()
            scores = np.asarray(self.last.descent.model.score_dataset(
                self.train), np.float64)
            reported = last[-1]
        else:
            (w, tables, lanes), scores, reported = control
        margins = reference_game.game_margins(
            self.train.feature_shards["global"], w,
            self._tables(tables, lanes))
        ours = reference_game.objective_of(
            margins, self.train.response, w, self._tables(tables, lanes),
            self.l2["fixed"])
        t1 = time.perf_counter()
        certificate = self._certify_items(w, tables, lanes)
        sequence = self.cfg.updating_sequence
        steps = [(sequence[k % len(sequence)], a, b) for k, (a, b) in
                 enumerate(zip([self.train_rows * np.log(2.0)] + last, last))]
        out = {
            "seconds": time.perf_counter() - t0,
            "objective_seconds": t1 - t0,
            "scores_gap": float((np.abs(scores - margins)
                                 / np.maximum(np.abs(margins), 1.0)).max()),
            "objective_float64": ours, "objective_reported": reported,
            "objective_rel_gap": abs(ours - reported) / abs(ours),
            "largest_rise": max((b - a) / abs(a) for name, a, b in steps
                                if name != "fixed"),
            "certificate": certificate,
            "fixed_steps_not_rising": all(
                b <= a + 1e-6 * abs(a) for name, a, b in steps
                if name == "fixed"),
            "fits_agree": all(reference.same_to(
                r["objective_history"], records[0]["objective_history"],
                1e-6) for r in records),
            "weights_rescaled": bool(certificate["weights_gap"] <= 1e-6),
            "items_at_optimum": bool(certificate["worst"] <= CERTIFICATE),
            "finite": bool(np.isfinite(w).all() and all(
                np.isfinite(t).all() for t in tables.values())),
        }
        out["scores_match"] = bool(out["scores_gap"] <= SCORES)
        out["objective_matches"] = bool(out["objective_rel_gap"] <= OBJECTIVE)
        out["rises_bounded"] = bool(out["largest_rise"] <= RISE)
        out["ok"] = all(v for v in out.values() if isinstance(v, bool))
        return out


def build(config, seed, chips):
    return GameFitUserItem(config, seed, chips)
