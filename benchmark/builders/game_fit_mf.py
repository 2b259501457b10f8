"""Role "fit" for the full GAME model: the three convex coordinates of
`game_fit_user_item.py` (fixed effect + per-user + per-item random effects)
and a per-user FACTORED random effect, each user's coefficients on the item
features `P^T c_u` with a latent `c_u` of rank 8 and one projection `P`
shared by all users, refitted alternately. The training configuration is the
retired `bench.py`'s `_game_setup(mode="full")`; data, fitting and recording
are `game_fit_user_item.py`'s, and what reads the model is here, since that
builder knows tables of per-entity coefficients only. The float64 reference
is `benchmark/reference_factored.py`.

`perItem` is no longer the coordinate visited last, so its items are not at
the optimum of their subproblems under the FINAL offsets, and the per-item
certificate of `game_fit_user_item.py` is that cell's to hold. What is
visited last here is the factored coordinate: `check` certifies both halves
of its last update, the projection it solved last under the final factors,
and the factors under the projection their solves ran under.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from benchmark import reference, reference_factored, reference_game
from benchmark.builders.game_fit_user_item import (OBJECTIVE, RISE, SCORES,
                                                   GameFitUserItem,
                                                   sample_lanes)

MF = "perUserMF"
#: users whose latent subproblem `check` certifies, spread over all buckets
CERTIFIED_USERS = 512
# The limits of `check`, each between two readings on the chip at the real
# size (PERF.md section 6, PR 35, has the runs). SCORES, OBJECTIVE and RISE
# are `game_fit_user_item.py`'s, whose reasons hold here:
# - SCORES (1e-4): float32 sums of 33 + 21 + 13 + 21 products and the product
#   C P in float32 read 9.6e-7 to 1.3e-6 over fourteen runs. The program
#   ITSELF was the lower-precision control before this limit was met: with
#   the factored coordinate's matrix products at a TPU's default precision
#   (bfloat16 operands, float32 sums) it read 2.4e-2 to 3.1e-2 on five runs,
#   where the reference's bfloat16 margins of `lower_precision_control` read
#   2.1e-2 and 3.2e-2 at the rehearsal's size on the CPU (two seeds).
# - OBJECTIVE (1e-4): a float32 sum of 7.6M terms reads 1.0e-6 to 1.4e-6. A
#   report without the penalty on P reads 4.5e-6 more or less at the real size
#   (|P|^2 / 2 is 14 of 3.2e6), which this limit cannot refuse, and 1e-3 at
#   the rehearsal's, which it does (PERF.md section 7, question 14).
# - RISE (3e-2): the factored coordinate trains a user on at most 256 rows
#   at weight count / 256, and its second update raises the objective by
#   8.9e-3 to 9.2e-3 in every run (its first lowers it by 5.5e-3 to 5.9e-3);
#   a random effect that never saw the other scores reads 8e-2 and more.
#: (f(P) - f*) / f(P) of the projection's refit is at most this, certified
#: in float64 by `reference_factored.projection_certificate` on the rows the
#: program's reservoir kept, at the weights the check counts for them, under
#: the final C and the final offsets: f(P) less a proven lower bound of f*,
#: f(v) - |g(v)|^2 / (2 l2) at a point v one Newton step from P. L-BFGS in
#: float32 under the upstream's stopping rule reads 2.6e-8 to 8.1e-8 (5.5e-7
#: and 3.0e-6 at the rehearsal's size on the CPU); the direct bound |g(P)|^2 / (2 l2) that
#: ISSUE 35 asked for reads 3.5e-4 to 1.5e-3 on those same fits, loose by
#: the refit's condition number (its curvature is a sum over 5.7M cells, l2
#: is 1), so it is reported and not judged. A projection left at its warm
#: start, under the C fitted to it, reads 4.7e-3 (9.1e-3 at the rehearsal's).
PROJECTION_GAP = 3e-5
# The projection's certificate, the scores and the objective hold of WHATEVER
# latent factors C the fit returns (with C left at 0 the refit takes P to 0
# and the model is the three convex coordinates', sound by every limit
# above), so two numbers hold the latent half, `jit_re_bucket_solve` of
# perUserMF. My chip runs, PR 35: calls 8 and 9 (faults planted in the program
# at the real size, four seeds) and twenty-two sound runs on thirteen seeds;
# in brackets the CPU at the rehearsal's size (two seeds):
#: the factored coordinate's FIRST update moves the objective by at most
#: this, relative: a fall of 5.5e-3 to 6.4e-3 (6.6e-3 and 8.2e-3). With every latent result dropped it falls by
#: 3.8e-7 (5.1e-5), the share of the penalty on the warm-start P; with the
#: latent solves on every other cell it RISES by 5.1e-3 to 5.3e-3 (4.1e-3).
FIRST_VISIT = -2e-3
#: The factors the fit returns are the optimum of the LAST latent solves: of
#: each user's subproblem on its kept rows at its weight, under the final
#: offsets, with the features projected through the P those solves ran under,
#: which is the P of the same fit stopped one outer iteration short
#: (`_sweeps_before_last`). Over CERTIFIED_USERS sampled users, certified in
#: float64 by `reference_factored.latent_certificate`: the sum of f_e(c_e)
#: less a proven lower bound of its least value, over the sum of f_e(c_e), is
#: at most LATENT_GAP. Float32 L-BFGS under the upstream's rule reads 1.6e-8
#: to 3.3e-8 (1.9e-9 and 2.1e-9); the last visit's latent
#: results dropped read 7.2e-3 and 8.8e-3 (1.4e-2, 1.8e-2), solves on every
#: other cell 1.5e-2 (1.6e-2, 1.9e-2).
LATENT_GAP = 1e-5
#: And the MEDIAN user's own share is at most this. The sum above is carried
#: by the few lanes whose line search fails at the float32 floor; the median
#: user reads 3.1e-10 to 7.9e-10 (4.5e-11, 4.7e-11), and the blocks
#: projected at a TPU's default precision, `project_blocks`' einsum with
#: bfloat16 operands, read 1.4e-7 to 1.6e-7 in three runs (2.4e-7 and 2.5e-7
#: with the operands rounded so on the CPU), where the sum reads 1.7e-7 to
#: 2.0e-7, eight times a sound fit's and too close to set a limit between.
LATENT_MEDIAN_GAP = 1e-8

class GameFitMF(GameFitUserItem):
    def __init__(self, config, seed, chips):
        from photon_ml_tpu.game import (FactoredRandomEffectCoordinateConfig,
                                        GLMOptimizationConfig)
        from photon_ml_tpu.optim import (OptimizerConfig,
                                         RegularizationContext,
                                         RegularizationType)
        super().__init__(config, seed, chips)
        p = config["params"]
        assert config["latent_dim"] == p["mf_latent_dim"]
        l2 = RegularizationContext(RegularizationType.L2)
        self.l2[MF], self.l2_projection = p["l2_mf"], p["l2_mf_projection"]
        entity, shard = self.SHARDS["perUser"]

        def opt(weight):
            return GLMOptimizationConfig(
                optimizer=OptimizerConfig(
                    max_iterations=p["mf_max_iterations"]),
                regularization=l2, regularization_weight=weight)

        coordinates = dict(self.cfg.coordinates)
        coordinates[MF] = FactoredRandomEffectCoordinateConfig(
            entity, shard, latent_dim=p["mf_latent_dim"],
            num_inner_iterations=p["mf_inner_iterations"],
            optimization=opt(self.l2[MF]),
            latent_optimization=opt(self.l2_projection),
            active_data_upper_bound=p["mf_active_data_upper_bound"])
        self.cfg = dataclasses.replace(
            self.cfg, coordinates=coordinates,
            updating_sequence=list(self.cfg.updating_sequence) + [MF])
        assert self.cfg.num_outer_iterations >= 2
        self._short = None
        self.info.update(latent_dim=p["mf_latent_dim"],
                         per_user_width=config["per_user_width"],
                         itemsize=4)

    @staticmethod
    def _arrays(model):
        out = []
        for m in model.coordinates.values():
            if hasattr(m, "glm"):
                out.append(m.glm.coefficients.means)
            elif hasattr(m, "latent_coefficients"):
                out += [m.latent_coefficients, m.projection]
            else:
                out.append(m.coefficients)
        return out

    def record(self, result) -> dict:
        """`game_fit.py`'s record, and the factored coordinate's passes by
        visit and by half, as `solver_diagnostics()` has them: `latent` the
        per-user solves in the latent space (the max over the lanes),
        `projection` the refit of the shared projection. A commit that does
        not keep the halves apart leaves `sum` alone."""
        out = super().record(result)
        mine = result.descent.solver_diagnostics().get(MF, {})
        out["mf_passes"] = {
            "sum": mine.get("data_passes"),
            "latent": mine.get("latent_data_passes"),
            "projection": mine.get("projection_data_passes"),
            "reasons": mine.get("reasons")}
        return out

    def _sweeps_before_last(self):
        """(P, objective history) of the same fit stopped one outer
        iteration short, through the same `GameEstimator` on the same data:
        the projection the LAST fit's last latent solves ran under, which
        the model it returns no longer holds (the refit that follows them
        replaced it). A fit is a pure function of its configuration and
        data (`fits_agree` holds that for the window's fits, `replay_agrees`
        for this one), and nothing in the descent reads the number of outer
        iterations. Made once a fit, after the window; compiles nothing."""
        if self._short is None or self._short[0] is not self.last:
            from photon_ml_tpu.game import GameEstimator
            from photon_ml_tpu.parallel import mesh_residency
            mesh_residency.clear()      # as `fit` does: PERF.md, PR 24
            cfg = dataclasses.replace(
                self.cfg,
                num_outer_iterations=self.cfg.num_outer_iterations - 1)
            result = GameEstimator(cfg, mesh=self.mesh).fit(
                self.train, validation_dataset=self.val,
                evaluator_specs=["AUC"])
            self._short = (self.last, np.asarray(
                result.descent.model.coordinates[MF].projection, np.float64),
                [float(v) for v in result.objective_history])
        return self._short[1:]

    def _returned_factored(self):
        """(C, P, lanes) of the factored coordinate of the model the last
        fit returned, in float64; a row's lane is the row of C of its user,
        counted here from the model's entity order."""
        entity, _ = self.SHARDS["perUser"]
        model = self.last.descent.model.coordinates[MF]
        red = self._blocks_of(MF)
        assert not len(red.discarded_rows)
        lane_of = np.full(len(self.train.entity_vocabs[entity]), -1)
        lane_of[red.entity_ids] = np.arange(red.num_entities)
        return (np.asarray(model.latent_coefficients, np.float64),
                np.asarray(model.projection, np.float64),
                lane_of[self.train.entity_indices[entity]])

    def _active_cells(self):
        """(rows, lanes, weights, weights_gap) of the cells the factored
        coordinate trains on: the rows the program's reservoir kept, each at
        the weight counted HERE (a user with more training rows than the cap
        has count / cap on each kept row, any other 1), and the largest
        relative distance from them of the weights the program's two halves
        read: the buckets' block weights (the latent solves) and the flat
        vector `flat_active_weights` (the projection's refit), which has to
        be 0 on every other row."""
        entity, _ = self.SHARDS["perUser"]
        red = self._blocks_of(MF)
        counts = np.bincount(self.train.entity_indices[entity])
        cap = self.cfg.coordinates[MF].active_data_upper_bound
        per_lane = np.maximum(counts[red.entity_ids] / cap, 1.0)
        rows, lanes, gap = [], [], 0.0
        for bucket in red.buckets:
            lane, slot = np.nonzero(bucket.row_ids >= 0)
            rows.append(bucket.row_ids[lane, slot])
            lanes.append(bucket.lane_start + lane)
            program = np.asarray(bucket.blocks.weights, np.float64)[lane, slot]
            gap = max(gap, float(np.abs(
                program / per_lane[lanes[-1]] - 1.0).max()))
        rows, lanes = np.concatenate(rows), np.concatenate(lanes)
        flat = np.array(red.flat_active_weights(self.train), np.float64)
        gap = max(gap, float(np.abs(flat[rows] / per_lane[lanes] - 1.0).max()))
        flat[rows] = 0.0
        if flat.any():
            gap = float("inf")
        return rows, lanes, per_lane[lanes], gap

    def _sampled_users(self, per_lane_cells):
        """[(lane, training rows, their weight)] of a seeded sample of
        CERTIFIED_USERS users from every bucket, out of `_active_cells`'s
        (rows, lanes, weights)."""
        rows, lanes, weights = per_lane_cells
        red = self._blocks_of(MF)
        by_lane = np.argsort(lanes, kind="stable")
        cuts = np.searchsorted(lanes[by_lane],
                               np.arange(red.num_entities + 1))
        rng = np.random.default_rng(self.seed)
        out = []
        for b, picked in sample_lanes(
                [bk.num_entities for bk in red.buckets], CERTIFIED_USERS,
                rng):
            for lane in red.buckets[b].lane_start + picked:
                mine = by_lane[cuts[lane]:cuts[lane + 1]]
                out.append((int(lane), rows[mine], float(weights[mine[0]])))
        return out

    def _mf_term(self, factored, operands=None):
        """x_user_i . (C P)[lane_i] of every row, float64 (or as the
        lower-precision control computes it)."""
        _, shard = self.SHARDS["perUser"]
        factors, projection, lanes = factored
        return reference_factored.factored_margins(
            np.zeros((self.train_rows, 1), np.float32), np.zeros(1), [],
            (self.train.feature_shards[shard], lanes, factors, projection),
            operands=operands)

    def _objective(self, margins, w, tables, lanes, factored, **kwargs):
        factors, projection, _ = factored
        return reference_factored.factored_objective(
            margins, self.train.response, w, self._tables(tables, lanes),
            self.l2["fixed"], factors, projection, self.l2[MF],
            self.l2_projection, **kwargs)

    def lower_precision_control(self):
        """(model, scores, objective) as the reference computes them in the
        precision below the configuration's: the returned model's margins
        with bfloat16 operands and float32 sums in every dot product, the
        product C P among them, and the objective summed from them in
        float32. `check(records, control=...)` has to refuse it."""
        returned = self._returned()
        factored = self._returned_factored()
        w, tables, lanes = returned
        low = reference_game.bfloat16
        scores = (reference_game.game_margins(
            self.train.feature_shards["global"], w,
            self._tables(tables, lanes), operands=low).astype(np.float32)
            + self._mf_term(factored, operands=low).astype(np.float32))
        response = np.asarray(self.train.response, np.float32)
        arrays = [(w, self.l2["fixed"]), (factored[0], self.l2[MF]),
                  (factored[1], self.l2_projection)] + [
                      (tables[n], self.l2[n]) for n in self.SHARDS]
        objective = float(
            reference.logloss(scores, response).sum(dtype=np.float32)
            + sum(np.float32(0.5 * l2) * (low(t) * low(t)).sum(
                dtype=np.float32) for t, l2 in arrays))
        return (returned, factored), scores.astype(np.float64), objective

    def unpenalised_projection_control(self):
        """(model, scores, objective) of a report that left the projection's
        penalty out of the objective: the returned model, the program's own
        scores, and the float64 objective without l2 / 2 |P|^2."""
        returned = self._returned()
        factored = self._returned_factored()
        w, tables, lanes = returned
        scores = np.asarray(self.last.descent.model.score_dataset(
            self.train), np.float64)
        margins = (reference_game.game_margins(
            self.train.feature_shards["global"], w,
            self._tables(tables, lanes)) + self._mf_term(factored))
        return (returned, factored), scores, self._objective(
            margins, w, tables, lanes, factored, penalise_projection=False)

    def check(self, records, control=None) -> dict:
        """`correct`, all on the model the last fit of the window returned
        (or on `control`). The limits and their reasons are at the top of
        this file and of `game_fit_user_item.py`.

        - the program's scores of all training rows under that model are
          its float64 margins over all FOUR coordinates (SCORES), and the
          objective reported last is `reference_factored.factored_objective`
          of it, the penalties on C and on P among its terms (OBJECTIVE);
        - no update of the fixed effect raises the objective (1e-6); no
          update of a random effect, the factored one included, raises it by
          more than RISE; every fit of the window gave the same history
          (1e-6) AND the same pass counts in both halves of every visit of
          the factored coordinate (one program, one dataset, one machine);
        - the projection is the last thing the fit solved: on the kept rows
          at the weights counted here, under the final C and the final offsets (the float64
          margins of the three convex coordinates), the certified gap of its
          refit is within PROJECTION_GAP;
        - the latent half: the coordinate's first update lowers the
          objective by FIRST_VISIT at the least, and the factors the fit
          returns are the optimum of the last latent solves, on the same
          rows and weights (the buckets' block weights and the refit's flat
          weights both have to equal them), under the final offsets and the
          projection of the same fit stopped one outer iteration short,
          whose history has to be this fit's as far as it goes (1e-6):
          LATENT_GAP over the sampled users, LATENT_MEDIAN_GAP for the
          median one;
        - every coefficient is finite."""
        if self.last is None:
            return {"ok": False, "why": "the last fit of the window failed"}
        t0 = time.perf_counter()
        last = records[-1]["objective_history"]
        if control is None:
            w, tables, lanes = self._returned()
            factored = self._returned_factored()
            scores = np.asarray(self.last.descent.model.score_dataset(
                self.train), np.float64)
            reported = last[-1]
        else:
            ((w, tables, lanes), factored), scores, reported = control
        factors, projection, _ = factored
        convex = reference_game.game_margins(
            self.train.feature_shards["global"], w,
            self._tables(tables, lanes))
        margins = convex + self._mf_term(factored)
        ours = self._objective(margins, w, tables, lanes, factored)
        t1 = time.perf_counter()
        rows, cell_lanes, weights, weights_gap = self._active_cells()
        _, shard = self.SHARDS["perUser"]
        certificate = reference_factored.projection_certificate(
            self.train.feature_shards[shard], self.train.response, rows,
            cell_lanes, weights, convex, factors, projection,
            self.l2_projection, PROJECTION_GAP)
        projection_before, replayed = self._sweeps_before_last()
        latent = reference_factored.latent_certificate(
            self.train.feature_shards[shard], self.train.response,
            self._sampled_users((rows, cell_lanes, weights)), convex,
            projection_before, self.l2[MF], factors)
        sequence = self.cfg.updating_sequence
        steps = [(sequence[k % len(sequence)], a, b) for k, (a, b) in
                 enumerate(zip([self.train_rows * np.log(2.0)] + last, last))]
        passes = [r["mf_passes"] for r in records]
        out = {
            "seconds": time.perf_counter() - t0,
            "objective_seconds": t1 - t0,
            "scores_gap": float((np.abs(scores - margins)
                                 / np.maximum(np.abs(margins), 1.0)).max()),
            "objective_float64": ours, "objective_reported": reported,
            "objective_rel_gap": abs(ours - reported) / abs(ours),
            "projection_penalty_share": 0.5 * self.l2_projection * float(
                (projection * projection).sum()) / abs(ours),
            "largest_rise": max((b - a) / abs(a) for name, a, b in steps
                                if name != "fixed"),
            "mf_rises": [(b - a) / abs(a) for name, a, b in steps
                         if name == MF],
            "certificate": certificate, "weights_gap": weights_gap,
            "latent_certificate": latent,
            "mf_passes": passes[-1],
            "fixed_steps_not_rising": all(
                b <= a + 1e-6 * abs(a) for name, a, b in steps
                if name == "fixed"),
            "fits_agree": all(reference.same_to(
                r["objective_history"], records[0]["objective_history"],
                1e-6) for r in records),
            "passes_agree": all(p == passes[0] for p in passes),
            "replay_agrees": reference.same_to(
                replayed, last[:len(replayed)], 1e-6),
            "weights_rescaled": bool(weights_gap <= 1e-6),
            "projection_at_optimum": certificate.pop("ok"),
            "finite": bool(np.isfinite(w).all() and np.isfinite(
                factors).all() and np.isfinite(projection).all() and all(
                    np.isfinite(t).all() for t in tables.values())),
        }
        out["scores_match"] = bool(out["scores_gap"] <= SCORES)
        out["objective_matches"] = bool(out["objective_rel_gap"] <= OBJECTIVE)
        out["rises_bounded"] = bool(out["largest_rise"] <= RISE)
        out["first_visit_lowers"] = bool(out["mf_rises"][0] <= FIRST_VISIT)
        out["latent_at_optimum"] = bool(latent["gap"] <= LATENT_GAP)
        out["median_user_at_optimum"] = bool(
            latent["median_gap"] <= LATENT_MEDIAN_GAP)
        out["ok"] = all(v for v in out.values() if isinstance(v, bool))
        return out


def build(config, seed, chips):
    return GameFitMF(config, seed, chips)
