"""The full GAME fit with a factored random effect (fixed + per-user +
per-item + a per-user latent-factor coordinate) against the plain float64
reference `benchmark/reference_factored.py`; the benchmark cell's `check`
against three controls it has to refuse; the reducers and counters the cell's
new per-layer metrics read; the spans of the factored coordinate's update in
a traced fit; and that a repeat fit compiles nothing.
"""
import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from benchmark import (coordinate_reduce_fe, costs, costs_kron, reference,
                       reference_factored, trace_reduce)
from benchmark.builders import game_fit_mf as builder
from benchmark.run import load_module
from photon_ml_tpu import telemetry
from photon_ml_tpu.game import (
    FactoredRandomEffectCoordinateConfig, GLMOptimizationConfig,
)
from photon_ml_tpu.game import coordinates as coordinates_module
from photon_ml_tpu.optim import OptimizerConfig
from photon_ml_tpu.parallel import factored
from photon_ml_tpu.parallel.factored import project_blocks
from tests.test_benchmark_user_item import (L2, WEIGHTS, active_sets,
                                            blocks_of, config, fit, ratings)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MF = builder.MF
L2_FACTORS, L2_PROJECTION = 1.0, 1.0
#: The program's C P and objective against the reference's, relative. Both
#: halves of the alternation are strictly convex under an L2 weight of 1, so
#: each has one optimum and the two solvers have to meet there; the
#: program's L-BFGS stops when f's relative change falls under 1e-13, which
#: leaves it sqrt(1e-13 f / l2) ~ 1e-5 from the optimum in the flattest
#: direction, and the second half starts from the first one's result.
ALTERNATION = 1e-4


def full_config(cap=60, tolerance=1e-13):
    """The three-coordinate configuration of `test_benchmark_user_item` and
    a factored per-user coordinate of rank 2 whose cap binds for most of
    the 40 users, visited last."""
    base = config("capped", tolerance=tolerance)

    def opt(weight):
        return GLMOptimizationConfig(
            optimizer=OptimizerConfig(max_iterations=500,
                                      tolerance=tolerance),
            regularization=L2, regularization_weight=weight)

    coordinates = dict(base.coordinates)
    coordinates[MF] = FactoredRandomEffectCoordinateConfig(
        "userId", "per_user", latent_dim=2, num_inner_iterations=1,
        optimization=opt(L2_FACTORS), latent_optimization=opt(L2_PROJECTION),
        active_data_upper_bound=cap)
    return dataclasses.replace(
        base, coordinates=coordinates,
        updating_sequence=list(base.updating_sequence) + [MF])


def test_each_factored_update_equals_the_plain_alternation(monkeypatch):
    """(a): after each update of the factored coordinate in a fit of two
    outer iterations, the program's product C P and the objective it
    reports equal what the reference's alternation gives from the same P0
    (the descent's warm start included), the same factors and the same
    offsets, with the Kronecker rows materialised."""
    ds, cfg = ratings(np.float64), full_config()
    latest, visits = {}, []

    def recording(cls):
        update = cls.update

        def wrapped(self, model, offsets, **kwargs):
            out = update(self, model, offsets, **kwargs)
            latest[self.name] = out[0]
            if self.name == MF:
                visits.append((model, np.asarray(offsets, np.float64),
                               out[0], dict(latest)))
            return out
        monkeypatch.setattr(cls, "update", wrapped)

    for cls in (coordinates_module.FixedEffectCoordinate,
                coordinates_module.RandomEffectCoordinate,
                coordinates_module.FactoredRandomEffectCoordinate):
        recording(cls)
    result = fit(ds, cfg)
    assert len(visits) == cfg.num_outer_iterations

    red = blocks_of(ds, cfg, MF)
    rows, lanes, weights = active_sets(red)
    assert result.coordinate_build[MF]["capped_entities"] >= 20
    x, y = ds.feature_shards["per_user"], ds.response
    row_lanes = red.flat_entity_lanes(ds.entity_indices["userId"])
    for visit, (before, offsets, after, models) in enumerate(visits):
        c, p = reference_factored.alternate(
            x, y, rows, lanes, weights, offsets, before.latent_coefficients,
            before.projection, L2_FACTORS, L2_PROJECTION)
        assert reference.same_to(after.global_coefficients(), c @ p,
                                 ALTERNATION), visit
        # both halves moved: the factors from zero or from the last visit,
        # the projection from its start
        assert not reference.same_to(after.projection, before.projection,
                                     1e-3)
        margins = offsets + np.einsum("nd,nd->n", x, (c @ p)[row_lanes])
        penalties = (
            0.5 * WEIGHTS["fixed"] * float(np.sum(np.square(
                models["fixed"].glm.coefficients.means)))
            + sum(0.5 * WEIGHTS[name] * float(np.sum(np.square(
                models[name].global_coefficients())))
                  for name in ("perUser", "perItem"))
            + 0.5 * L2_FACTORS * float((c * c).sum())
            + 0.5 * L2_PROJECTION * float((p * p).sum()))
        ours = float(reference.logloss(margins, np.asarray(
            y, np.float64)).sum()) + penalties
        reported = result.objective_history[4 * visit + 3]
        assert abs(ours - reported) <= ALTERNATION * ours, visit
    # what the halves cost is kept apart, and adds up to what was reported
    mine = result.descent.solver_diagnostics()[MF]
    assert len(mine["projection_data_passes"]) == len(visits)
    assert [a + b for a, b in zip(mine["latent_data_passes"],
                                  mine["projection_data_passes"])] == \
        mine["data_passes"]
    assert "latent_data_passes" not in \
        result.descent.solver_diagnostics()["perUser"]


def test_materialised_kronecker_rows_are_the_refits_design(rng, monkeypatch):
    """The reference's design row is kron(c, x): its product with vec(P) is
    c . P x, and `projection_pass` in blocks equals a plain evaluation."""
    n, k, d, e = 700, 3, 5, 9
    x, c = rng.normal(size=(n, d)), rng.normal(size=(e, k))
    p = rng.normal(size=(k, d))
    lanes = rng.integers(0, e, n)
    rows = rng.permutation(n)[:500]
    y = (rng.random(n) < 0.5).astype(np.float64)
    weights, offsets = rng.uniform(0.5, 2.0, 500), rng.normal(size=n)
    design = reference_factored.kron_rows(c[lanes[rows]], x[rows])
    assert design.shape == (500, k * d)
    z = np.einsum("nk,kd,nd->n", c[lanes[rows]], p, x[rows])
    assert np.allclose(design @ p.reshape(-1), z)
    monkeypatch.setattr(reference_factored, "KRON_BLOCK", 128)  # 4 blocks
    f, g, h = reference_factored.projection_pass(
        x, y, rows, lanes[rows], weights, offsets, c, p, 2.0, hessian=True)
    zo = z + offsets[rows]
    plain = weights @ (np.log1p(np.exp(zo)) - y[rows] * zo) + p.ravel() @ \
        p.ravel()
    assert abs(f - plain) <= 1e-10 * abs(plain)
    eps = 1e-6
    bump = np.zeros_like(p)
    bump[1, 2] = eps
    f2, g2 = reference_factored.projection_pass(
        x, y, rows, lanes[rows], weights, offsets, c, p + bump, 2.0)
    assert abs((f2 - f) / eps - g[1, 2]) <= 1e-4 * max(1.0, abs(g[1, 2]))
    assert np.allclose((g2 - g).reshape(-1) / eps, h[:, 1 * d + 2],
                       rtol=1e-3, atol=1e-3)
    # the certificate: loose at P by the direct bound, tight after a step
    got = reference_factored.projection_certificate(
        x, y, rows, lanes[rows], weights, offsets, c, p, 2.0, 1e-9)
    assert not got["ok"] and got["newton_steps"] == 3
    best = reference_factored.solve_projection(
        x, y, rows, lanes[rows], weights, offsets, c, 2.0, p)
    at_best = reference_factored.projection_certificate(
        x, y, rows, lanes[rows], weights, offsets, c, best, 2.0, 1e-9)
    assert at_best["ok"] and at_best["gap"] <= 1e-9
    assert got["f_star_lower"] <= at_best["f"] <= got["f"]


@pytest.fixture(scope="module")
def small_cell():
    """The cell at its rehearsal's size, float32 as on the chip, with its
    first fit made (every program compiled) and recorded."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "game-ml20m-mf.json")) as f:
        small = json.load(f)
    small.update(small["rehearsal"])
    with jax.enable_x64(False):
        built = builder.build(small, 11, 1)
        record = built.record(built.fit())
    return built, record


def failed(check):
    return {k for k, v in check.items() if v is False}


def test_check_accepts_a_sound_fit_and_refuses_the_controls(small_cell):
    """(b): `check` accepts the fit, and refuses the reference's margins
    with bfloat16 operands (by the scores alone) and an objective reported
    without the projection's penalty (by the objective alone)."""
    built, record = small_cell
    with jax.enable_x64(False):
        check = built.check([record])
        low = built.check([record], control=built.lower_precision_control())
        bare = built.check(
            [record], control=built.unpenalised_projection_control())
    assert check["ok"], check
    assert check["scores_gap"] < builder.SCORES / 10
    assert check["certificate"]["gap"] < builder.PROJECTION_GAP / 10
    assert check["latent_certificate"]["gap"] < builder.LATENT_GAP / 10
    assert check["latent_certificate"]["median_gap"] < \
        builder.LATENT_MEDIAN_GAP / 10
    assert check["latent_certificate"]["entities"] == 400
    assert check["mf_rises"][0] < 2 * builder.FIRST_VISIT
    passes = check["mf_passes"]
    # the latent half's passes are summed over its runs, one a bucket (at
    # most four) since PR 37
    assert all(2 <= p <= 52 for p in passes["projection"])
    assert all(2 <= p <= 4 * 52 for p in passes["latent"])
    assert passes["sum"] == [a + b for a, b in zip(passes["latent"],
                                                   passes["projection"])]
    assert check["weights_gap"] == 0.0
    assert failed(low) == {"ok", "scores_match"}, low
    assert low["scores_gap"] > 10 * builder.SCORES
    assert failed(bare) == {"ok", "objective_matches"}, bare
    assert bare["objective_rel_gap"] == pytest.approx(
        check["projection_penalty_share"], rel=1e-2)


def test_check_refuses_a_projection_left_at_its_warm_start(small_cell,
                                                           monkeypatch):
    """(b), the fault planted in the PROGRAM: the refit runs and its result
    is dropped, so the factors are fitted to the warm start and the
    projection the fit returns is no optimum of anything."""
    built, _ = small_cell
    refit = factored.refit_latent_projection

    def left(blocks, factors, projection, *args, **kwargs):
        return projection, refit(blocks, factors, projection, *args,
                                 **kwargs)[1]
    monkeypatch.setattr(factored, "refit_latent_projection", left)
    with jax.enable_x64(False):
        check = built.check([built.record(built.fit())])
    assert failed(check) == {"ok", "projection_at_optimum"}, check
    assert check["certificate"]["gap"] > 10 * builder.PROJECTION_GAP


def _latent_results_dropped(visits):
    """`fit_random_effects` as the factored update calls it, four S-buckets
    a visit and two visits a fit, with the result of each call of `visits`
    left where it started. (The check's replay of the first sweep follows a
    whole fit, so its visit counts as the first again.)"""
    real, calls = factored.fit_random_effects, []

    def dropped(blocks, *args, **kwargs):
        result = real(blocks, *args, **kwargs)
        calls.append(None)
        if (len(calls) - 1) // 4 % 2 in visits:
            result = result._replace(x=kwargs["x0"])
        return result
    return dropped


def _every_other_cell(blocks, projection):
    out = project_blocks(blocks, projection)
    keep = (np.arange(out.x.shape[1]) % 2 == 0).astype(np.float32)
    return dataclasses.replace(out, weights=out.weights * keep[None, :])


def _bfloat16_operands(blocks, projection):
    """`project_blocks` as a TPU's default precision takes its einsum: the
    operands rounded to bfloat16, the sums in float32."""
    def low(a):
        return a.astype(jax.numpy.bfloat16).astype(a.dtype)
    return project_blocks(dataclasses.replace(blocks, x=low(blocks.x)),
                          low(projection))


@pytest.mark.parametrize("fault,refused", [
    ("every-visit", {"first_visit_lowers"}),
    ("last-visit", {"latent_at_optimum", "median_user_at_optimum"}),
    ("every-other-cell", {"first_visit_lowers", "latent_at_optimum",
                          "median_user_at_optimum"}),
    ("bfloat16-projection", {"median_user_at_optimum"})])
def test_check_refuses_a_fault_in_the_latent_half(small_cell, monkeypatch,
                                                  fault, refused):
    """(b), faults planted in the PROGRAM's latent half, which neither the
    scores nor the objective nor the projection's certificate can see (each
    holds of whatever C the fit returns). Every latent result dropped: C
    stays 0, the refit takes P to 0, the model is the three convex
    coordinates' and only the first visit's fall of the objective is
    missing. The last visit's dropped: C is the first visit's, no optimum
    under the last offsets. Solves on every other cell: both. The blocks
    projected with bfloat16 operands: every user a little off its optimum,
    which the median user's gap alone tells from float32."""
    built, _ = small_cell
    if fault == "every-other-cell":
        monkeypatch.setattr(factored, "project_blocks", _every_other_cell)
    elif fault == "bfloat16-projection":
        monkeypatch.setattr(factored, "project_blocks", _bfloat16_operands)
    else:
        monkeypatch.setattr(
            factored, "fit_random_effects", _latent_results_dropped(
                {0, 1} if fault == "every-visit" else {1}))
    with jax.enable_x64(False):
        check = built.check([built.record(built.fit())])
    assert failed(check) == refused | {"ok"}, check
    latent = check["latent_certificate"]
    if "first_visit_lowers" in refused:
        assert check["mf_rises"][0] > builder.FIRST_VISIT / 10
    if "latent_at_optimum" in refused:
        assert latent["gap"] > 10 * builder.LATENT_GAP
    if "median_user_at_optimum" in refused:
        assert latent["median_gap"] > 10 * builder.LATENT_MEDIAN_GAP


def test_a_repeat_fit_compiles_nothing_and_counts_what_it_solves_on(
        small_cell):
    """(e), and the `train.mf_build.*` gauges: what the factored update
    solves on, the buckets' cells and the shard's flat rows, counted from
    the build."""
    built, record = small_cell

    def counters():
        return {k: v for k, v in telemetry.snapshot()["metrics"][
            "counters"].items() if k.startswith("jax.")}
    with jax.enable_x64(False):
        before = counters()
        again = built.record(built.fit())
    assert counters() == before
    assert again["mf_passes"] == record["mf_passes"]
    assert reference.same_to(again["objective_history"],
                             record["objective_history"], 1e-6)
    stats = built.info["coordinates"][MF]
    mf, red = stats["mf_build"], built._blocks_of(MF)
    assert mf["entities"] == stats["entities"] == red.num_entities
    assert mf["samples"] == max(s for _, s, _ in stats["buckets"]) == 256
    assert mf["cells"] == stats["cells"] == sum(
        b.num_entities * b.samples_per_entity for b in red.buckets)
    assert mf["rows"] == built.train_rows
    assert mf["real_rows"] == stats["active_rows"]
    assert mf["padded_cells"] == (mf["cells"] - mf["real_rows"]) + (
        mf["rows"] - mf["real_rows"])
    assert mf["latent_dim"] == 8
    assert mf["device_bytes"] == 4 * (8 * mf["cells"] + 9 * mf["rows"])
    # the flat view of the blocks' weights: the same cells, where they lie
    flat = np.asarray(red.flat_active_weights(built.train))
    assert np.count_nonzero(flat) == mf["real_rows"]
    for bucket in red.buckets:
        lane, slot = np.nonzero(bucket.row_ids >= 0)
        assert np.array_equal(flat[bucket.row_ids[lane, slot]], np.asarray(
            bucket.blocks.weights)[lane, slot])
    gauges = telemetry.snapshot()["metrics"]["gauges"]
    for key, value in mf.items():
        assert gauges[f"train.mf_build.{MF}.{key}"] == value
    assert f"train.re_build.{MF}.mf_build" not in gauges
    assert gauges[f"train.re_build.{MF}.cells"] == stats["cells"]


def test_every_solve_of_the_factored_update_is_called_in_its_span(
        small_cell, tmp_path):
    """(d): in a traced fit every call of the one-lane solve program lies
    in an `fe/dispatch` span (the fixed effect's two and the projection
    refit's two), so `span_reduce.match_calls` pairs each run with its
    call; and every per-entity solve the factored coordinate calls lies in
    a `re/dispatch` span inside `{it}/perUserMF/solve`."""
    built, _ = small_cell
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.enable_x64(False):
        jax.profiler.start_trace(str(tmp_path), profiler_options=options)
        try:
            built.fit()
        finally:
            jax.profiler.stop_trace()
    host = trace_reduce.read_trace(str(tmp_path))["host"]

    def named(name):
        return sorted((s, e) for ev, s, e in host if ev == name)

    def inside(inner, outers):
        return sum(a <= inner[0] and inner[1] <= b for a, b in outers)

    def calls(function):
        """The jitted calls of `function`; the runtime writes a call as two
        nested events of one name, of which the outer is kept."""
        out = []
        for span in named(f"PjitFunction({function})"):
            if not out or span[0] >= out[-1][1]:
                out.append(span)
        return out

    fe_calls = calls("fe_solve")
    fe_spans = named("photon/fe/dispatch")
    assert len(fe_calls) == len(fe_spans) == 4
    assert all(inside(call, fe_spans) == 1 for call in fe_calls)
    mf_solves = [span for it in range(2)
                 for span in named(f"photon/{it}/{MF}/solve")]
    assert len(mf_solves) == 2
    assert sum(inside(call, mf_solves) for call in fe_calls) == 2
    assert sum(inside(span, mf_solves) for span in
               named("photon/fe/stage")) >= 2
    re_spans = named("photon/re/dispatch")
    mine = [call for call in calls("re_bucket_solve")
            if inside(call, mf_solves)]
    assert len(mine) == 2 * 4       # four S-buckets a visit
    assert all(inside(call, re_spans) == 1 for call in mine)
    for name in ("photon/re/x0", "photon/re/solve_call"):
        assert sum(inside(span, mf_solves) for span in named(name)) == 8, \
            name
    # the offsets of all four buckets: one gather, one span, a visit
    offsets = named("photon/re/offsets")
    assert sum(inside(span, mf_solves) for span in offsets) == 2
    gathers = [call for call in calls("_gather_flat_offsets")
               if inside(call, mf_solves)]
    assert len(gathers) == 2
    assert all(inside(call, offsets) == 1 for call in gathers)


def test_one_lane_solve_seconds_are_split_by_the_span_the_call_was_made_in():
    """(c): the fixed effect and the projection refit run the same program;
    a run belongs to the coordinate in whose solve span its call was MADE,
    and a refit with no call span leaves every run unplaced."""
    host = [("photon/0/fixed/solve", 0.0, 1.0),
            ("photon/fe/dispatch", 0.1, 0.2),
            ("photon/0/perUser/solve", 1.0, 2.0),
            ("photon/re/dispatch", 1.1, 1.2),
            ("photon/0/perUserMF/solve", 2.0, 3.0),
            ("photon/re/dispatch", 2.1, 2.2),
            ("photon/fe/stage", 2.3, 2.5), ("photon/fe/dispatch", 2.5, 2.6)]
    modules = [("jit_fe_solve(1)", 0.2, 1.4),
               ("jit_re_bucket_solve(2)", 1.4, 2.4),
               ("jit_re_bucket_solve(2)", 2.4, 3.0),
               ("jit_repeat(5)", 3.0, 3.1),
               ("jit_fe_solve(7)", 3.1, 4.0)]       # called at 2.5
    ops = [(0.2, 1.0), (1.2, 1.4), (1.4, 3.1), (3.1, 3.6), (3.8, 4.0)]
    got = coordinate_reduce_fe.split(ops, modules, host, 0.0, 5.0)
    assert got == pytest.approx({"fixed": 1.0, MF: 0.7})
    # the parent commit: the refit's call is made in no span of its own
    assert coordinate_reduce_fe.split(ops, modules, host[:-1], 0.0,
                                      5.0) is None
    # a call span outside every solve span places nothing
    assert coordinate_reduce_fe.split(
        ops, modules, host[:4] + host[5:], 0.0, 5.0) is None


def test_counter_metrics_read_the_builders_record_and_price_the_work():
    """(c): the three new readers that need no trace, on a hand-made
    record; the roofline's reader reads nothing without one; the price of a
    Kronecker pass counts real rows and entities, not cells."""
    stats = {"entities": 10, "samples": 8, "cells": 70, "rows": 90,
             "real_rows": 50, "padded_cells": 60, "latent_dim": 2,
             "device_bytes": 0}
    fits = [{"record": {"mf_passes": {"sum": [70, 71], "latent": [18, 19],
                                      "projection": [52, 52]}}}]
    record = {"built": {"coordinates": {"perUser": {"cells": 5},
                                        MF: {"cells": 60,
                                             "mf_build": stats}},
                        "per_user_width": 21, "itemsize": 4},
              "samples": {"fits": fits}, "trace": None,
              "peak": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}

    def read(name, rec=record):
        return load_module("layer_metrics", name).read(rec)
    assert read("mf_projection_passes.fit") == 104
    assert read("mf_padded_share.fit") == pytest.approx(37.5)
    assert read("mf_kron_roofline.fit") is None
    older = {"built": {"coordinates": {MF: {"cells": 60}}},
             "samples": {"fits": [{"record": {"mf_passes": {
                 "sum": [70, 71], "latent": None, "projection": None}}}]},
             "trace": None, "peak": record["peak"]}
    for name in ("mf_projection_passes.fit", "mf_padded_share.fit",
                 "mf_kron_roofline.fit"):
        assert read(name, older) is None
    nbytes = costs_kron.kron_value_grad_pass_bytes(50, 10, 21, 2, 4)
    assert nbytes == (50 * (21 + 3) + 10 * 2) * 4
    flops = costs_kron.kron_value_grad_pass_flops(50, 21, 2)
    assert flops == 4 * 50 * 2 * 21
    assert costs.roofline_seconds(nbytes, flops, record["peak"]) == \
        pytest.approx(nbytes / 819e9)


def test_cell_is_in_the_benchmark_with_its_five_metrics():
    """The entries are appended, the configuration states its cut, and each
    new metric lists the new cell alone."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    assert spec["configs"][-1]["name"] == "game-ml20m-mf"
    assert spec["configs"][-1]["reduced"] == ["rows", "users"]
    assert len(spec["configs"][-1]["source"]) <= 200
    cell = spec["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == \
        ("game-ml20m-mf.fit", "game-ml20m-mf", "fit-loop", 1)
    new = ["re_solve_device_s.perUserMF.fit", "fe_solve_device_s.perUserMF.fit",
           "mf_projection_passes.fit", "mf_padded_share.fit",
           "mf_kron_roofline.fit"]
    names = [m["name"] for m in spec["per_layer"]]
    mine = spec["per_layer"][names.index(new[0]):][:5]
    assert [m["name"] for m in mine] == new
    for entry in mine:
        assert entry["workloads"] == [cell["name"]]
        meta = load_module("layer_metrics", entry["name"]).META
        assert meta == {k: entry[k] for k in ("name", "unit", "layer",
                                              "moves")}
    listed = [m["name"] for m in spec["per_layer"]
              if "workloads" not in m or cell["name"] in m["workloads"]]
    assert len(listed) == 22      # and PR 37's three lock-step counts
    # the exchange and the buckets' padding are read here as in the
    # user-item cell: the factored update gathers offsets and solves by
    # S-bucket like any random effect
    assert {"exchange_device_s.fit", "re_padded_share.fit"} <= set(listed)
    with open(os.path.join(REPO, spec["configs"][-1]["file"])) as f:
        body = json.load(f)
    other = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "glmix-ml20m-user-item.json")))
    for key in ("rows", "users", "items", "global_width", "per_user_width",
                "per_item_width"):
        assert body[key] == other[key], key
    for key, value in other["params"].items():
        assert body["params"][key] == value, key
    assert body["latent_dim"] == body["published"]["latent_dim"] == 8
    assert body["rehearsal"] and body["assumed"] and body["guarantees"]
