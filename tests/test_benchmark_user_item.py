"""The three-coordinate GLMix fit (fixed + per-user + per-item) against the
plain float64 reference `benchmark/reference_game.py`, the counters of each
random-effect coordinate's build, the benchmark cell's rehearsal, the
configuration through `cli.train`, and the reduction that splits the
per-entity solve's device seconds by coordinate (on hand-made intervals).
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmark import coordinate_reduce, reference, reference_game
from benchmark.builders import game_fit_user_item as builder
from photon_ml_tpu import telemetry
from photon_ml_tpu.data import build_game_dataset
from photon_ml_tpu.data.game_data import save_game_dataset
from photon_ml_tpu.game import (
    FixedEffectCoordinateConfig, GameEstimator, GameTrainingConfig,
    GLMOptimizationConfig, RandomEffectCoordinateConfig,
)
from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
from photon_ml_tpu.models.io import load_game_model
from photon_ml_tpu.optim import (
    OptimizerConfig, RegularizationContext, RegularizationType,
)
from photon_ml_tpu.parallel import make_mesh
from tests.test_io_cli import _run_cli

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
L2 = RegularizationContext(RegularizationType.L2)
ENTITIES = {"perUser": ("userId", "per_user"), "perItem": ("itemId", "per_item")}
WEIGHTS = {"fixed": 1.0, "perUser": 2.0, "perItem": 0.5}
#: cap, passive lower bound: none; a cap that binds for a third of the items,
#: whose leftovers are passive above the bound and discarded at or below it
CAPS = {"free": (None, None), "capped": (100, 30)}


def ratings(dtype, n=3000, users=40, items=30, seed=5):
    """A small three-coordinate corpus with skewed item popularity."""
    rng = np.random.default_rng(seed)
    pop = rng.lognormal(0.0, 1.2, items)
    user = rng.integers(0, users, n)
    item = rng.choice(items, n, p=pop / pop.sum())
    item_feats = np.c_[rng.uniform(size=(items, 3)) < 0.4, np.ones(items)]
    user_feats = np.c_[rng.uniform(size=(users, 2)) < 0.5, np.ones(users)]
    xg = np.c_[item_feats[item, :-1], user_feats[user, :-1], np.ones(n)]
    xu, xi = item_feats[item], user_feats[user]
    z = (xg @ rng.normal(size=xg.shape[1])
         + np.einsum("nd,nd->n", xu, rng.normal(size=(users, 4))[user])
         + np.einsum("nd,nd->n", xi, 0.5 * rng.normal(size=(items, 3))[item]))
    y = rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-z))
    return build_game_dataset(
        y.astype(dtype), {"global": xg.astype(dtype),
                          "per_user": xu.astype(dtype),
                          "per_item": xi.astype(dtype)},
        entity_ids={"userId": user, "itemId": item})


def config(caps, tolerance=None, seed=3):
    cap, lower = CAPS[caps]

    def opt(name):
        optimizer = (OptimizerConfig() if tolerance is None else
                     OptimizerConfig(max_iterations=200, tolerance=tolerance))
        return GLMOptimizationConfig(optimizer=optimizer, regularization=L2,
                                     regularization_weight=WEIGHTS[name])

    coordinates = {"fixed": FixedEffectCoordinateConfig("global",
                                                        opt("fixed"))}
    for name, (entity, shard) in ENTITIES.items():
        coordinates[name] = RandomEffectCoordinateConfig(
            entity, shard, opt(name),
            active_data_upper_bound=cap if name == "perItem" else None,
            passive_data_lower_bound=lower if name == "perItem" else None)
    return GameTrainingConfig(
        task_type="logistic_regression", coordinates=coordinates,
        updating_sequence=["fixed", "perUser", "perItem"],
        num_outer_iterations=2, seed=seed)


def fit(ds, cfg):
    # the suite runs with x64 on; a float32 corpus is fitted as the chip
    # fits it, with x64 off
    with jax.enable_x64(bool(ds.feature_shards["global"].dtype
                             == np.float64)):
        return GameEstimator(
            cfg, mesh=make_mesh(devices=jax.devices()[:1])).fit(
                ds, validation_dataset=ds, evaluator_specs=["AUC"])


def blocks_of(ds, cfg, name):
    from photon_ml_tpu.data.batching import build_random_effect_dataset
    return build_random_effect_dataset(
        ds, cfg.coordinates[name].data_config(cfg.seed))


def active_sets(red):
    """(rows, lanes, weights) of the cells that hold a real row in the
    blocks of a `RandomEffectDataset`: the rows the program trains each
    entity on, and the weights it gives them (count / cap for an entity the
    reservoir cut)."""
    rows, lanes, weights = [], [], []
    for bucket in red.buckets:
        lane, slot = np.nonzero(bucket.row_ids >= 0)
        rows.append(bucket.row_ids[lane, slot])
        lanes.append(bucket.lane_start + lane)
        weights.append(np.asarray(bucket.blocks.weights,
                                  np.float64)[lane, slot])
    return (np.concatenate(rows), np.concatenate(lanes),
            np.concatenate(weights))


def reference_inputs(ds, cfg):
    """`reference_game.fit_game`'s `entities`, with the active row sets and
    weights the program's build chose."""
    entities = {}
    for name, (entity, shard) in ENTITIES.items():
        red = blocks_of(ds, cfg, name)
        rows, lanes, weights = active_sets(red)
        entities[name] = {
            "x": ds.feature_shards[shard],
            "lanes": red.flat_entity_lanes(ds.entity_indices[entity]),
            "active_rows": rows, "active_lanes": lanes,
            "active_weights": weights,
            "num_entities": red.num_entities, "l2": WEIGHTS[name]}
    return entities


@pytest.fixture(scope="module")
def float64_fit():
    """caps -> (corpus, configuration, result) of the float64 fit stopped at
    1e-12, each made once for the tests that read it."""
    made = {}

    def of(caps):
        if caps not in made:
            ds, cfg = ratings(np.float64), config(caps, tolerance=1e-12)
            made[caps] = (ds, cfg, fit(ds, cfg))
        return made[caps]
    return of


@pytest.mark.parametrize("caps", ["free", "capped"])
def test_float64_fit_equals_the_plain_reference(float64_fit, caps):
    """(a), (b): coefficients of all three coordinates and the objective
    history equal the reference's block coordinate descent to 1e-6, with no
    cap and with one that binds; under the binding cap passive rows are
    scored and discarded ones are not."""
    ds, cfg, result = float64_fit(caps)
    entities = reference_inputs(ds, cfg)
    want = reference_game.fit_game(
        ds.feature_shards["global"], ds.response, WEIGHTS["fixed"], entities,
        cfg.updating_sequence, cfg.num_outer_iterations)
    model = result.descent.model.coordinates
    assert reference.same_to(model["fixed"].glm.coefficients.means,
                             want["w"], 1e-6)
    for name in ENTITIES:
        assert reference.same_to(model[name].global_coefficients(),
                                 want["tables"][name], 1e-6), name
    assert reference.same_to(result.objective_history,
                             want["objective_history"], 1e-6)

    item = entities["perItem"]
    stats = result.coordinate_build["perItem"]
    trained = np.zeros(ds.num_rows, bool)
    trained[item["active_rows"]] = True
    scored = item["lanes"] >= 0
    if caps == "free":
        assert trained.all() and scored.all()
        assert all(b <= a * (1 + 1e-9) for a, b in zip(
            result.objective_history, result.objective_history[1:]))
        return
    capped = stats["capped_entities"]
    assert 0.25 * stats["entities"] <= capped <= 0.45 * stats["entities"]
    assert stats["passive_rows"] > 0 and stats["discarded_rows"] > 0
    assert (scored & ~trained).sum() == stats["passive_rows"]
    assert (~scored).sum() == stats["discarded_rows"]
    # the program gives a passive row its item's score and a discarded row 0
    coordinate_scores = np.asarray(
        model["perItem"].global_coefficients())[np.maximum(item["lanes"], 0)]
    margins = np.einsum("nd,nd->n", ds.feature_shards["per_item"],
                        coordinate_scores) * scored
    objective = reference_game.game_objective(
        ds.feature_shards["global"], model["fixed"].glm.coefficients.means,
        [(e["x"], e["lanes"], model[name].global_coefficients(), e["l2"])
         for name, e in entities.items()], ds.response, WEIGHTS["fixed"])
    assert abs(objective - result.objective_history[-1]) <= 1e-9 * objective
    assert np.abs(margins[scored & ~trained]).max() > 0
    assert not margins[~scored].any()


def small_cell():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "glmix-ml20m-user-item.json")) as f:
        small = json.load(f)
    # the cell at its rehearsal's size: the cap stays 512, on which the
    # rise that `check` bounds depends
    small.update(small["rehearsal"])
    return builder.build(small, 11, 1)


def drop_the_rescale(built, monkeypatch):
    """The fault in the PROGRAM: every capped item's rows at weight 1."""
    for bucket in built._blocks_of("perItem").buckets:
        bucket._blocks = dataclasses.replace(bucket._blocks,
                                             weights=bucket._blocks.mask)


def solve_under_other_offsets(built, monkeypatch):
    """The fault in the PROGRAM: perItem never sees the other scores."""
    update = RandomEffectCoordinate.update

    def faulty(self, model, offsets, **kwargs):
        return update(self, model,
                      offsets * 0 if self.name == "perItem" else offsets,
                      **kwargs)
    monkeypatch.setattr(RandomEffectCoordinate, "update", faulty)


def test_float32_check_certifies_every_item_and_refuses_the_control():
    """(c): after a float32 fit through the cell's builder, `check` finds
    every item within CERTIFICATE of the float64 optimum of its own
    subproblem, at weights it counts itself; the direct strong-convexity
    bound holds too and is the looser. The lower-precision control (the
    reference's margins, objective and per-item solves with bfloat16
    operands and float32 sums) is refused, by the scores' limit alone."""
    with jax.enable_x64(False):     # float32, as on the chip
        built = small_cell()
        result = built.fit()
        records = [built.record(result)]
        check = built.check(records)
        control = built.check(records,
                              control=built.lower_precision_control())
    assert result.descent.model.coordinates[
        "perItem"].coefficients.dtype == np.float32
    stats = built.info["coordinates"]["perItem"]
    assert stats["capped_entities"] >= 10
    assert check["ok"], check
    certificate = check["certificate"]
    assert certificate["items"] == stats["entities"]
    assert certificate["worst"] <= builder.CERTIFICATE
    assert certificate["worst"] <= certificate["direct_bound_worst"]
    assert certificate["weights_gap"] == 0.0
    assert 0 < check["largest_rise"] <= builder.RISE
    failed = {k for k, v in control.items() if v is False}
    assert failed == {"ok", "scores_match"}, control
    assert control["scores_gap"] > 10 * builder.SCORES
    assert check["scores_gap"] < builder.SCORES / 10


@pytest.mark.parametrize("fault, fails", [
    (drop_the_rescale, {"weights_rescaled", "items_at_optimum"}),
    (solve_under_other_offsets, {"rises_bounded", "items_at_optimum"})])
def test_check_refuses_a_fault_planted_in_the_program(fault, fails,
                                                      monkeypatch):
    """The test of the test: the fault is in the program, so the program's
    own blocks and lanes carry it, and `check` still refuses the fit."""
    with jax.enable_x64(False):
        built = small_cell()
        fault(built, monkeypatch)
        check = built.check([built.record(built.fit())])
    assert not check["ok"]
    assert {k for k, v in check.items() if v is False} == fails | {"ok"}
    assert check["certificate"]["worst"] > 2 * builder.CERTIFICATE


def run_cell(seed):
    child = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "glmix-ml20m-user-item.fit", "--seed", str(seed),
         "--seconds", "2", "--trace", "0", "--rehearse-cpu"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert child.returncode == 0, child.stderr[-2000:]
    lines = child.stdout.strip().splitlines()
    built = next(line for line in lines if line.startswith("set-up "))
    return json.loads(lines[-1]), json.loads(built.split("; built ", 1)[1])


def test_cell_rehearsal_is_correct_and_seeds_share_bucket_shapes():
    """(d): the cell's command, rehearsed on the CPU, ends in a line with
    `correct: true`, and two seeds report the same bucket shapes."""
    shapes = []
    for seed in (2147483659, 11):
        line, built = run_cell(seed)
        assert line["correct"] is True and line["failed"] == 0, line
        assert {"fit_examples_per_s", "setup_s"} <= set(line["metrics"])
        shapes.append({name: stats["buckets"] for name, stats in
                       built["coordinates"].items()})
    assert set(shapes[0]) == set(ENTITIES)
    assert shapes[0] == shapes[1]


def test_cli_train_gives_the_estimators_model(float64_fit, tmp_path):
    """(e): the three-coordinate configuration as a --config JSON through
    cli.train gives the model of the estimator call, and its summary carries
    the build counters."""
    ds, cfg, result = float64_fit("capped")
    want = result.descent.model.coordinates
    train_p, cfg_p = str(tmp_path / "train.npz"), str(tmp_path / "game.json")
    save_game_dataset(ds, train_p)
    with open(cfg_p, "w") as f:
        f.write(cfg.to_json())
    out_dir = str(tmp_path / "out")
    child = _run_cli("photon_ml_tpu.cli.train",
                     ["--train-data", train_p, "--config", cfg_p, "--x64",
                      "--output-dir", out_dir])
    assert child.returncode == 0, child.stderr[-2000:]
    model, cfg_back = load_game_model(os.path.join(out_dir, "best"))
    assert cfg_back == cfg
    assert reference.same_to(model.coordinates["fixed"].glm.coefficients.means,
                             want["fixed"].glm.coefficients.means, 1e-6)
    for name in ENTITIES:
        assert reference.same_to(
            model.coordinates[name].global_coefficients(),
            want[name].global_coefficients(), 1e-6), name
    with open(os.path.join(out_dir, "training-summary.json")) as f:
        summary = json.load(f)
    # with the coordinate's own count: on the CPU no gather runs the kernel
    assert summary["coordinate_build"]["perItem"] == dict(
        blocks_of(ds, cfg, "perItem").build_counts, vmem_offsets=0)
    gauges = summary["telemetry"]["metrics"]["gauges"]
    for key in ("passive_rows", "vmem_offsets"):
        assert gauges[f"train.re_build.perItem.{key}"] == \
            summary["coordinate_build"]["perItem"][key]


def test_build_counters_equal_a_direct_count():
    """(f): the counters of a random-effect coordinate's build, in the
    fit's result and in telemetry.snapshot(), equal a plain NumPy count."""
    ds = ratings(np.float32)
    cfg = config("capped")
    cap, lower = CAPS["capped"]
    result = fit(ds, cfg)
    counts = np.bincount(ds.entity_indices["itemId"])
    counts = counts[counts > 0]
    leftover = counts - np.minimum(counts, cap)
    want = {"entities": len(counts),
            "active_rows": int(np.minimum(counts, cap).sum()),
            "passive_rows": int(leftover[leftover > lower].sum()),
            "discarded_rows": int(leftover[leftover <= lower].sum()),
            "capped_entities": int((counts > cap).sum())}
    got = result.coordinate_build["perItem"]
    assert {k: got[k] for k in want} == want
    assert sum(r for _, _, r in got["buckets"]) == want["active_rows"]
    assert sum(e for e, _, _ in got["buckets"]) == want["entities"]
    assert got["cells"] == sum(e * s for e, s, _ in got["buckets"])
    assert got["padded_cells"] == got["cells"] - want["active_rows"]
    users = result.coordinate_build["perUser"]
    assert users["passive_rows"] == users["capped_entities"] == 0
    assert users["active_rows"] == ds.num_rows
    gauges = telemetry.snapshot()["metrics"]["gauges"]
    for name, stats in result.coordinate_build.items():
        for key, value in stats.items():
            if key != "buckets":
                assert gauges[f"train.re_build.{name}.{key}"] == value
        for k, shape in enumerate(stats["buckets"]):
            assert [gauges[f"train.re_build.{name}.bucket{k}.{key}"]
                    for key in ("entities", "samples", "real_rows")] == shape


def test_solve_seconds_are_split_by_the_span_the_call_was_made_in():
    """Two coordinates run the same program; a run belongs to the coordinate
    in whose solve span its call was MADE, wherever the device runs it."""
    host = [("photon/0/fixed/solve", 0.0, 1.0), ("photon/fe/dispatch", 0.1, 0.2),
            ("photon/0/perUser/solve", 1.0, 2.0),
            ("photon/re/dispatch", 1.1, 1.2), ("photon/re/dispatch", 1.5, 1.6),
            ("photon/0/perItem/solve", 2.0, 3.0),
            ("photon/re/dispatch", 2.1, 2.2)]
    modules = [("jit_fe_solve(1)", 0.2, 1.4),
               ("jit_re_bucket_solve(2)", 1.4, 2.4),    # called at 1.1
               ("jit__gather_flat_offsets(3)", 2.4, 2.5),
               ("jit_re_bucket_solve(2)", 2.5, 3.5),    # called at 1.5
               ("jit_re_bucket_solve(4)", 3.5, 4.0)]    # called at 2.1
    ops = [(0.2, 1.4), (1.4, 2.0), (2.2, 2.4), (2.4, 4.0)]
    got = coordinate_reduce.split(ops, modules, host, 0.0, 5.0)
    assert got == pytest.approx({"perUser": 0.8 + 1.0, "perItem": 0.5})
    # a run whose call has no span, or whose call lies in no solve span
    assert coordinate_reduce.split(ops, modules, host[:-1], 0.0, 5.0) is None
    assert coordinate_reduce.split(
        ops, modules, host[:-2] + [host[-1]], 0.0, 5.0) is None
