"""The wide sparse fixed effect (`criteo-hashed-1m`) against the plain
float64 reference `benchmark/reference_sparse.py`, on both sides of
`CSC_MIN_COLS`: the model, the fixed work of a tolerance-0 fit, the pack that
runs once a dataset with its span and counters, the pass's price, the cell's
rehearsal with its planted faults, and the yardstick's own checks.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest
import scipy.sparse as sp

from benchmark import (
    costs, costs_sparse, reference, reference_sparse, selftest,
)
from benchmark.builders import sparse_fe_fit as builder
from benchmark.reference_game import bfloat16
from benchmark.run import load_json
from photon_ml_tpu import telemetry
from photon_ml_tpu.data import build_game_dataset
from photon_ml_tpu.game import (
    FixedEffectCoordinateConfig, GameEstimator, GameTrainingConfig,
    GLMOptimizationConfig,
)
from photon_ml_tpu.ops import features as fops
from photon_ml_tpu.optim import (
    OptimizerConfig, RegularizationContext, RegularizationType,
)
from photon_ml_tpu.parallel import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
L2 = 2.0
NNZ_A_ROW = 39
#: columns: under CSC_MIN_COLS the gradient is a scatter-add over the padded
#: rows, at or over it a segment sum over the column-sorted view
SIDES = {"scatter": 5_000, "csc": 120_000}
#: the limits the float64 fit is held to, and what they refuse
COEFFICIENTS = 1e-6      # of max |w|; a solve stopped at 1e-7 reads 1e-4
OBJECTIVE = 1e-10
SCORES = 1e-8            # float64 sums of 39 products; bfloat16 reads 4e-3
AUC = 1e-12


def clicks(columns, dtype, n=3000, seed=0):
    """(dataset, CSR matrix, labels): `n` rows of 39 draws from `columns`
    (a row that draws a column twice holds fewer non-zeros, so the padded
    rows have padding), labels from a planted truth."""
    rng = np.random.default_rng([seed, columns])
    popular = rng.random(columns) ** 3
    cols = rng.choice(columns, (n, NNZ_A_ROW), p=popular / popular.sum())
    x = sp.csr_matrix((np.ones(cols.size, dtype), cols.reshape(-1),
                       np.arange(0, cols.size + 1, NNZ_A_ROW)),
                      shape=(n, columns))
    x.sum_duplicates()
    z = x @ (0.4 * rng.standard_normal(columns)) - 1.0
    y = (rng.random(n) < reference.sigmoid(z)).astype(dtype)
    return build_game_dataset(y, {"global": x}), x, y


def config(max_iterations, tolerance, l2=L2):
    return GameTrainingConfig(
        task_type="logistic_regression",
        coordinates={"fixed": FixedEffectCoordinateConfig(
            "global", GLMOptimizationConfig(
                optimizer=OptimizerConfig(max_iterations=max_iterations,
                                          tolerance=tolerance),
                regularization=RegularizationContext(RegularizationType.L2),
                regularization_weight=l2))},
        updating_sequence=["fixed"], num_outer_iterations=1)


def fit(ds, cfg, x64=False):
    # the suite runs with x64 on; a float32 matrix is fitted as the chip
    # fits it, with x64 off
    with jax.enable_x64(x64):
        return GameEstimator(
            cfg, mesh=make_mesh(devices=jax.devices()[:1])).fit(
                ds, validation_dataset=ds, evaluator_specs=["AUC"])


def coefficients(result):
    return np.asarray(
        result.descent.model.coordinates["fixed"].glm.coefficients.means,
        np.float64)


@pytest.mark.parametrize("side", list(SIDES))
def test_float64_fit_equals_the_plain_reference(side):
    """Coefficients, objective, per-row scores and AUC of the estimator's
    fit are the float64 reference's, on either side of CSC_MIN_COLS; the
    same model rounded to bfloat16 fails the scores' limit."""
    ds, x, y = clicks(SIDES[side], np.float64)
    result = fit(ds, config(300, 1e-13), x64=True)
    stats = result.coordinate_build["fixed"]
    assert stats["csc"] == (SIDES[side] >= fops.CSC_MIN_COLS)
    x64 = reference_sparse.as_float64(x)
    want = reference_sparse.fit(x64, y, L2)
    got = coefficients(result)
    assert np.abs(got - want).max() <= COEFFICIENTS * np.abs(want).max()
    margins = reference_sparse.margins(x64, got)
    f = reference_sparse.objective_of(margins, y, got, L2)
    f_star = reference_sparse.value_and_gradient(x64, y, want, L2)[0]
    assert abs(f - f_star) <= OBJECTIVE * f_star
    assert abs(result.objective_history[-1] - f) <= OBJECTIVE * f
    with jax.enable_x64(True):
        scores = np.asarray(result.descent.model.score_dataset(ds))
    gap = np.abs(scores - margins) / np.maximum(np.abs(margins), 1.0)
    assert gap.max() <= SCORES
    assert abs(result.validation["AUC"]
               - reference_sparse.auc(margins, y)) <= AUC
    certificate = reference_sparse.certify(x64, y, got, L2, 1e-9)
    assert certificate["ok"] and certificate["newton_steps"] == 0
    low = reference_sparse.margins(x64, bfloat16(got))
    assert (np.abs(low - margins)
            / np.maximum(np.abs(margins), 1.0)).max() > 1e4 * SCORES


@pytest.mark.parametrize("side", list(SIDES))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_tolerance_zero_fixes_the_work_of_a_fit(side, seed):
    """With tolerance 0 a float32 fit that is still moving ends by
    MAX_ITERATIONS after exactly max_iterations + 2 data passes."""
    ds, _, _ = clicks(SIDES[side], np.float32, seed=seed)
    tracker = fit(ds, config(8, 0.0)).descent.trackers["0/fixed"]
    assert tracker.reasons == {"MAX_ITERATIONS": 1}
    assert tracker.iterations == 8 and tracker.data_passes == 10


@pytest.mark.parametrize("side", list(SIDES))
def test_the_pack_runs_once_a_dataset_and_is_counted(side, monkeypatch):
    """Two fits of one dataset: the first packs the sparse shard inside a
    `photon/fe/pack` span and sets the gauges; the second finds it in the
    dataset's shard cache (`pack_s` 0, no span). The counters equal what
    the reference counts from the CSR alone."""
    opened = []
    annotate = telemetry.annotate
    monkeypatch.setattr(telemetry, "annotate",
                        lambda name: opened.append(name) or annotate(name))
    ds, x, _ = clicks(SIDES[side], np.float32, seed=3)
    first, second = (fit(ds, config(3, 0.0)).coordinate_build["fixed"]
                     for _ in range(2))
    assert opened.count("fe/pack") == 1
    assert first["pack_s"] > 0 and second["pack_s"] == 0
    assert {k: v for k, v in first.items() if k != "pack_s"} == \
        {k: v for k, v in second.items() if k != "pack_s"}
    want = reference_sparse.build_counts(x)
    assert want["padded_slots"] > 0
    assert {k: first[k] for k in want} == want
    csc = SIDES[side] >= fops.CSC_MIN_COLS
    assert first["csc"] == csc
    # int32 index + float32 value a slot, and with the view a row id and a
    # value a non-zero and an end a column (+ 1)
    assert first["device_bytes"] == (
        8 * want["rows"] * want["ell_width"]
        + csc * (8 * want["nnz"] + 4 * (want["cols"] + 1)))
    gauges = telemetry.snapshot()["metrics"]["gauges"]
    assert {k: gauges[f"train.fe_build.fixed.{k}"] for k in first} == first


@pytest.mark.parametrize("host", ["held", "released"])
def test_a_cached_row_view_is_upgraded_not_packed_twice(host):
    """A shard that something else put on the device first (scoring does)
    has no column-sorted view: the coordinate's first materialisation packs
    it once more, counted as its pack, and the next fit finds it. Where the
    host matrix is held the view is the host's (the stored non-zeros, as if
    the coordinate had come first); only a released host shard has it
    sorted out of the rows read back, padding slots and all."""
    ds, x, _ = clicks(SIDES["csc"], np.float32, seed=4)
    assert not ds.device_shard(
        "global", release_host=host == "released").has_csc
    first, second = (fit(ds, config(3, 0.0)).coordinate_build["fixed"]
                     for _ in range(2))
    assert ds.device_shard("global").has_csc
    assert first["csc"] == second["csc"] == 1
    assert first["pack_s"] > 0 and second["pack_s"] == 0
    want = reference_sparse.build_counts(x)
    assert {k: first[k] for k in want} == want
    stream = (want["nnz"] if host == "held"
              else want["rows"] * want["ell_width"])
    assert first["device_bytes"] == (8 * want["rows"] * want["ell_width"]
                                     + 8 * stream + 4 * (want["cols"] + 1))


def test_a_sparse_pass_is_priced_by_its_work():
    """A hand-counted shape: 10 rows, 25 non-zeros, float32."""
    assert costs_sparse.sparse_value_grad_pass_bytes(10, 25, 4) == \
        25 * (4 + 4) + 10 * 3 * 4
    assert costs_sparse.sparse_value_grad_pass_flops(25) == 100
    peak = load_json(os.path.join(REPO, "benchmark", "peaks.json"))[
        "TPU v5 lite"]
    # the cell's pass: the bytes bound it, 1.13 ms
    rows, nnz = 2_850_000, 2_850_000 * 39
    nbytes = costs_sparse.sparse_value_grad_pass_bytes(rows, nnz, 4)
    assert nbytes == 923_400_000
    assert costs.roofline_seconds(
        nbytes, costs_sparse.sparse_value_grad_pass_flops(nnz),
        peak) == pytest.approx(nbytes / 819e9)


def rehearsal_cell(seed=5):
    cfg = load_json(os.path.join(REPO, "benchmark", "configs",
                                 "criteo-hashed-1m.json"))
    cfg.update(cfg["rehearsal"])
    return builder.build(cfg, seed, 1)


def bfloat16_scoring(built, monkeypatch):
    """The returned model scores with its coefficients rounded to bfloat16
    (planted after the fits: their own validation is untouched)."""
    import dataclasses
    from photon_ml_tpu.models.coefficients import Coefficients
    plain = Coefficients.compute_score
    monkeypatch.setattr(
        Coefficients, "compute_score", lambda self, x: plain(
            dataclasses.replace(self, means=self.means.astype(
                jax.numpy.bfloat16).astype(self.means.dtype)), x))


def cut_short(built, iterations):
    """The program runs `iterations` where the configuration states more."""
    import dataclasses
    fixed = built.cfg.coordinates["fixed"]
    optimizer = dataclasses.replace(fixed.optimization.optimizer,
                                    max_iterations=iterations)
    built.cfg = dataclasses.replace(built.cfg, coordinates={
        "fixed": dataclasses.replace(fixed, optimization=dataclasses.replace(
            fixed.optimization, optimizer=optimizer))})


@pytest.fixture(scope="module")
def sound_cell():
    """(the cell's builder at the rehearsal's size, the records of two fits
    of it), float32 as on the chip, made once."""
    with jax.enable_x64(False):
        built = rehearsal_cell()
        return built, [built.record(built.fit()) for _ in range(2)]


def failed(check):
    return {k for k, v in check.items() if v is False}


def test_check_accepts_a_sound_fit_and_refuses_the_control(sound_cell):
    """`check` accepts the fits and refuses the lower-precision control
    (the reference's own 30-iteration fit, its scores and its objective
    with bfloat16 operands and float32 sums) by the scores' limit; at the
    rehearsal's size the few held-out rows' AUC may fail as well."""
    built, records = sound_cell
    with jax.enable_x64(False):
        check = built.check(records)
        control = built.check(records,
                              control=built.lower_precision_control())
    assert check["ok"] and not failed(check), check
    assert check["passes"] == [built.max_iterations + 2] * 2
    # the first fit packed the shard, the second found it
    assert check["repacked_s"] == [built.info["fe_build"]["pack_s"], 0.0]
    assert built.info["fe_build"]["pack_s"] > 0
    assert check["scores_gap"] < builder.SCORES / 10
    assert check["certificate"]["rel_gap"] < builder.GAP / 2
    assert {"ok", "scores_match"} <= failed(control) <= {
        "ok", "scores_match", "auc_matches"}, control
    assert control["scores_gap"] > 10 * builder.SCORES


def test_check_refuses_a_model_that_scores_in_bfloat16(sound_cell,
                                                       monkeypatch):
    built, records = sound_cell
    bfloat16_scoring(built, monkeypatch)
    with jax.enable_x64(False):
        check = built.check(records)
    assert failed(check) == {"ok", "scores_match"}, check


@pytest.mark.parametrize("iterations,by", [
    (5, {"ok", "work_fixed"}),
    (3, {"ok", "work_fixed", "at_optimum"})])
def test_check_refuses_a_fit_cut_short(sound_cell, iterations, by):
    """A fit that ran fewer iterations than the configuration states is
    refused by its pass count whatever it reached (at the rehearsal's
    20,000 rows the cell's L2 weight makes 5 iterations a fit: certified
    gap 1e-5), and by the certificate as well where it is no fit (3
    iterations: 1e-2)."""
    built, _ = sound_cell
    cfg, last = built.cfg, built.last
    try:
        with jax.enable_x64(False):
            cut_short(built, iterations)
            check = built.check([built.record(built.fit())])
    finally:
        built.cfg, built.last = cfg, last
    assert failed(check) == by, check
    assert check["passes"] == [iterations + 2]
    assert (check["certificate"]["rel_gap"] > 5 * builder.GAP) == \
        ("at_optimum" in by)


def test_cell_rehearsal_is_correct_and_seeds_share_shapes(sound_cell):
    """The cell's command, rehearsed on the CPU with a traced window, ends
    in a line with `correct: true` and the metrics a CPU run can read; the
    shard it built has the shapes another seed builds."""
    child = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", "criteo-hashed-1m.fit", "--seed", "2147483659",
         "--seconds", "1", "--trace", "1", "--rehearse-cpu"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert child.returncode == 0, child.stderr[-2000:]
    lines = child.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0, lines[-4:]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["solve_passes.fit"] == 32
    assert metrics["fe_pack_s"] > 0
    info = next(l for l in lines if l.startswith("set-up "))
    shard = json.loads(info.split("; built ", 1)[1])["fe_build"]
    assert shard["padded_slots"] == 0 and shard["csc"] == 1
    other = sound_cell[0].info["fe_build"]
    assert {k: v for k, v in other.items() if k != "pack_s"} == \
        {k: v for k, v in shard.items() if k != "pack_s"}


@pytest.mark.parametrize("name", sorted(
    k for k in vars(selftest) if k.startswith("test_")))
def test_the_yardsticks_own_checks(name):
    """`benchmark/selftest.py`, check by check: among them that every name
    in BENCHMARK.json resolves to a file and to a metric the cell reports."""
    getattr(selftest, name)()
