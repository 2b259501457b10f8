"""The offsets gather of an entity coordinate from the flat scores held in
VMEM (`ops/features.py::vmem_take`, `RandomEffectDataset.blocks_with_offsets`),
on the CPU.

Here the kernel runs interpreted, so these tests say what it computes and
when it engages, never how fast.  Where the program asks whether its arrays
land on a TPU (`fops._on_tpu`), a test answers for the chip at the
coordinate's build and lets the kernel run interpreted after;
`tests/test_tpu_compile.py` compiles the real kernel at the cells' sizes.
Every kernel program here is traced with x64 off, as the chip runs.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from photon_ml_tpu import telemetry
from photon_ml_tpu.data import batching, build_game_dataset
from photon_ml_tpu.data.batching import (RandomEffectDataConfig,
                                         build_random_effect_dataset)
from photon_ml_tpu.game import (FixedEffectCoordinateConfig, GameEstimator,
                                GameTrainingConfig, GLMOptimizationConfig,
                                RandomEffectCoordinateConfig)
from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
from photon_ml_tpu.ops import features as fops
from photon_ml_tpu.optim import (OptimizerConfig, RegularizationContext,
                                 RegularizationType)
from photon_ml_tpu.parallel import make_mesh
from photon_ml_tpu.utils.jax_cache import CompileTimeTracker

#: rows a user: one of 512 (a bucket of one entity at S = 512 under four
#: buckets), counts that are no multiple of the sample granule, and users of
#: one row (S = 8)
COUNTS = [512, 300, 161, 40, 9, 8, 7] + [5] * 3 + [3] * 5 + [1] * 16
L2 = RegularizationContext(RegularizationType.L2)


def corpus(dtype=np.float32):
    """A GLMix corpus of `COUNTS` users, rows shuffled so that a user's
    rows lie apart, over a global and a per-user shard."""
    rng = np.random.default_rng(11)
    users = rng.permutation(np.repeat(np.arange(len(COUNTS)), COUNTS))
    n = len(users)
    xg = np.c_[rng.normal(size=(n, 3)), np.ones(n)]
    xu = np.c_[rng.uniform(size=(n, 2)) < 0.5, np.ones(n)]
    z = xg @ rng.normal(size=4) + np.einsum(
        "nd,nd->n", xu, rng.normal(size=(len(COUNTS), 3))[users])
    y = rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-z))
    return build_game_dataset(
        y.astype(dtype), {"global": xg.astype(dtype),
                          "per_user": xu.astype(dtype)},
        entity_ids={"userId": users})


@pytest.fixture(scope="module")
def ds():
    with jax.enable_x64(False):
        yield corpus()


def training_config():
    opt = GLMOptimizationConfig(optimizer=OptimizerConfig(),
                                regularization=L2, regularization_weight=1.0)
    return GameTrainingConfig(
        task_type="logistic_regression",
        coordinates={"fixed": FixedEffectCoordinateConfig("global", opt),
                     "perUser": RandomEffectCoordinateConfig(
                         "userId", "per_user", opt)},
        updating_sequence=["fixed", "perUser"], num_outer_iterations=2,
        seed=3)


def flat_offsets(n):
    """Offsets of every sign, zeros of both signs among them; row 0's is
    negative, so XLA's form writes -0.0 in a padded cell."""
    values = np.random.default_rng(n).normal(size=n).astype(np.float32)
    values[0] = -1.5
    values[1::7] = 0.0
    values[2::7] = -0.0
    return values


@pytest.mark.parametrize("buckets", [1, 2, 4])
def test_kernel_offsets_equal_xla_forms(ds, buckets):
    """Bit for bit but the sign of a zero in a padded cell: a bucket's
    tiles fill no whole grid step (1 bucket: 8 steps exactly; 2 and 4: 13
    and 17 steps, the last in part), S runs from 8 to 512, E is no multiple
    of a tile's 128 lanes, and the four buckets hold a bucket of one
    entity."""
    if buckets == 4:      # the coordinate's own build, as the fits use it
        cfg = training_config().coordinates["perUser"].data_config(3)
    else:
        cfg = RandomEffectDataConfig("userId", "per_user",
                                     max_buckets=buckets, seed=3)
    with jax.enable_x64(False):
        red = build_random_effect_dataset(ds, cfg)
        flat = jnp.asarray(flat_offsets(ds.num_rows))
        kernel = red.blocks_with_offsets(flat, vmem=True)
        xla = red.blocks_with_offsets(flat, vmem=False)
    shapes = [(b.num_entities, b.samples_per_entity) for b in red.buckets]
    assert len(shapes) == buckets
    cells = sum(np.prod(batching._tiled(e, s)) for e, s in shapes)
    assert red.offsets_stream(ds.num_rows).shape == (
        fops.vmem_take_cells(cells),)
    assert (cells % fops._VT_BLOCK > 0) == (buckets > 1)
    if buckets == 4:
        assert (1, 512) in shapes and min(s for _, s in shapes) == 8
    host = np.concatenate([flat_offsets(ds.num_rows), [0.0]])
    for bucket, got, want in zip(red.buckets, kernel, xla):
        got = np.asarray(got.offsets)
        want = np.asarray(want.offsets)
        assert got.dtype == want.dtype == np.float32
        np.testing.assert_array_equal(got, host[np.where(
            bucket.row_ids >= 0, bucket.row_ids, ds.num_rows)])
        np.testing.assert_array_equal(np.abs(got).view(np.int32),
                                      np.abs(want).view(np.int32))
        padded = bucket.row_ids < 0
        assert not np.signbit(got[padded]).any()
        if padded.any():
            assert np.signbit(want[padded]).all()   # flat[0] * 0 = -0.0


def kernels_run(coord, flat, monkeypatch):
    """`pallas_call`s in the jaxpr of the one `_gather_flat_offsets`
    program a visit of `coord` runs over `flat`; nothing is compiled."""
    seen = []
    real = batching._gather_flat_offsets

    def record(*args, **kwargs):
        program = functools.partial(real, **kwargs)
        seen.append(str(jax.make_jaxpr(program)(*args)).count("pallas_call"))
        return jax.eval_shape(program, *args)

    with monkeypatch.context() as m:
        m.setattr(batching, "_gather_flat_offsets", record)
        coord.red.blocks_with_offsets(flat, coord.vmem_offsets)
    assert len(seen) == 1
    return seen[0]


@pytest.mark.parametrize("how,kernels", [
    ("one-tpu-device-float32", 1), ("cpu", 0), ("float64", 0),
    ("table-over-the-budget", 0), ("two-device-data-axis", 0),
    ("budgeted", 0), ("float64-operand", 0), ("sharded-operand", 0)])
def test_the_rule_that_decides_and_its_gauge(ds, monkeypatch, how, kernels):
    """The kernel runs where the build lands on a TPU, on one device, with
    float32 blocks, flat offsets within `VMEM_TABLE_BYTES` and no HBM
    budget, and where the offsets a visit hands it are float32 on one
    device; `vmem_offsets` (build counts and gauge) says so, 1 or 0."""
    monkeypatch.setattr(fops, "_on_tpu", lambda: how != "cpu")
    mesh, budget, data, x64 = None, None, ds, False
    if how == "float64":
        data, x64 = corpus(np.float64), True
    elif how == "table-over-the-budget":
        monkeypatch.setattr(fops, "VMEM_TABLE_BYTES",
                            4 * (ds.num_rows // 128 + 1) * 128 - 1)
    elif how == "two-device-data-axis":
        mesh = make_mesh(devices=jax.devices()[:2])
    elif how == "budgeted":
        budget = 1 << 30
    name = "perUser"
    with jax.enable_x64(x64):
        coord = RandomEffectCoordinate(
            name, data, training_config().coordinates[name],
            "logistic_regression", mesh=mesh, seed=3,
            hbm_budget_bytes=budget)
        flat = jnp.asarray(flat_offsets(data.num_rows))
        if how == "float64-operand":
            with jax.enable_x64(True):
                flat = jnp.asarray(flat, jnp.float64)
                assert kernels_run(coord, flat, monkeypatch) == 0
            return
        if how == "sharded-operand":
            two = make_mesh(devices=jax.devices()[:2])
            flat = jax.device_put(flat[:ds.num_rows - ds.num_rows % 2],
                                  jax.sharding.NamedSharding(
                                      two, jax.sharding.PartitionSpec("data")))
        assert kernels_run(coord, flat, monkeypatch) == kernels
    assert coord.build_stats["vmem_offsets"] == coord.vmem_offsets
    if how != "sharded-operand":
        assert coord.vmem_offsets == kernels
    gauges = telemetry.snapshot()["metrics"]["gauges"]
    assert gauges[f"train.re_build.{name}.vmem_offsets"] == \
        coord.vmem_offsets


def fit(ds, chip):
    """A fit of fixed + perUser on `ds` with the rule answered for the chip
    (`chip`, the kernel interpreted) or the CPU, counting the calls of
    `_gather_flat_offsets`."""
    calls = []
    real_program = batching._gather_flat_offsets

    def counted(*args, **kwargs):
        calls.append(kwargs.get("shapes") is not None)
        return real_program(*args, **kwargs)

    real_rule = batching.RandomEffectDataset.vmem_offsets

    def rule(self, *args, **kwargs):
        with pytest.MonkeyPatch.context() as m:
            m.setattr(fops, "_on_tpu", lambda: chip)
            return real_rule(self, *args, **kwargs)

    with pytest.MonkeyPatch.context() as m, jax.enable_x64(False):
        m.setattr(batching, "_gather_flat_offsets", counted)
        m.setattr(batching.RandomEffectDataset, "vmem_offsets", rule)
        result = GameEstimator(
            training_config(),
            mesh=make_mesh(devices=jax.devices()[:1])).fit(ds)
    return result, calls


def test_a_fit_with_the_kernel_is_the_xla_forms_fit(ds):
    """The same objective history to the last digit; one gather a visit,
    the kernel's where the rule holds; a repeat fit traces nothing; the
    gauge in the fit's result and in the snapshot."""
    plain, plain_calls = fit(ds, chip=False)
    kernel, kernel_calls = fit(ds, chip=True)
    assert plain_calls == [False, False] and kernel_calls == [True, True]
    assert kernel.descent.objective_history == \
        plain.descent.objective_history
    assert [b["vmem_offsets"] for b in (plain.coordinate_build["perUser"],
                                        kernel.coordinate_build["perUser"])
            ] == [0, 1]
    assert telemetry.snapshot()["metrics"]["gauges"][
        "train.re_build.perUser.vmem_offsets"] == 1
    tracker = CompileTimeTracker().install()
    again, _ = fit(ds, chip=True)
    assert tracker.count == 0
    assert again.descent.objective_history == kernel.descent.objective_history


def test_the_program_keeps_its_name(ds):
    """`exchange_device_s.fit` reads the device program by the name
    `jit__gather_flat_offsets`, whichever form runs."""
    with jax.enable_x64(False):
        red = build_random_effect_dataset(
            ds, training_config().coordinates["perUser"].data_config(3))
        flat = jnp.zeros(ds.num_rows, jnp.float32)
        shapes = tuple((b.num_entities, b.samples_per_entity)
                       for b in red.buckets)
        forms = [((flat, red.offsets_stream(ds.num_rows), None),
                  dict(shapes=shapes, interpret=True)),
                 ((flat, tuple(b.safe_ids_dev() for b in red.buckets),
                   tuple(b.blocks.mask for b in red.buckets)), {})]
        for args, kwargs in forms:
            text = batching._gather_flat_offsets.lower(
                *args, dtype="float32", **kwargs).as_text()
            assert "jit__gather_flat_offsets" in text
