"""The main path's programs compile for a TPU v5e, at real shapes.

No chip is attached here; the TPU's compiler is.  It compiles for a chip
that is described (`v5e:2x2`), and raises what the chip's compiler would
raise: an unaligned slice, a kernel over its fast-memory budget, a program
that does not fit 16 GB.  Nothing runs, so these tests say nothing about
results or times — chip_smoke.py does that on the chip.

What is compiled is what `python chip_smoke.py` runs (the GLMix fit through
cli.train and its model through cli.serve) plus BASELINE config 1's solve
and the Pallas kernel: the jitted callables the product itself builds,
lowered from `jax.ShapeDtypeStruct`s.  The shapes of the GLMix fit were read
off the real fit (seed 11: 950,051 training rows; per-user buckets E x S of
878 x 512, 1011 x 272, 1506 x 144, 2643 x 72).

Rules of this file (on-chip-measurement guide, section 2): the topology is
described inside a module-scoped fixture — never at import, never in
conftest.py, not autouse — everything compiles in the test's own process,
and these tests stay in this one file: a process keeps the TPU library, and
its lock, until it exits.
"""
import math
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from photon_ml_tpu.ops import LOGISTIC
from photon_ml_tpu.ops.objective import GLMObjective
from photon_ml_tpu.optim import (OptimizerConfig, OptimizerType,
                                 RegularizationContext, RegularizationType)
from photon_ml_tpu.optim.admm import collective_summary
from photon_ml_tpu.parallel.mesh import DATA_AXIS, FEATURE_AXIS

F32 = jnp.float32
L2 = RegularizationContext(RegularizationType.L2)
GLMIX_ROWS, D_GLOBAL, D_USER, USERS = 950_051, 31, 19, 6040
# the benchmark's per-user buckets (glmix-ml20m, both cells) and the per-item
# buckets of glmix-ml20m-user-item, E x S x d; the largest comes first
ML20M_USER_BUCKETS = [(6_384, 512, 21), (8_769, 280, 21), (15_365, 144, 21),
                      (24_860, 64, 21)]
ML20M_ITEM_BUCKETS = [(4_350, 512, 13), (2_291, 248, 13), (4_319, 80, 13),
                      (11_697, 16, 13)]
ML20M_BIG_BUCKET = ML20M_USER_BUCKETS[0]
CONFIG1_SHAPE = (1_643_520, 124)
V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep the cache out of it.
    # The product runs float32 (no --x64), so these compiles do too.
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with jax.enable_x64(False):
            yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_float32(compiled):
    """float64 must not leak in from host defaults: a TPU has no f64 unit
    and the product path is float32 end to end."""
    assert "f64[" not in compiled.as_text()


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, m
    return m.argument_size_in_bytes


def _trainer_objective(n, d, rows, replicated, x=None):
    """The objective FixedEffectCoordinate.update hands fit_fixed_effect on
    the mesh path (no weights or normalization in the GLMix fit; offsets
    are the other coordinates' scores, the mask marks real rows).  `x`: a
    feature matrix of shapes in place of the dense `[n, d]`."""
    return GLMObjective(LOGISTIC,
                        _sds((n, d), F32, rows(2)) if x is None else x,
                        _sds((n,), F32, rows(1)), weights=None,
                        offsets=_sds((n,), F32, rows(1)),
                        mask=_sds((n,), F32, rows(1)), norm=None,
                        l2_weight=0.0), \
        _sds((d,), F32, replicated), _sds((), F32, replicated)


@pytest.mark.parametrize("optimizer", [OptimizerType.LBFGS,
                                       OptimizerType.TRON],
                         ids=lambda o: o.value)
@pytest.mark.parametrize("shape", [CONFIG1_SHAPE, (GLMIX_ROWS, D_GLOBAL)],
                         ids=["config1-1643520x124", "glmix-950051x31"])
def test_fixed_effect_solve_compiles(one_chip, shape, optimizer):
    """The FE solve the trainer jits (parallel/fixed_effect._cached_solver),
    LBFGS and TRON, at config 1's a1a x 1024 shape and the GLMix global
    shard."""
    from photon_ml_tpu.parallel.fixed_effect import _cached_solver
    obj, x0, lam = _trainer_objective(*shape, lambda ndim: one_chip, one_chip)
    cfg = OptimizerConfig(optimizer=optimizer, max_iterations=100)
    compiled = _cached_solver(cfg, L2).lower(obj, x0, lam).compile()
    _assert_float32(compiled)
    args = _fits(compiled)
    n, d = shape
    assert args >= n * d * 4          # the design matrix is an argument


def test_factored_projection_refit_and_scoring_compile_at_the_cells_size(
        one_chip):
    """`game-ml20m-mf.fit`'s projection refit: the fixed effect's solver
    over the implicit Kronecker design of the per-user shard's 7,600,100
    flat rows (width 21, rank 8, the cell's 50 iterations under the
    upstream's stopping rule), weights and offsets as `ProjectionRows`
    hands them. It holds the shard and the factors by row as arguments and
    well under a GB of temporaries. The scoring program every entity
    coordinate runs has to fit its temporaries beside what the cell holds
    resident, which the allocator's peak never counted (PERF.md section 7,
    question 13)."""
    from photon_ml_tpu.ops.features import KroneckerDesign
    from photon_ml_tpu.parallel.fixed_effect import _cached_solver
    from photon_ml_tpu.parallel.random_effect import score_entities_matmul
    n, d, k, users = 7_600_100, 21, 8, 55_397
    #: the cell's `memory_peak_bytes` (my chip runs, PR 35); the scoring
    #: program's arguments are among them
    resident = 7_520_929_280
    rows = _sds((n,), F32, one_chip)
    obj = GLMObjective(LOGISTIC, KroneckerDesign(_sds((n, d), F32, one_chip),
                                                 _sds((n, k), F32, one_chip)),
                       rows, weights=rows, offsets=rows)
    cfg = OptimizerConfig(max_iterations=50)
    compiled = _cached_solver(cfg, L2).lower(
        obj, _sds((k * d,), F32, one_chip), _sds((), F32, one_chip),
        None).compile()
    _assert_float32(compiled)
    assert _fits(compiled) >= n * (d + k + 3) * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 1024 ** 3
    scoring = score_entities_matmul.lower(
        _sds((users, k), F32, one_chip), _sds((k, d), F32, one_chip),
        _sds((n, d), F32, one_chip), _sds((n,), jnp.int32, one_chip)).compile()
    _fits(scoring)
    m = scoring.memory_analysis()
    assert (resident + m.temp_size_in_bytes + m.output_size_in_bytes
            < V5E_HBM_BYTES), m


# criteo-hashed-1m.fit: 2,850,000 rows x 39 slots, 1,000,000 columns (721,687
# stored), 111,150,000 non-zeros; and a shape whose `w` is over the VMEM budget
CRITEO = (2_850_000, 39, 1_000_000, 111_150_000, 721_687)
OVER_BUDGET = (100_000, 8, 13_000_000, 800_000, 500_000)


def _packed_sparse_shapes(shape, one_chip, fops):
    """A `PaddedSparse` of shapes as `pack_sparse` lays it out for a TPU:
    the kernel's streams where its rule takes the shape, else the XLA
    forms' `[n, k]` rows and the column-sorted stream."""
    n, k, d, nnz, stored = shape
    i32 = jnp.int32
    if fops._vmem_gather_fits(n, d, k, np.float32):
        block, group = fops._VG_BLOCK, fops._VG_GROUP
        slots = -(-n // block) * block * k
        # every stored column's run ends in half a group of padding
        groups = -(-(nnz + stored * group // 2) // group)
        stream = -(-groups // block) * block * group
        return fops.PaddedSparse(
            _sds((slots,), i32, one_chip), _sds((slots,), F32, one_chip), d,
            _sds((stream,), i32, one_chip), _sds((stream,), F32, one_chip),
            _sds((d + 1,), i32, one_chip),
            vmem_gather=fops.VmemGather(n, k))
    return fops.PaddedSparse(
        _sds((n, k), i32, one_chip), _sds((n, k), F32, one_chip), d,
        _sds((nnz,), i32, one_chip), _sds((nnz,), F32, one_chip),
        _sds((d + 1,), i32, one_chip))


@pytest.mark.parametrize("shape,kernel", [(CRITEO, True),
                                          (OVER_BUDGET, False)],
                         ids=["criteo-2850000x39", "table-over-budget"])
def test_sparse_fixed_effect_solve_compiles(one_chip, monkeypatch, shape,
                                            kernel):
    """The FE solve the trainer jits over the packed sparse shard of
    `criteo-hashed-1m.fit`: both products of a pass fetch their random
    operand from a table in VMEM (`ops/features.py::_vmem_segment_sums`,
    a Mosaic kernel), float32, within the chip's memory.  A shard whose
    `w` table is over `VMEM_TABLE_BYTES` compiles to the XLA forms.  The
    test stands in for the chip where the program asks which backend it
    packs and compiles for."""
    from photon_ml_tpu.ops import features as fops
    from photon_ml_tpu.parallel.fixed_effect import _cached_solver
    monkeypatch.setattr(fops, "_on_tpu", lambda: True)
    n, _, d, _, _ = shape
    x = _packed_sparse_shapes(shape, one_chip, fops)
    assert (x.vmem_gather is not None) == kernel and x.shape == (n, d)
    obj, x0, lam = _trainer_objective(n, d, lambda ndim: one_chip, one_chip,
                                      x=x)
    cfg = OptimizerConfig(max_iterations=30, tolerance=0.0)
    compiled = _cached_solver(cfg, L2).lower(obj, x0, lam).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
    _assert_float32(compiled)
    _fits(compiled)


#: the factored coordinate's per-user buckets in game-ml20m-mf (cap 256)
ML20M_MF_BUCKETS = [(13_484, 256), (8_999, 160), (13_505, 96), (19_390, 48)]
ML20M_ROWS = 7_600_100


@pytest.mark.parametrize("rows,buckets,kernel", [
    (ML20M_ROWS, [(e, s) for e, s, _ in ML20M_USER_BUCKETS], True),
    (ML20M_ROWS, ML20M_MF_BUCKETS, True),
    (20_000_263, [(e, s) for e, s, _ in ML20M_USER_BUCKETS], False)],
    ids=["perUser-9527528", "perUserMF-7118944", "table-over-budget"])
def test_offsets_gather_compiles_at_the_cells_size(one_chip, rows, buckets,
                                                   kernel):
    """`jit__gather_flat_offsets`, a coordinate visit's one gather of every
    bucket's offsets (data/batching.py): where the flat offsets fit
    `VMEM_TABLE_BYTES` it is the VMEM table gather (`ops/features.py::
    vmem_take`, a Mosaic kernel) over the visit's one index stream, within
    the chip's fast memory and float32; the whole 20 M-row corpus's offsets
    (80 MB) do not fit, and get XLA's element gather a bucket."""
    from photon_ml_tpu.data.batching import _gather_flat_offsets, _tiled
    from photon_ml_tpu.ops import features as fops
    assert fops.vmem_take_fits(rows, np.float32) == kernel
    flat = _sds((rows,), F32, one_chip)
    if kernel:
        cells = fops.vmem_take_cells(sum(math.prod(_tiled(e, s))
                                         for e, s in buckets))
        assert (rows // 128 + 1) * 128 * 4 * 2 + fops._VMEM_HEADROOM_BYTES \
            <= 128 * 1024 ** 2
        lowered = _gather_flat_offsets.lower(
            flat, _sds((cells,), jnp.int32, one_chip), None, dtype="float32",
            shapes=tuple(buckets), interpret=False)
    else:
        lowered = _gather_flat_offsets.lower(
            flat, tuple(_sds(b, jnp.int32, one_chip) for b in buckets),
            tuple(_sds(b, F32, one_chip) for b in buckets), dtype="float32")
    compiled = lowered.compile()
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
    assert "jit__gather_flat_offsets" in lowered.as_text()
    _assert_float32(compiled)
    _fits(compiled)
    if kernel:
        # the blocks are cut from the stream by slices and bitcasts: cut
        # from cells in row order they were 3.6 MB of element shuffles,
        # some 6 s of compile in every process
        assert compiled.memory_analysis().generated_code_size_in_bytes \
            < 1024 ** 2


def _bucket_solve(one_chip, E, S, d, config):
    """`jit_re_bucket_solve` (parallel/random_effect._cached_batched_solver)
    compiled for one E x S x d bucket with weights and offsets, as the
    trainer calls it; float32 and within the chip's memory."""
    from photon_ml_tpu.parallel.random_effect import _cached_batched_solver
    cells = _sds((E, S), F32, one_chip)
    solver = _cached_batched_solver(LOGISTIC, config, L2, True, True)
    compiled = solver.lower(
        _sds((E, S, d), F32, one_chip), cells, cells, cells, cells,
        _sds((E, d), F32, one_chip), _sds((), F32, one_chip), None).compile()
    _assert_float32(compiled)
    _fits(compiled)
    return compiled


@pytest.mark.parametrize("entities,samples",
                         [(878, 512), (1011, 272), (1506, 144), (2643, 72)])
def test_random_effect_bucket_solve_compiles(one_chip, entities, samples):
    """The vmapped per-entity solver (parallel/random_effect.
    _cached_batched_solver) at every S-bucket of the GLMix fit: rows capped
    at active_data_upper_bound=512, per-user width 19."""
    _bucket_solve(one_chip, entities, samples, D_USER,
                  OptimizerConfig(max_iterations=100))


def _hbm_bytes(dims, minor_to_major, tile):
    """Bytes of a float32 array as the TPU lays it out: the two minor-most
    dimensions padded to the tile."""
    by_minor = [dims[i] for i in minor_to_major]
    for k, t in enumerate(reversed(tile)):
        by_minor[k] = -(-by_minor[k] // t) * t
    return 4 * math.prod(by_minor)


def test_random_effect_big_bucket_solve_holds_no_slot_axis(one_chip):
    """`jit_re_bucket_solve` at the benchmark's big bucket, history m = 10.
    A history indexed by a per-lane pair count was an [E, m, d] buffer that
    this compiler tiles T(8,128) over [m, d]: ten times its bytes, streamed
    whole for every row taken from it (PERF.md, PR 28).  The program holds no
    array with the slot axis beside lanes and width, in any order, and every
    [E, d] vector of the solve (x, g, p, the 2 m history leaves) costs less
    than twice its real bytes."""
    E, S, d = ML20M_BIG_BUCKET
    config = OptimizerConfig(max_iterations=100)
    m = config.history
    text = _bucket_solve(one_chip, E, S, d, config).as_text()
    shapes = {tuple(map(int, dims.split(",")))
              for dims in re.findall(r"\b[a-z]+[0-9]*\[([0-9,]+)\]", text)}
    assert (E, S, d) in shapes and (E, d) in shapes     # the parser does read
    assert not [s for s in shapes if sorted(s) == sorted((E, m, d))]
    assert not [s for s in shapes if len(s) >= 3 and E in s and m in s]
    vectors = re.findall(
        rf"f32\[({E},{d}|{d},{E})\]\{{([0-9,]+):T\(([0-9]+),([0-9]+)\)", text)
    assert len(vectors) >= 2 * m
    for dims, order, *tile in vectors:
        dims = tuple(map(int, dims.split(",")))
        padded = _hbm_bytes(dims, tuple(map(int, order.split(","))),
                            tuple(map(int, tile)))
        assert padded <= 2 * 4 * E * d, (dims, order, tile)


@pytest.mark.parametrize("bucket", ML20M_USER_BUCKETS[1:]
                         + ML20M_ITEM_BUCKETS[-1:], ids=str)
def test_random_effect_bucket_sample_axis_pads_to_the_granule(one_chip,
                                                              bucket):
    """What `data/batching.py`'s `_SAMPLE_GRANULE` rests on.  At the
    benchmark's bucket shapes whose S is a multiple of 8 and not of 128, the
    feature block and the four `[E, S]` operands of `jit_re_bucket_solve`
    arrive with the sample axis on sublanes (lanes minor: E is what pads to
    128), so a bucket's arguments cost its cells and no more."""
    from photon_ml_tpu.data.batching import _SAMPLE_GRANULE
    E, S, d = bucket
    assert S % _SAMPLE_GRANULE == 0 and S % 128
    text = _bucket_solve(one_chip, E, S, d,
                         OptimizerConfig(max_iterations=100)).as_text()
    entry = text[:text.index("->")]             # the arguments' layouts
    operands = re.findall(
        rf"f32\[({E},{S}(?:,{d})?)\]\{{([0-9,]+):T\(([0-9]+),([0-9]+)\)",
        entry)
    assert len(operands) == 5
    for dims, order, *tile in operands:
        dims = tuple(map(int, dims.split(",")))
        order = tuple(map(int, order.split(",")))
        assert order[0] == 0 and order[1] == 1, (dims, order)
        padded = _hbm_bytes(dims, order, tuple(map(int, tile)))
        assert padded <= 1.02 * 4 * math.prod(dims), (dims, order, tile)


@pytest.mark.parametrize("bucket", [8, 1024], ids=["smallest", "largest"])
def test_scorer_bucket_program_compiles(one_chip, bucket):
    """The serving scorer's one fused program per bucket (serving/scorer.
    CompiledScorer._program) for the GLMix model: FE matvec + per-user
    gather-dot, at the smallest and the largest default bucket."""
    from photon_ml_tpu.models.coefficients import Coefficients
    from photon_ml_tpu.models.game import (FixedEffectModel, GameModel,
                                           RandomEffectModel)
    from photon_ml_tpu.models.glm import model_for_task
    from photon_ml_tpu.serving.scorer import CompiledScorer
    task = "logistic_regression"
    model = GameModel({
        "fixed": FixedEffectModel(model_for_task(task, Coefficients(
            jnp.zeros(D_GLOBAL, F32))), "global"),
        "perUser": RandomEffectModel(
            "userId", "per_user", task, jnp.zeros((USERS, D_USER), F32),
            np.arange(USERS).astype(str).astype(object), None, D_USER),
    }, task)
    scorer = CompiledScorer(model)
    assert scorer.bucket_sizes()[0] == 8 and scorer.bucket_sizes()[-1] == 1024
    tables = tuple(_sds(t.shape, t.dtype, one_chip) for t in scorer._tables)
    assert [t.shape for t in tables] == [(D_GLOBAL,), (USERS, D_USER)]
    xs = {"global": _sds((bucket, D_GLOBAL), F32, one_chip),
          "per_user": _sds((bucket, D_USER), F32, one_chip)}
    lanes = {"perUser": _sds((bucket,), jnp.int32, one_chip)}
    compiled = scorer._program.lower(tables, xs, lanes).compile()
    _assert_float32(compiled)
    _fits(compiled)


@pytest.mark.parametrize("n,d", [CONFIG1_SHAPE, (200_000, 2048), (700, 37)],
                         ids=["1643520x124", "200000x2048",
                              "unaligned-700x37"])
def test_pallas_fused_value_and_gradient_compiles(one_chip, n, d):
    """ops/pallas_kernels.fused_value_and_gradient as a real Mosaic kernel
    (interpret=False) at the two shapes its docstring quotes and at the
    unaligned shape its interpret-mode test uses."""
    from photon_ml_tpu.ops.pallas_kernels import fused_value_and_gradient
    row = _sds((n,), F32, one_chip)
    compiled = fused_value_and_gradient.lower(
        LOGISTIC, _sds((n, d), F32, one_chip), row, _sds((d,), F32, one_chip),
        row, row, False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_float32(compiled)
    _fits(compiled)


def test_fixed_effect_solve_shards_over_four_chips(topo, one_chip):
    """`chip_smoke.py --multichip`'s program: the GLMix FE solve with rows
    sharded over a data=4 mesh of the 2x2 chips.  Per device the arguments
    are about a quarter of the one-chip program's, and the only collectives
    are data-axis all-reduces no larger than the [d] gradient."""
    from photon_ml_tpu.parallel.fixed_effect import _cached_solver
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1),
                (DATA_AXIS, FEATURE_AXIS))
    rows = lambda ndim: NamedSharding(
        mesh, P(DATA_AXIS, *([None] * (ndim - 1))))
    n = GLMIX_ROWS + (-GLMIX_ROWS) % 4      # the residency layer pads
    solver = _cached_solver(OptimizerConfig(max_iterations=100), L2)
    with mesh:
        sharded = solver.lower(*_trainer_objective(
            n, D_GLOBAL, rows, NamedSharding(mesh, P()))).compile()
    single = solver.lower(*_trainer_objective(
        n, D_GLOBAL, lambda ndim: one_chip, one_chip)).compile()
    _assert_float32(sharded)
    share = _fits(sharded) / _fits(single)
    assert 0.24 <= share <= 0.26, share

    summary = collective_summary(sharded.as_text(), mesh)
    assert summary["data"], summary
    assert not (summary["feature"] or summary["other"]), summary
    assert max(nbytes for _, nbytes in summary["data"]) <= D_GLOBAL * 4
    assert "all-reduce" not in single.as_text()


def test_random_effect_bucket_solve_shards_over_four_chips(topo):
    """The other program of the --multichip fit: one S-bucket's vmapped
    solves with the ENTITY axis sharded over the data=4 mesh.  Entities are
    independent, so the program moves no lane's data between chips: its
    collectives are scalars over the data axis (the loops' predicates and
    the lock step's own count, PR 37) and the run's row of counts."""
    from photon_ml_tpu.optim.types import LOCKSTEP
    from photon_ml_tpu.parallel.random_effect import _cached_batched_solver
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1),
                (DATA_AXIS, FEATURE_AXIS))
    E, S, d = 2644, 72, D_USER     # 2643 lanes padded to the mesh's four
    lanes = lambda ndim: NamedSharding(
        mesh, P(DATA_AXIS, *([None] * (ndim - 1))))
    cells = _sds((E, S), F32, lanes(2))
    scalar = _sds((), F32, NamedSharding(mesh, P()))
    solver = _cached_batched_solver(
        LOGISTIC, OptimizerConfig(max_iterations=100), L2, True, True)
    with mesh:
        compiled = solver.lower(
            _sds((E, S, d), F32, lanes(3)), cells, cells, cells, cells,
            _sds((E, d), F32, lanes(2)), scalar, None).compile()
    _assert_float32(compiled)
    assert _fits(compiled) <= 0.3 * (E * S * (d + 4) + E * d) * 4
    summary = collective_summary(compiled.as_text(), mesh)
    assert not (summary["feature"] or summary["global"]
                or summary["other"]), summary
    assert max(nbytes for _, nbytes in summary["data"]) <= 4 * len(
        LOCKSTEP), summary
