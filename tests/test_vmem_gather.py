"""The VMEM table-gather kernel of the sparse products
(`ops/features.py::_vmem_segment_sums`), on the CPU.

Here the kernel runs interpreted (a matrix in the kernel's layout that
finds itself off the TPU does), so these tests say what it computes and
when it engages, never how fast: both forms of each product, the kernel's
and XLA's over the same streams, are held to a float64 SciPy product.  The
tests stand in for the chip where `pack_sparse` asks what it packs for
(`_on_tpu`); `tests/test_tpu_compile.py` compiles the real kernel at the
cell's shape.
"""
import json

import numpy as np
import pytest
import scipy.sparse as sp

import jax
import jax.numpy as jnp

from photon_ml_tpu.ops import features as fops

EPS = float(np.finfo(np.float32).eps)
CHUNK = 16          # `_CSC_CHUNK` for these tests, in groups of the stream


def _random_rows(rng, n, d, width):
    """CSR of `n` rows with 1..`width` distinct columns each (so the padded
    rows have padding slots), values from a normal."""
    counts = rng.integers(1, width + 1, n)
    cols = np.concatenate([rng.choice(d, c, replace=False) for c in counts])
    indptr = np.concatenate([[0], np.cumsum(counts)])
    return sp.csr_matrix((rng.standard_normal(len(cols)).astype(np.float32),
                          cols, indptr), shape=(n, d))


def _with_column(mat, col, rows, rng):
    """`mat` with column `col` holding exactly `rows`."""
    mat = mat.tolil()
    mat[:, col] = 0
    for r in rows:
        mat[r, col] = np.float32(rng.standard_normal())
    return mat.tocsr()


def _case(name):
    """One matrix an edge; every case's name says what it guards."""
    rng = np.random.default_rng(sorted(map(ord, name)))
    n, d, width = 1024, 256, 5
    if name == "padding-slots":
        mat = _random_rows(rng, n, d, width)
        assert np.diff(mat.indptr).min() < np.diff(mat.indptr).max()
    elif name == "rows-no-multiple-of-the-block":
        mat = _random_rows(rng, 1030, d, width)
    elif name == "empty-columns":
        mat = _random_rows(rng, n, d, width)
        for col in (0, 7, d - 1):
            mat = _with_column(mat, col, [], rng)
    elif name == "column-of-one-nonzero":
        mat = _with_column(_random_rows(rng, n, d, width), 3, [11], rng)
    elif name == "column-longer-than-a-scan-chunk":
        # 300 non-zeros are 38 groups, over two chunks of 16
        mat = _with_column(_random_rows(rng, n, d, width), 5, range(300), rng)
    elif name == "column-straddles-two-chunks":
        # the first three columns hold 14 groups, the fourth's 4 cross 16
        mat = _random_rows(rng, n, d, width)
        for col, count in enumerate([40, 40, 32, 30]):
            mat = _with_column(mat, col, range(col, col + count), rng)
    elif name == "duplicate-columns-in-a-row":
        rows = np.repeat(np.arange(n), 4)
        cols = rng.integers(0, d, (n, 4))
        cols[:, 1] = cols[:, 0]                  # every row repeats a column
        mat = sp.coo_matrix(
            (rng.standard_normal(4 * n).astype(np.float32),
             (rows, cols.reshape(-1))), shape=(n, d)).tocsr()
    elif name == "tables-no-multiple-of-128":
        mat = _random_rows(rng, 1000, 200, width)
    else:
        raise KeyError(name)
    return mat


CASES = ["padding-slots", "rows-no-multiple-of-the-block", "empty-columns",
         "column-of-one-nonzero", "column-longer-than-a-scan-chunk",
         "column-straddles-two-chunks", "duplicate-columns-in-a-row",
         "tables-no-multiple-of-128"]


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """`pack_sparse` packs as it would for one TPU device, a small matrix
    gets the column-sorted view, and the scan's chunk is short enough for a
    small stream to span many."""
    monkeypatch.setattr(fops, "_on_tpu", lambda: True)
    monkeypatch.setattr(fops, "CSC_MIN_COLS", 1)
    monkeypatch.setattr(fops, "_CSC_CHUNK", CHUNK)


def _pack(mat):
    return fops.pack_sparse(mat, with_csc=True)


@pytest.mark.parametrize("product", ["matvec", "rmatvec", "sq_rmatvec"])
@pytest.mark.parametrize("case", CASES)
def test_kernel_and_xla_forms_equal_the_float64_product(as_on_the_chip,
                                                        monkeypatch, case,
                                                        product):
    """float32 to a few ulps: a row's sum to 8 eps of its absolute sum; a
    column's to 8 eps of its absolute sum plus the largest absolute sum a
    scan chunk holds (a column's sum is a difference of two prefixes of
    its chunk, in both forms)."""
    mat = _case(case)
    x, counts = _pack(mat)
    assert counts["vmem_gather"] == 2 and x.vmem_gather == (
        mat.shape[0], counts["ell_width"])
    monkeypatch.setattr(fops, "_on_tpu", lambda: False)   # run interpreted
    n, d = mat.shape
    rng = np.random.default_rng(len(case))
    m64 = mat.astype(np.float64)
    m64.sum_duplicates()
    if product == "matvec":
        operand = rng.standard_normal(d).astype(np.float32)
        truth = m64 @ operand.astype(np.float64)
        limit = 8 * EPS * (abs(m64) @ abs(operand.astype(np.float64)))
    else:
        operand = rng.standard_normal(n).astype(np.float32)
        if product == "sq_rmatvec":
            m64 = m64.multiply(m64).tocsr()
        truth = m64.T @ operand.astype(np.float64)
        column = abs(m64).T @ abs(operand.astype(np.float64))
        # the largest absolute sum of CHUNK consecutive groups' worth of
        # the column-sorted stream: at most that of CHUNK * group slots
        contrib = abs(m64.tocsc().data) * abs(
            operand.astype(np.float64))[m64.tocsc().indices]
        span = CHUNK * fops._VG_GROUP
        chunk = max(contrib[i:i + span].sum()
                    for i in range(0, max(len(contrib), 1), span // 2))
        limit = 8 * EPS * (column + chunk)
    fn = getattr(fops, product)
    kernel = np.asarray(fn(x, jnp.asarray(operand)), np.float64)
    plain = np.asarray(fn(x.xla_forms(), jnp.asarray(operand)), np.float64)
    assert "pallas_call" in str(jax.make_jaxpr(fn)(x, jnp.asarray(operand)))
    assert kernel.shape == truth.shape == plain.shape
    assert (abs(kernel - truth) <= limit + 1e-30).all(), \
        (abs(kernel - truth) / (limit + 1e-30)).max()
    assert (abs(plain - truth) <= limit + 1e-30).all(), \
        (abs(plain - truth) / (limit + 1e-30)).max()
    if case == "column-straddles-two-chunks" and product != "matvec":
        end = np.asarray(x.csc_end)
        assert end[3] // CHUNK != (end[4] - 1) // CHUNK      # it does cross
        assert end[4] - end[3] < CHUNK
    if case == "column-longer-than-a-scan-chunk" and product != "matvec":
        end = np.asarray(x.csc_end)
        assert end[6] - end[5] > 2 * CHUNK


def test_the_kernel_layout_holds_the_same_two_views(as_on_the_chip):
    """The streams ARE the two views: read as the XLA forms read them they
    give back the matrix, a column's run is whole groups with row 0 at
    value 0 in its padding, no group straddles a column, and the device
    holds no third copy."""
    mat = _case("padding-slots")
    x, counts = _pack(mat)
    n, d = mat.shape
    width, group, block = counts["ell_width"], fops._VG_GROUP, fops._VG_BLOCK
    assert x.shape == (n, d) and x.indices.shape == (block * width,)
    np.testing.assert_array_equal(np.asarray(fops.densify(x)), mat.toarray())
    plain = x.xla_forms()
    assert plain.vmem_gather is None and plain.indices.shape == (n, width)
    end = np.asarray(x.csc_end, np.int64)
    csc = mat.tocsc()
    np.testing.assert_array_equal(np.diff(end),
                                  -(-np.diff(csc.indptr) // group))
    rows, vals = np.asarray(x.csc_row), np.asarray(x.csc_val)
    assert len(rows) % (group * block) == 0 and end[-1] * group <= len(rows)
    for j in (0, 1, d // 2, d - 1):
        run = slice(end[j] * group, end[j + 1] * group)
        stored = csc.indptr[j + 1] - csc.indptr[j]
        np.testing.assert_array_equal(rows[run][:stored],
                                      csc.indices[csc.indptr[j]:][:stored])
        assert not rows[run][stored:].any() and not vals[run][stored:].any()
    assert counts["device_bytes"] == (8 * len(np.asarray(x.indices))
                                      + 8 * len(rows) + 4 * (d + 1))
    assert fops.pack_sparse(cached=x, with_csc=True) == (x, None)
    assert x.without_csc().indices.shape == (n, width)
    assert fops.pad_rows(x, 3).shape == (n + 3, d)


@pytest.mark.parametrize("blocks,bucketed", [
    (1, 1), (2047, 2047), (2049, 2050), (13_901, 13_904), (13_902, 13_904)])
def test_stream_lengths_are_bucketed_to_a_thousandth(blocks, bucketed):
    """Seeds of the cell pad their column runs to 13,901 or 13,902 blocks
    (my chip runs, PR 33): one compiled length, at most 0.1% more slots."""
    assert fops._bucketed_blocks(blocks) == bucketed
    assert blocks <= bucketed <= blocks * 1.001 + 1


def test_a_product_outside_any_jit_compiles_once(as_on_the_chip,
                                                monkeypatch):
    """A one-device coordinate scores with `matvec` outside any jit: the
    second call of a shape traces and compiles nothing (my chip run, PR 33:
    a kernel built anew each call compiled in every fit of the window)."""
    from photon_ml_tpu.utils.jax_cache import CompileTimeTracker
    x, _ = _pack(_case("tables-no-multiple-of-128"))
    monkeypatch.setattr(fops, "_on_tpu", lambda: False)
    v = jnp.ones(x.shape[1], jnp.float32)
    u = jnp.ones(x.shape[0], jnp.float32)
    first = [np.asarray(fn(x, o)) for fn, o in ((fops.matvec, v),
                                                (fops.rmatvec, u))]
    tracker = CompileTimeTracker().install()
    again = [np.asarray(fn(x, o)) for fn, o in ((fops.matvec, v),
                                                (fops.rmatvec, u))]
    assert tracker.count == 0
    for a, b in zip(first, again):
        np.testing.assert_array_equal(a, b)


def _packed_for(how, monkeypatch):
    """`(matrix, counts)` of one small matrix packed under one condition of
    the rule."""
    mat = _case("padding-slots")
    monkeypatch.setattr(fops, "CSC_MIN_COLS", 1)
    monkeypatch.setattr(fops, "_on_tpu", lambda: how != "cpu")
    with_csc = how != "several-devices"      # what a coordinate asks there
    if how == "values-not-float32":
        mat = mat.astype(np.float64)
    elif how == "under-csc-min-cols":
        monkeypatch.setattr(fops, "CSC_MIN_COLS", mat.shape[1] + 1)
    elif how == "table-over-the-budget":
        monkeypatch.setattr(fops, "VMEM_TABLE_BYTES", 4 * mat.shape[0] - 1)
    elif how == "row-wider-than-smem":
        monkeypatch.setattr(fops, "_VG_MAX_WIDTH", 4)
    return fops.pack_sparse(mat, with_csc=with_csc)


@pytest.mark.parametrize("how,products", [
    ("one-tpu-device-float32", 2), ("cpu", 0), ("several-devices", 0),
    ("values-not-float32", 0), ("under-csc-min-cols", 0),
    ("table-over-the-budget", 0), ("row-wider-than-smem", 0)])
def test_the_rule_that_decides_and_its_counter(monkeypatch, how, products):
    """`pack_sparse` lays a shard out for the kernel only where it packs
    for a TPU, the shard gets the column-sorted view, its values are
    float32 and both tables and a row's slots fit; `vmem_gather` counts the
    products a pass that then run it, and the jaxprs agree."""
    x, counts = _packed_for(how, monkeypatch)
    assert counts["vmem_gather"] == products
    assert (x.vmem_gather is not None) == bool(products)
    n, d = x.shape
    v, u = jnp.zeros(d, jnp.float32), jnp.zeros(n, jnp.float32)
    calls = sum(str(jax.make_jaxpr(fn)(x, operand)).count("pallas_call")
                for fn, operand in ((fops.matvec, v), (fops.rmatvec, u)))
    assert calls == products
    assert str(jax.make_jaxpr(fops.sq_rmatvec)(x, u)).count(
        "pallas_call") == products // 2
    if products:
        # an operand that is not float32 gets the XLA forms
        for fn, operand in ((fops.matvec, v), (fops.rmatvec, u)):
            assert "pallas_call" not in str(jax.make_jaxpr(fn)(
                x, operand.astype(jnp.bfloat16)))
        assert not fops._vmem_gather_fits(n, d, counts["ell_width"],
                                          jnp.bfloat16)


def test_a_dense_matrix_products_are_what_they_were():
    x = jnp.ones((6, 4), jnp.float32)
    v, u = jnp.ones(4, jnp.float32), jnp.ones(6, jnp.float32)
    assert str(jax.make_jaxpr(fops.matvec)(x, v)) == \
        str(jax.make_jaxpr(lambda x, v: x @ v)(x, v))
    assert str(jax.make_jaxpr(fops.rmatvec)(x, u)) == \
        str(jax.make_jaxpr(lambda x, u: x.T @ u)(x, u))
    assert str(jax.make_jaxpr(fops.sq_rmatvec)(x, u)) == \
        str(jax.make_jaxpr(lambda x, u: (x * x).T @ u)(x, u))


@pytest.mark.parametrize("packed_for", ["cpu", "tpu"])
def test_the_gauge_is_in_the_fit_result_and_the_snapshot(monkeypatch,
                                                         packed_for):
    """`vmem_gather` rides with PR 32's build counters: in
    `GameResult.coordinate_build` (which `cli.train` writes to
    `training-summary.json` as it is: tests/test_io_cli.py reads it there)
    and as the gauge `train.fe_build.<coordinate>.vmem_gather`; 0 on the
    CPU, 2 where the shard was packed for the chip, and that fit (here
    interpreted) is the XLA forms' fit."""
    from photon_ml_tpu import telemetry
    from tests.test_benchmark_sparse_fe import (clicks, coefficients, config,
                                                fit)
    monkeypatch.setattr(fops, "CSC_MIN_COLS", 100)
    ds, _, _ = clicks(300, np.float32, n=600, seed=1)
    want = coefficients(fit(ds, config(2, 0.0)))
    ds.release_device_shard("global")
    if packed_for == "tpu":
        real = fops.pack_sparse

        def pack(*args, **kwargs):
            with monkeypatch.context() as m:
                m.setattr(fops, "_on_tpu", lambda: True)
                return real(*args, **kwargs)
        monkeypatch.setattr(fops, "pack_sparse", pack)
    result = fit(ds, config(2, 0.0))
    built = result.coordinate_build["fixed"]
    assert built["vmem_gather"] == (2 if packed_for == "tpu" else 0)
    gauges = telemetry.snapshot()["metrics"]["gauges"]
    assert gauges["train.fe_build.fixed.vmem_gather"] == built["vmem_gather"]
    json.dumps(result.coordinate_build)
    np.testing.assert_allclose(coefficients(result), want, rtol=0,
                               atol=1e-5 * abs(want).max())
