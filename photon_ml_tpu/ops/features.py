"""Feature-matrix abstraction: dense arrays, sparse BCOO, implicit Kronecker.

The reference streams Breeze sparse/dense vectors per datum (reference:
photon-lib/.../data/DataPoint.scala, util/VectorUtils.scala).  On TPU the unit
of work is the whole batch: a feature matrix X of shape [n, d], either dense
(the common case after densification — e.g. a1a is d=123, the Yahoo! Music
fixture d=14,983), `jax.experimental.sparse.BCOO` when d is large and rows
are sparse, or `KroneckerDesign` — an IMPLICIT design matrix whose row i is
kron(factors_i, x_i), used by the factored-random-effect latent refit.  Every
kernel in ops/aggregators.py only touches X through the products below, so
all representations (and future pallas kernels) plug in transparently.  All
are pytrees, so they flow through jit/vmap/shard_map unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import sparse as jsparse


#: float32 products as float32 (see `KroneckerDesign`)
_EXACT = lax.Precision.HIGHEST


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class KroneckerDesign:
    """Implicit [n, k*d] design matrix with row_i = kron(factors_i, x_i).

    The reference MATERIALIZES this matrix when refitting the latent
    projection of a factored random effect — one k*d-dim dense vector per
    datum shuffled through Spark (reference: FactoredRandomEffectCoordinate
    .kroneckerProductFeaturesAndCoefficients + VectorUtils.kroneckerProduct).
    Here the products are computed directly from X [n, d] and the per-row
    latent factors C [n, k]:
      matvec(P_flat)   = ((X @ P^T) * C).sum(-1)        — two MXU matmuls
      rmatvec(u)       = (C * u[:, None])^T @ X         — one MXU matmul
    so the k*d matrix never exists and HBM traffic stays O(n(d+k)).

    The products are taken at `Precision.HIGHEST`: a TPU's default rounds a
    float32 matmul's operands to bfloat16, which a matrix-vector product
    (lowered to a multiply-reduce, exact) never meets and these
    matrix-matrix products do — the refit would train, and the model score,
    2e-2 from their float32 margins (PERF.md section 6, PR 35).  They are
    k = rank columns wide and bound by reading X, so the passes cost
    nothing that shows."""

    x: jax.Array        # [n, d]
    factors: jax.Array  # [n, k]

    def tree_flatten(self):
        return (self.x, self.factors), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self):
        return (self.x.shape[0], self.factors.shape[1] * self.x.shape[1])

    @property
    def ndim(self) -> int:
        return 2

    @property
    def dtype(self):
        return self.x.dtype

    def _unflatten_coef(self, v: jax.Array) -> jax.Array:
        return v.reshape(self.factors.shape[1], self.x.shape[1])


class VmemGather(NamedTuple):
    """What a `PaddedSparse` in the kernel's layout cannot read off its flat
    streams: its rows and its ELL width."""
    rows: int
    width: int


def _csc_fields(csc) -> dict:
    """`PaddedSparse`'s column-view fields from host `(row ids, values,
    column ends)`, none from None."""
    return {} if csc is None else dict(zip(
        ("csc_row", "csc_val", "csc_end"), map(jnp.asarray, csc)))


def _csc_of_ell(ind, val, num_cols):
    """Host: the column-sorted stream (row ids, values, column ends) of ELL
    rows.  Their padding slots (value 0 at column 0) contribute nothing to
    a segment sum, so they stay in the stream; sorted by column only."""
    import numpy as np
    rows = np.repeat(np.arange(ind.shape[0], dtype=np.int32), ind.shape[1])
    cols = ind.reshape(-1)
    order = np.argsort(cols, kind="stable")
    end = np.zeros(num_cols + 1, np.int32)
    end[1:] = np.cumsum(np.bincount(cols, minlength=num_cols))
    return rows[order], val.reshape(-1)[order], end


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PaddedSparse:
    """Padded row-sparse (ELL) batch: the TPU-native sparse format.

    Each row stores its nonzeros in `values[n, k]` at columns
    `indices[n, k]` (k = max nonzeros per row; padding slots hold index 0
    with value 0, so no mask is needed).  Every product is a dense gather or
    scatter-add with STATIC shapes — rows shard over the mesh data axis under
    GSPMD exactly like a dense batch, which BCOO (whose leaves are
    nse-leading) cannot do.  This is the product path for the reference's
    wide sparse regime (SparseVector features, AvroDataReader.scala:332-440;
    >200k-feature depth switch GameEstimator.scala:667-669).

    The optional `csc_*` arrays are a SECOND, column-sorted view of the same
    nonzeros for the gradient product X^T u: `rmatvec` gathers u by row,
    multiplies, cumsums the column-sorted stream, and differences the
    cumulative sums at column boundaries — gather, multiply, prefix-scan,
    gather: no scatter anywhere (the scatter-add it replaces serializes on
    the TPU and has not been timed on this chip).  Built by `with_csc()` or
    `from_scipy(with_csc=True)` (single-device solves); the GSPMD
    multi-device path strips them and keeps the row-shardable scatter+psum
    formulation.

    What the element gathers cost on a v5e (cell `criteo-hashed-1m.fit`:
    2.85 M rows x 39 non-zeros, 1 M columns, PERF.md sections 5 and 6):
    as XLA lowers them, `w[indices]` in `matvec` and `u[rows]` in
    `_csc_segment_sum` run at 8.0 ns a non-zero whatever the bytes, 0.89 s
    each a pass of 111 M, 89% of a value+gradient pass of 2.00 s (PR 32).
    So where `pack_sparse` can (one TPU device, float32, tables that fit
    VMEM) it lays both views out for `_vmem_segment_sums`, one Pallas
    kernel that fetches the random operand from a table held in VMEM:
    1.76 ns a non-zero by rows, 1.93 by columns, a pass 0.44 s (PR 33).
    """

    indices: jax.Array   # [n, k] int32, padding = 0
    values: jax.Array    # [n, k], padding = 0.0
    num_cols: int        # static
    csc_row: jax.Array = None    # [nnz] int32 row ids, column-sorted
    csc_val: jax.Array = None    # [nnz] values in the same order
    csc_end: jax.Array = None    # [d+1] int32: nz of column j live in
    #                              [csc_end[j], csc_end[j+1]) of the stream
    #: static; set by `pack_sparse` alone.  Where it is set the SAME two
    #: views are held as the streams the VMEM table-gather kernel reads
    #: (`_vmem_segment_sums`): `indices` / `values` flat `[n_pad * k]`, rows
    #: padded to a multiple of `_VG_BLOCK`; `csc_row` / `csc_val` with every
    #: column's run padded to a multiple of `_VG_GROUP` slots (row 0, value
    #: 0) and the stream to whole blocks of groups; `csc_end` in GROUPS
    vmem_gather: Optional[VmemGather] = None

    def tree_flatten(self):
        return ((self.indices, self.values, self.csc_row, self.csc_val,
                 self.csc_end), (self.num_cols, self.vmem_gather))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0], *children[2:],
                   vmem_gather=aux[1])

    @property
    def has_csc(self) -> bool:
        return self.csc_row is not None

    def with_csc(self) -> "PaddedSparse":
        """Attach the column-sorted gradient view (host-side prep)."""
        import numpy as np
        if self.has_csc:
            return self
        return PaddedSparse(
            self.indices, self.values, self.num_cols, **_csc_fields(
                _csc_of_ell(np.asarray(self.indices),
                            np.asarray(self.values), self.num_cols)))

    def without_csc(self) -> "PaddedSparse":
        if not self.has_csc:
            return self
        x = self.xla_forms()
        return PaddedSparse(x.indices, x.values, self.num_cols)

    def xla_forms(self) -> "PaddedSparse":
        """The same matrix as XLA's gather forms read it: `[n, k]` rows, and
        the column-sorted stream with `csc_end` in slots (the padding slots
        of a run, row 0 at value 0, add nothing to a segment sum).  `self`
        where the kernel's layout is not held."""
        lay = self.vmem_gather
        if lay is None:
            return self
        ell = lambda a: a.reshape(-1, lay.width)[:lay.rows]
        return PaddedSparse(ell(self.indices), ell(self.values),
                            self.num_cols, self.csc_row, self.csc_val,
                            self.csc_end * _VG_GROUP)

    @property
    def shape(self):
        rows = (self.indices.shape[0] if self.vmem_gather is None
                else self.vmem_gather.rows)
        return (rows, self.num_cols)

    @property
    def ndim(self) -> int:
        return 2

    @property
    def dtype(self):
        return self.values.dtype

    @staticmethod
    def from_dense(x) -> "PaddedSparse":
        import numpy as np
        x = np.asarray(x)
        nnz = np.count_nonzero(x, axis=1)
        k = max(int(nnz.max()), 1) if len(nnz) else 1
        rows, cols = np.nonzero(x)
        slot = np.arange(len(rows)) - np.repeat(
            np.concatenate([[0], np.cumsum(nnz)[:-1]]), nnz)
        indices = np.zeros((x.shape[0], k), dtype=np.int32)
        values = np.zeros((x.shape[0], k), dtype=x.dtype)
        indices[rows, slot] = cols
        values[rows, slot] = x[rows, cols]
        return PaddedSparse(jnp.asarray(indices), jnp.asarray(values), x.shape[1])

    @staticmethod
    def from_scipy(mat, with_csc: bool = False) -> "PaddedSparse":
        """scipy.sparse -> ELL (host-side, no densification).  `with_csc`
        also attaches the exact column-sorted gradient view (scipy's own
        CSC conversion — no ELL padding slots in the stream)."""
        indices, values, csc = _views_of_scipy(mat, with_csc)
        return PaddedSparse(jnp.asarray(indices), jnp.asarray(values),
                            mat.shape[1], **_csc_fields(csc))


def _views_of_scipy(mat, with_csc: bool):
    """Host: `(indices [n, k], values [n, k], csc)` of a scipy matrix, `csc`
    the column-sorted stream `(row ids, values, column ends)` or None."""
    import numpy as np
    csr = mat.tocsr()
    csr.sum_duplicates()
    nnz = np.diff(csr.indptr)
    k = max(int(nnz.max()), 1) if len(nnz) else 1
    n = csr.shape[0]
    slot = np.arange(csr.indptr[-1]) - np.repeat(csr.indptr[:-1], nnz)
    rows = np.repeat(np.arange(n), nnz)
    indices = np.zeros((n, k), dtype=np.int32)
    values = np.zeros((n, k), dtype=csr.data.dtype if csr.data.size
                      else np.float32)
    indices[rows, slot] = csr.indices
    values[rows, slot] = csr.data
    if not with_csc:
        return indices, values, None
    csc = mat.tocsc()
    csc.sum_duplicates()
    return indices, values, (csc.indices.astype(np.int32),
                             csc.data.astype(values.dtype),
                             csc.indptr.astype(np.int32))


FeatureMatrix = Union[jax.Array, jsparse.BCOO, KroneckerDesign, PaddedSparse]


# below this width the scatter-add accumulator is small enough that the
# scatter path is expected to win outright, and the csc stream would only add
# host->device transfer.  The csc side is measured (cell
# `criteo-hashed-1m.fit`, 1 M columns); the scatter side and the width
# itself are not (ROADMAP S5; both sides are held to float64 in
# tests/test_benchmark_sparse_fe.py)
CSC_MIN_COLS = 100_000

# The VMEM table gather (`_vmem_segment_sums`): a grid step takes
# `_VG_BLOCK` segments, so a block of either stream is a multiple of 1024
# scalars whatever the width; a segment of the column-sorted stream is a
# group of `_VG_GROUP` slots of one column (at 8, the runs of the cell's
# 721,687 stored columns grow the stream by 2.3%).
_VG_BLOCK = 1024
_VG_GROUP = 8
_LANES = 128
# The largest table a product may hold in VMEM: the pipeline gives the
# table two buffers although its block never moves, so 2 x 48 MiB = 96 MiB
# of a v5e's 128 MiB, the rest for the lane partials of a block (0.5 MiB),
# the output blocks and the compiler.  `w` of the cell is 4 MB (1,000,000
# columns), `u` 11.4 MB (2,850,000 rows); 12.5 M rows or columns fit.
VMEM_TABLE_BYTES = 48 << 20
_VMEM_HEADROOM_BYTES = 16 << 20
# The widest ELL row: the index and the value block of a grid step, two
# buffers each, are 4 x 1024 x width x 4 B = 16 KiB x width of a v5e's
# 1 MiB of SMEM (at 64 the compile runs out by 1.1 KB); the cell's 39 take
# 624 KiB.
_VG_MAX_WIDTH = 56


def _on_tpu() -> bool:
    """Whether arrays made now land on a TPU: what `pack_sparse` packs
    for.  A matrix in the kernel's layout that finds itself elsewhere (a
    test steers it there) runs the same kernel interpreted."""
    return jax.default_backend() == "tpu"


def _vmem_gather_fits(rows: int, cols: int, width: int, dtype) -> bool:
    """The part of `pack_sparse`'s rule that shapes decide: float32 values,
    a row's slots within `_VG_MAX_WIDTH`, and both tables (`w` [cols] for
    X w, `u` [rows] for X^T u) within `VMEM_TABLE_BYTES` as the kernel
    holds them, whole rows of 128 lanes."""
    import numpy as np
    table = lambda m: -(-m // _LANES) * _LANES * 4
    return (np.dtype(dtype) == np.float32 and width <= _VG_MAX_WIDTH
            and max(table(rows), table(cols)) <= VMEM_TABLE_BYTES)


def is_scipy_sparse(x) -> bool:
    try:
        import scipy.sparse as sp
    except ImportError:
        return False
    return sp.issparse(x)


def pack_sparse(host=None, cached: Optional["PaddedSparse"] = None,
                with_csc: bool = False):
    """The one place a sparse shard is packed for the device, and the owner
    of its span: `(matrix, counts)`, `counts` None where `cached` already
    is what is asked for and nothing ran.

    `host` is the scipy matrix (None once released), `cached` a device copy
    made earlier.  A matrix of `CSC_MIN_COLS` columns or more gets the
    column-sorted view when `with_csc` asks for it.  Whatever is missing is
    packed from `host` (ELL rows plus scipy's own column-sorted view: the
    stream holds the stored non-zeros only) inside a `photon/fe/pack`
    annotation, so the stream does not depend on who touched the shard
    first; only where the host copy is gone is the view sorted out of the
    cached rows read back from the device (its stream keeps their padding
    slots, which add nothing to a segment sum).

    **Which layout.**  Both views are laid out for the VMEM table-gather
    kernel (`PaddedSparse.vmem_gather`; `matvec`, `rmatvec`, `sq_rmatvec`
    then run it in place of XLA's element gathers) where all of this holds:
    the shard is packed for a TPU, it gets the column-sorted view (so one
    device, `CSC_MIN_COLS` columns or more), its values are float32, both
    tables fit `VMEM_TABLE_BYTES` and a row's slots `_VG_MAX_WIDTH`.
    Anything else is packed for the XLA forms as before.  Decided here,
    once, from what the pack can see; no option chooses it.

    `counts` is what was made, from the arrays in hand on the host: rows,
    cols, nnz (stored values that are not zero), ell_width, padded_slots
    (of the ELL rows, whatever the layout), csc, vmem_gather (0, or the 2
    products a pass that run the kernel), device_bytes, pack_s."""
    import numpy as np
    from photon_ml_tpu.telemetry import annotate, clock
    num_cols = cached.num_cols if host is None else host.shape[1]
    want_csc = with_csc and num_cols >= CSC_MIN_COLS
    if cached is not None and (cached.has_csc or not want_csc):
        return cached, None
    t0 = clock()
    with annotate("fe/pack"):
        if host is None:
            indices = np.asarray(cached.indices)
            values = np.asarray(cached.values)
            csc = _csc_of_ell(indices, values, num_cols)
            nnz = np.count_nonzero(values)
        else:
            csr = host.tocsr()
            csr.sum_duplicates()
            indices, values, csc = _views_of_scipy(csr, want_csc)
            nnz = np.count_nonzero(csr.data)
        rows, width = indices.shape
        layout = None
        if want_csc and _on_tpu() and _vmem_gather_fits(
                rows, num_cols, width, values.dtype):
            layout = VmemGather(rows, width)
            indices, values, csc = _kernel_streams(indices, values, csc)
        elif host is None:      # the rows are on the device already
            indices, values = cached.indices, cached.values
        x = PaddedSparse(jnp.asarray(indices), jnp.asarray(values),
                         num_cols, vmem_gather=layout, **_csc_fields(csc))
    return x, {
        "rows": int(rows), "cols": int(num_cols), "nnz": int(nnz),
        "ell_width": int(width), "padded_slots": int(rows * width - nnz),
        "csc": int(x.has_csc), "vmem_gather": 2 * int(layout is not None),
        "device_bytes": sum(int(leaf.nbytes)
                            for leaf in jax.tree_util.tree_leaves(x)),
        "pack_s": float(clock() - t0)}


def _bucketed_blocks(blocks: int) -> int:
    """`blocks` rounded up to a power-of-two granule of about a thousandth
    of it.  How many groups the padded column runs make follows the data
    (the cell's 721,687 columns: 13,901 or 13,902 blocks by seed), and a
    program is compiled for a length; so shards of nearly one size share
    their programs, for at most 0.1% more slots."""
    granule = 1 << max(0, blocks.bit_length() - 11)
    return -(-blocks // granule) * granule


def _kernel_streams(indices, values, csc):
    """Host: both views as the streams `_vmem_segment_sums` reads.  Rows:
    the ELL slots flat, zero rows appended to a whole block.  Columns:
    every column's run padded to whole groups of `_VG_GROUP` slots (row 0,
    value 0), so no group straddles a column, the stream padded to whole
    blocks of groups (`_bucketed_blocks` of them), and the column ends
    counted in groups."""
    import numpy as np
    rows, width = indices.shape
    pad = (-rows) % _VG_BLOCK
    flat = lambda a: np.concatenate(
        [a.reshape(-1), np.zeros(pad * width, a.dtype)])
    csc_row, csc_val, end = csc
    run = np.diff(end)
    groups = -(-run // _VG_GROUP)
    group_end = np.zeros(len(end), np.int64)
    np.cumsum(groups, out=group_end[1:])
    total = _bucketed_blocks(-(-int(group_end[-1]) // _VG_BLOCK)) * _VG_BLOCK
    assert total * _VG_GROUP < 2 ** 31
    # where a stored slot lands: its column's new start plus its place in
    # the run
    slot = np.repeat(group_end[:-1] * _VG_GROUP - end[:-1], run)
    slot += np.arange(len(csc_row), dtype=np.int64)
    out_row = np.zeros(total * _VG_GROUP, np.int32)
    out_val = np.zeros(total * _VG_GROUP, csc_val.dtype)
    out_row[slot] = csc_row
    out_val[slot] = csc_val
    return flat(indices), flat(values), (out_row, out_val,
                                         group_end.astype(np.int32))


def as_feature_matrix(x, with_csc: bool = False) -> FeatureMatrix:
    """Ingest adapter: scipy.sparse -> PaddedSparse, everything else as-is
    (dense arrays pass through jnp.asarray).  `with_csc` attaches the
    column-sorted gradient view to WIDE sparse inputs (single-device
    solves, >= CSC_MIN_COLS features)."""
    if isinstance(x, PaddedSparse):
        return pack_sparse(cached=x, with_csc=with_csc)[0]
    if isinstance(x, (jsparse.BCOO, KroneckerDesign)):
        return x
    if is_scipy_sparse(x):
        return pack_sparse(x, with_csc=with_csc)[0]
    return jnp.asarray(x)


def is_sparse(x: FeatureMatrix) -> bool:
    return isinstance(x, jsparse.BCOO)


def num_features(x: FeatureMatrix) -> int:
    return x.shape[-1]


def num_rows(x: FeatureMatrix) -> int:
    return x.shape[0]


def matvec(x: FeatureMatrix, v: jax.Array) -> jax.Array:
    """X @ v -> [n].  The margin kernel."""
    if isinstance(x, KroneckerDesign):
        p = x._unflatten_coef(v)
        return jnp.sum(jnp.matmul(x.x, p.T, precision=_EXACT) * x.factors,
                       axis=-1)
    if isinstance(x, PaddedSparse):
        if _runs_vmem_gather(x, v):
            lay = x.vmem_gather
            return _vmem_segment_sums(x.indices, x.values, v,
                                      lay.width)[:lay.rows]
        x = x.xla_forms()
        # indices are constructed in-bounds (from_dense/from_scipy), so the
        # clamp/fill handling of the default gather is dead weight —
        # promise_in_bounds halves the gather time on the TPU at wide d
        g = v.at[x.indices].get(mode="promise_in_bounds")
        return jnp.sum(x.values * g, axis=-1)
    return x @ v


def _runs_vmem_gather(x: "PaddedSparse", operand: jax.Array) -> bool:
    """The kernel takes a product where the pack laid the matrix out for it
    and the operand is float32 as the values are; any other operand gets
    the XLA forms over the same streams."""
    return x.vmem_gather is not None and operand.dtype == jnp.float32


def _vmem_segment_sums(idx: jax.Array, val: jax.Array, table: jax.Array,
                       width: int) -> jax.Array:
    """out[s] = sum_k val[s * width + k] * table[idx[s * width + k]], float32
    (`_vmem_segment_sums_program`, compiled for the TPU or, elsewhere,
    interpreted)."""
    return _vmem_segment_sums_program(idx, val, table, width=width,
                                      interpret=not _on_tpu())


# jitted on its own: a product called outside any jit (a one-device
# coordinate's scoring is) would otherwise build, and compile, a new kernel
# every call
@functools.partial(jax.jit, static_argnames=("width", "interpret"))
def _vmem_segment_sums_program(idx, val, table, *, width: int,
                               interpret: bool):
    """out[s] = sum_k val[s * width + k] * table[idx[s * width + k]], float32.

    The random operand of a sparse product, fetched from a table held in
    VMEM by ONE Pallas kernel in place of XLA's element gather (which costs
    about twelve core cycles an INDEX on a v5e whatever the bytes: 8.04 ns,
    PERF.md section 6, PR 33).  `idx` and `val` are flat streams in SMEM
    blocks of `_VG_BLOCK` segments of `width` slots; the table is whole in
    VMEM as `[ceil(m / 128), 128]`.  An element costs no gather primitive:
    the index is a scalar, `table[i >> 7]` is an ordinary vector load of
    one row at a dynamic sublane offset, and the lane `i & 127` is picked
    by a compare (taken in the vector unit: the scalar slots are what bound
    the loop).  A segment's products add up lane by lane in float32; the
    128 lane partials of each of a block's segments are summed by one
    float32 (`HIGHEST`) product with ones on the MXU, which leaves the sums
    lane-dense.  The two segmentations: a row of the padded-row view
    (`matvec`), a group of `_VG_GROUP` slots of one column of the
    column-sorted view (`rmatvec`, `sq_rmatvec`)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    segments = idx.shape[0] // width
    assert segments % _VG_BLOCK == 0, (idx.shape, width)
    m = table.shape[0]
    height = -(-m // _LANES)
    tab = jnp.pad(table, (0, height * _LANES - m)).reshape(height, _LANES)
    i32 = jnp.int32
    # segments a trip of the loop, a power of two: the scheduler overlaps
    # the scalar work of what one trip holds, and wants about 160 elements
    # (my chip runs, PR 33, ns an element: groups of 8 at 1 / 4 / 16 a trip
    # 2.91 / 2.12 / 1.93; rows of 39 at 1 / 2 / 4 a trip 1.93 / 1.82 / 1.76)
    unroll = 1 << max(0, (160 // width).bit_length() - 1)

    def kernel(idx_ref, val_ref, tab_ref, out_ref, part_ref):
        lane = lax.broadcasted_iota(i32, (1, _LANES), 1)

        def segment(s):
            acc = jnp.zeros((1, _LANES), jnp.float32)
            base = s * i32(width)
            for k in range(width):
                i = idx_ref[base + i32(k)]
                row = tab_ref[pl.ds(i >> i32(7), 1), :]
                hit = lane == (jnp.full((1, _LANES), i, i32) & i32(127))
                acc = acc + (jnp.where(hit, row, jnp.float32(0))
                             * val_ref[base + i32(k)])
            part_ref[pl.ds(s, 1), :] = acc

        def trip(t, carry):
            for j in range(unroll):
                segment(t * i32(unroll) + i32(j))
            return carry

        lax.fori_loop(i32(0), i32(_VG_BLOCK // unroll), trip, i32(0))
        sums = lax.dot_general(
            jnp.ones((8, _LANES), jnp.float32), part_ref[...],
            (((1,), (1,)), ((), ())), precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        out_ref[...] = sums[0:1, :].reshape(1, 1, _VG_BLOCK)

    block = _VG_BLOCK * width
    stream = lambda: pl.BlockSpec((block,), lambda b: (b,),
                                  memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        kernel, grid=(segments // _VG_BLOCK,),
        in_specs=[stream(), stream(),
                  pl.BlockSpec((height, _LANES), lambda b: (0, 0))],
        out_specs=pl.BlockSpec((1, 1, _VG_BLOCK), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (segments // _VG_BLOCK, 1, _VG_BLOCK), jnp.float32),
        scratch_shapes=[pltpu.VMEM((_VG_BLOCK, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * height * _LANES * 4 + _VMEM_HEADROOM_BYTES),
        interpret=interpret, name="vmem_gather")(idx, val, tab)
    return out.reshape(-1)


# The VMEM table gather of single elements (`vmem_take`): cells a grid step,
# and cells a trip of its loop, one lane-dense output row
_VT_BLOCK = 8192
_VT_TRIP = _LANES


def vmem_take_fits(m: int, dtype) -> bool:
    """Whether `vmem_take` can hold a table of `m` entries of `dtype`:
    float32, and the table with the zero entry past its end within
    `VMEM_TABLE_BYTES` as whole rows of 128 lanes."""
    import numpy as np
    return (np.dtype(dtype) == np.float32
            and (m // _LANES + 1) * _LANES * 4 <= VMEM_TABLE_BYTES)


def vmem_take_cells(cells: int) -> int:
    """The length of an index stream `vmem_take` reads for `cells` cells:
    whole grid steps."""
    return -(-cells // _VT_BLOCK) * _VT_BLOCK


@functools.partial(jax.jit, static_argnames=("interpret",))
def vmem_take(table: jax.Array, idx: jax.Array, *,
              interpret: bool) -> jax.Array:
    """out[c] = table[idx[c]], float32; an index of `len(table)` reads 0.

    A gather of single elements (an entity coordinate's offsets put into
    bucket layout), in the form of `_vmem_segment_sums_program`, whose
    lane partials summed on the MXU would cost every element what a
    segment of 8-39 shares:
    the table is whole in VMEM as `[m // 128 + 1, 128]` (zero past its
    end), `idx` is a flat stream in SMEM blocks of `_VT_BLOCK` cells
    (`vmem_take_cells` long), an element is a scalar index, an ordinary
    vector load of one `(1, 128)` row at a dynamic sublane offset and its
    lane `i & 127` kept by a compare.  No product places the elements: the
    kept rows of a trip's 128 cells fill a `[128, 128]` tile, summed over
    lanes (one non-zero a row) and transposed into the trip's output row,
    lane-dense.  On a TPU v5e (streams of 3.3-9.5 M cells over 7.6 M rows,
    PERF.md section 6) 2.55 ns a cell, where XLA's gather takes 6.66 and the
    other placements 2.65 (the tile transposed, then summed over
    sublanes), 2.78 (a float32 product with ones on the MXU) and 2.92 (a
    lane roll a cell), and the fetch alone, placed nowhere, 1.43; the grid
    step's length (1,024 to 32,768 cells) moves nothing.  Interpreted off
    the TPU."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    cells = idx.shape[0]
    assert cells % _VT_BLOCK == 0, cells
    m = table.shape[0]
    height = m // _LANES + 1
    tab = jnp.pad(table, (0, height * _LANES - m)).reshape(height, _LANES)
    i32 = jnp.int32

    def kernel(idx_ref, tab_ref, out_ref, part_ref):
        # the trip's 128 cells are traced one by one: in `lax` terms, whose
        # binds cost a fraction of `jnp`'s, since every process traces and
        # lowers the kernel anew for each coordinate (no cache saves that)
        lane = lax.broadcasted_iota(i32, (1, _LANES), 1)
        lane_bits = jnp.full((1, _LANES), _LANES - 1, i32)
        zero = jnp.zeros((1, _LANES), jnp.float32)

        def trip(t, carry):
            base = lax.mul(t, i32(_VT_TRIP))
            for j in range(_VT_TRIP):
                i = idx_ref[lax.add(base, i32(j))]
                row = tab_ref[pl.ds(lax.shift_right_arithmetic(i, i32(7)), 1),
                              :]
                hit = lax.eq(lane, lax.bitwise_and(
                    lax.broadcast(i, (1, _LANES)), lane_bits))
                part_ref[j:j + 1, :] = lax.select(hit, row, zero)
            out_ref[pl.ds(t, 1), :] = jnp.sum(part_ref[...], axis=1,
                                              keepdims=True).T
            return carry

        lax.fori_loop(i32(0), i32(_VT_BLOCK // _VT_TRIP), trip, i32(0))

    rows = _VT_BLOCK // _LANES
    out = pl.pallas_call(
        kernel, grid=(cells // _VT_BLOCK,),
        in_specs=[pl.BlockSpec((_VT_BLOCK,), lambda b: (b,),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((height, _LANES), lambda b: (0, 0))],
        out_specs=pl.BlockSpec((rows, _LANES), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((cells // _LANES, _LANES),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((_VT_TRIP, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=2 * height * _LANES * 4 + _VMEM_HEADROOM_BYTES),
        interpret=interpret, name="vmem_take")(idx, tab)
    return out.reshape(-1)


_CSC_CHUNK = 1 << 16


def _csc_segment_sum(vals: jax.Array, rows: jax.Array, end: jax.Array,
                     u: jax.Array) -> jax.Array:
    """sum_j vals_j * u[rows_j] per column, for a column-sorted stream.

    The XLA form: gather -> multiply -> CHUNKED prefix-scan -> boundary
    gather — every op is a TPU-parallel primitive.  On a v5e the gather
    `u[rows]` is most of it: 8.0 ns an element, 0.89 s a call over 111 M
    non-zeros, where the scan and its neighbours take about 0.1 s and the
    boundary gathers over 1 M columns 0.04 s (cell `criteo-hashed-1m.fit`
    at PR 32, PERF.md section 6).  It is what a shard runs that
    `pack_sparse` did not lay out for the VMEM table gather, which takes
    the gather and the multiply (`rmatvec`) and leaves `_boundary_sums`.
    The scatter-add this replaces has no chip record (ROADMAP S5).

    Chunking is a precision device, not a speed one: a single global
    cumsum accumulates ~eps*sqrt(nnz) rounding noise into every boundary
    difference, which slowed LBFGS convergence (61 iterations
    against 34 on the exact path in a pre-round record).  With the scan restarted per
    64k-element chunk, a column contained in one chunk — the overwhelming
    case at realistic column counts — differences two LOCAL prefixes and
    the cross-chunk terms cancel EXACTLY (identical floats), so its error
    is ~eps*sqrt(chunk) instead; only the rare chunk-spanning column sees
    the coarse chunk-total prefix."""
    contrib = vals * u.at[rows].get(mode="promise_in_bounds")
    return _boundary_sums(
        contrib.astype(jnp.promote_types(vals.dtype, u.dtype)), end)


def _boundary_sums(contrib: jax.Array, end: jax.Array) -> jax.Array:
    """out[j] = sum of contrib[end[j]:end[j + 1]]: the chunked prefix scan
    and the boundary differences of `_csc_segment_sum`, over the products
    of the stream or (the kernel's layout) over its group sums with `end`
    in groups."""
    acc = contrib.dtype
    nnz = contrib.shape[0]
    L = _CSC_CHUNK
    C = -(-max(nnz, 1) // L)
    local = jnp.cumsum(
        jnp.pad(contrib, (0, C * L - nnz)).reshape(C, L), axis=1)
    # chunk_pref[c] = exact-ish sum of all chunks before c (small array:
    # its own rounding enters only chunk-SPANNING columns)
    chunk_pref = jnp.concatenate(
        [jnp.zeros((1,), acc), jnp.cumsum(local[:, -1])])

    def local_prefix(p):
        """Within-chunk inclusive prefix of the first p%L elements of
        chunk p//L, and the chunk index."""
        c, r = p // L, p % L
        # p == nnz == C*L makes c == C with r == 0: the select discards the
        # gathered value, but the row index must still honor the in-bounds
        # promise (both branches execute)
        loc = jnp.where(
            r > 0,
            local.at[jnp.minimum(c, C - 1),
                     jnp.maximum(r - 1, 0)].get(mode="promise_in_bounds"),
            jnp.zeros((), acc))
        return c, loc

    # one prefix a boundary (end[j] closes column j - 1 and opens column
    # j): d + 1 element gathers of each kind, not 2 d
    c, loc = local_prefix(end)
    pref = chunk_pref.at[c].get(mode="promise_in_bounds")
    # ORDER MATTERS for the exactness claim: the local difference and the
    # chunk-prefix difference are formed separately — for a same-chunk
    # column the latter is x - x == 0.0 exactly, so no large prefix ever
    # touches the local result
    return (loc[1:] - loc[:-1]) + (pref[1:] - pref[:-1])


def rmatvec(x: FeatureMatrix, u: jax.Array) -> jax.Array:
    """X^T @ u -> [d].  The gradient-assembly kernel."""
    if isinstance(x, KroneckerDesign):
        return jnp.matmul((x.factors * u[:, None]).T, x.x,
                          precision=_EXACT).reshape(-1)
    if isinstance(x, PaddedSparse):
        if _runs_vmem_gather(x, u):
            return _boundary_sums(_vmem_segment_sums(
                x.csc_row, x.csc_val, u, _VG_GROUP), x.csc_end)
        x = x.xla_forms()
        if x.has_csc:
            return _csc_segment_sum(x.csc_val, x.csc_row, x.csc_end, u)
        # GSPMD multi-device fallback: per-shard scatter-add + psum.
        # Accumulate in the PROMOTED dtype: with bf16 feature storage the
        # contrib product is f32 and the gradient must not round through a
        # bf16 buffer (the solver state is f32)
        contrib = (x.values * u[:, None]).reshape(-1)
        acc = jnp.promote_types(x.dtype, u.dtype)
        return jnp.zeros(x.num_cols, acc).at[x.indices.reshape(-1)].add(
            contrib, mode="promise_in_bounds")
    if is_sparse(x):
        # BCOO transpose-matvec: (u @ X) contracts over rows.
        return u @ x
    return x.T @ u


def sq_rmatvec(x: FeatureMatrix, u: jax.Array) -> jax.Array:
    """(X*X)^T @ u -> [d].  Used by the Hessian-diagonal aggregator
    (reference: photon-lib/.../function/glm/HessianDiagonalAggregator.scala:33)."""
    if isinstance(x, KroneckerDesign):
        # kron(c, x)^2 == kron(c^2, x^2)
        f2 = x.factors * x.factors
        return jnp.matmul((f2 * u[:, None]).T, x.x * x.x,
                          precision=_EXACT).reshape(-1)
    if isinstance(x, PaddedSparse):
        if _runs_vmem_gather(x, u):
            return _boundary_sums(_vmem_segment_sums(
                x.csc_row, x.csc_val * x.csc_val, u, _VG_GROUP), x.csc_end)
        x = x.xla_forms()
        if x.has_csc:
            return _csc_segment_sum(x.csc_val * x.csc_val, x.csc_row,
                                    x.csc_end, u)
        contrib = (x.values * x.values * u[:, None]).reshape(-1)
        acc = jnp.promote_types(x.dtype, u.dtype)
        return jnp.zeros(x.num_cols, acc).at[x.indices.reshape(-1)].add(
            contrib, mode="promise_in_bounds")
    if is_sparse(x):
        x2 = jsparse.BCOO((x.data * x.data, x.indices), shape=x.shape,
                          indices_sorted=x.indices_sorted, unique_indices=x.unique_indices)
        return u @ x2
    return (x * x).T @ u


def pad_rows(x: FeatureMatrix, rem: int) -> FeatureMatrix:
    """Append `rem` zero rows (mesh-alignment padding; pair with mask=0)."""
    if rem == 0:
        return x
    zpad = lambda a: jnp.concatenate(
        [a, jnp.zeros((rem,) + a.shape[1:], a.dtype)])
    if isinstance(x, KroneckerDesign):
        return KroneckerDesign(zpad(x.x), zpad(x.factors))
    if isinstance(x, PaddedSparse):
        # the csc stream is untouched: appended rows carry no nonzeros and
        # existing row ids stay valid against the grown u.  (The kernel's
        # layout is one device's, where no mesh asks for a remainder; a
        # matrix that is padded all the same goes on in the XLA forms.)
        x = x.xla_forms()
        return PaddedSparse(zpad(x.indices), zpad(x.values), x.num_cols,
                            x.csc_row, x.csc_val, x.csc_end)
    if is_sparse(x):
        # all-zero rows need no stored elements: only the shape grows
        return jsparse.BCOO((x.data, x.indices), shape=(x.shape[0] + rem,) +
                            tuple(x.shape[1:]), indices_sorted=x.indices_sorted,
                            unique_indices=x.unique_indices)
    return zpad(x)


def densify(x: FeatureMatrix) -> jax.Array:
    if isinstance(x, KroneckerDesign):
        return jax.vmap(jnp.kron)(x.factors, x.x)
    if isinstance(x, PaddedSparse):
        x = x.xla_forms()
        n, d = x.shape
        return jnp.zeros((n, d), x.dtype).at[
            jnp.arange(n)[:, None], x.indices].add(x.values)
    return x.todense() if is_sparse(x) else x
