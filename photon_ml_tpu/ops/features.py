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
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax.experimental import sparse as jsparse


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
    so the k*d matrix never exists and HBM traffic stays O(n(d+k))."""

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

    What this costs on a v5e (cell `criteo-hashed-1m.fit`: 2.85 M rows x 39
    non-zeros, 1 M columns, PERF.md section 5): both element gathers,
    `w[indices]` in `matvec` and `u[rows]` in `_csc_segment_sum`, run at
    8.0 ns a non-zero, 0.89 s each a pass of 111 M, and are 89% of a
    value+gradient pass of 2.00 s; reading every non-zero once at the HBM
    peak would take 0.056% of that pass (`fe_sparse_roofline.fit`).  The
    multiplies, the chunked prefix scan and the boundary gathers are the
    other 11%.
    """

    indices: jax.Array   # [n, k] int32, padding = 0
    values: jax.Array    # [n, k], padding = 0.0
    num_cols: int        # static
    csc_row: jax.Array = None    # [nnz] int32 row ids, column-sorted
    csc_val: jax.Array = None    # [nnz] values in the same order
    csc_end: jax.Array = None    # [d+1] int32: nz of column j live in
    #                              [csc_end[j], csc_end[j+1]) of the stream

    def tree_flatten(self):
        return ((self.indices, self.values, self.csc_row, self.csc_val,
                 self.csc_end), self.num_cols)

    @classmethod
    def tree_unflatten(cls, num_cols, children):
        return cls(children[0], children[1], num_cols, *children[2:])

    @property
    def has_csc(self) -> bool:
        return self.csc_row is not None

    def with_csc(self) -> "PaddedSparse":
        """Attach the column-sorted gradient view (host-side prep)."""
        import numpy as np
        if self.has_csc:
            return self
        ind = np.asarray(self.indices)
        val = np.asarray(self.values)
        rows = np.repeat(np.arange(ind.shape[0], dtype=np.int32),
                         ind.shape[1])
        cols = ind.reshape(-1)
        vals = val.reshape(-1)
        # ELL padding slots (value 0 at column 0) contribute nothing to the
        # segment sums, so they can stay in the stream; sort by column only
        order = np.argsort(cols, kind="stable")
        cols_sorted = cols[order]
        end = np.zeros(self.num_cols + 1, np.int32)
        end[1:] = np.cumsum(np.bincount(cols_sorted,
                                        minlength=self.num_cols))
        return PaddedSparse(
            self.indices, self.values, self.num_cols,
            csc_row=jnp.asarray(rows[order]),
            csc_val=jnp.asarray(vals[order]),
            csc_end=jnp.asarray(end))

    def without_csc(self) -> "PaddedSparse":
        return (PaddedSparse(self.indices, self.values, self.num_cols)
                if self.has_csc else self)

    @property
    def shape(self):
        return (self.indices.shape[0], self.num_cols)

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
        out = PaddedSparse(jnp.asarray(indices), jnp.asarray(values),
                           csr.shape[1])
        if with_csc:
            csc = mat.tocsc()
            csc.sum_duplicates()
            out = PaddedSparse(
                out.indices, out.values, out.num_cols,
                csc_row=jnp.asarray(csc.indices.astype(np.int32)),
                csc_val=jnp.asarray(csc.data.astype(values.dtype)),
                csc_end=jnp.asarray(csc.indptr.astype(np.int32)))
        return out


FeatureMatrix = Union[jax.Array, jsparse.BCOO, KroneckerDesign, PaddedSparse]


# below this width the scatter-add accumulator is small enough that the
# scatter path is expected to win outright, and the csc stream would only add
# host->device transfer.  The csc side is measured (cell
# `criteo-hashed-1m.fit`, 1 M columns); the scatter side and the width
# itself are not (ROADMAP S5; both sides are held to float64 in
# tests/test_benchmark_sparse_fe.py)
CSC_MIN_COLS = 100_000


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
    slots, which add nothing to a segment sum).  `counts` is what was
    made, from the arrays in hand on the host: rows, cols, nnz (stored
    values that are not zero), ell_width, padded_slots, csc, device_bytes,
    pack_s."""
    import numpy as np
    from photon_ml_tpu.telemetry import annotate, clock
    num_cols = cached.num_cols if host is None else host.shape[1]
    want_csc = with_csc and num_cols >= CSC_MIN_COLS
    if cached is not None and (cached.has_csc or not want_csc):
        return cached, None
    t0 = clock()
    with annotate("fe/pack"):
        if host is None:
            x = cached.with_csc()
            nnz = np.count_nonzero(np.asarray(cached.values))
        else:
            csr = host.tocsr()
            csr.sum_duplicates()
            x = PaddedSparse.from_scipy(csr, with_csc=want_csc)
            nnz = np.count_nonzero(csr.data)
    rows, width = x.indices.shape
    return x, {
        "rows": int(rows), "cols": int(num_cols), "nnz": int(nnz),
        "ell_width": int(width), "padded_slots": int(rows * width - nnz),
        "csc": int(x.has_csc),
        "device_bytes": sum(int(leaf.nbytes)
                            for leaf in jax.tree_util.tree_leaves(x)),
        "pack_s": float(clock() - t0)}


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
        return jnp.sum((x.x @ p.T) * x.factors, axis=-1)
    if isinstance(x, PaddedSparse):
        # indices are constructed in-bounds (from_dense/from_scipy), so the
        # clamp/fill handling of the default gather is dead weight —
        # promise_in_bounds halves the gather time on the TPU at wide d
        g = v.at[x.indices].get(mode="promise_in_bounds")
        return jnp.sum(x.values * g, axis=-1)
    return x @ v


_CSC_CHUNK = 1 << 16


def _csc_segment_sum(vals: jax.Array, rows: jax.Array, end: jax.Array,
                     u: jax.Array) -> jax.Array:
    """sum_j vals_j * u[rows_j] per column, for a column-sorted stream.

    Formulated as gather -> multiply -> CHUNKED prefix-scan -> boundary
    gather — every op is a TPU-parallel primitive.  On a v5e the gather
    `u[rows]` is most of it: 8.0 ns an element, 0.89 s a call over 111 M
    non-zeros, where the scan and its neighbours take about 0.1 s and the
    boundary gathers over 1 M columns 0.03 s (cell `criteo-hashed-1m.fit`,
    PERF.md section 5).  The scatter-add this replaces has no chip record
    (ROADMAP S5).

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
    acc = jnp.promote_types(vals.dtype, u.dtype)
    contrib = contrib.astype(acc)
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

    c1, loc1 = local_prefix(end[1:])
    c0, loc0 = local_prefix(end[:-1])
    # ORDER MATTERS for the exactness claim: the local difference and the
    # chunk-prefix difference are formed separately — for a same-chunk
    # column the latter is x - x == 0.0 exactly, so no large prefix ever
    # touches the local result
    cross = (chunk_pref.at[c1].get(mode="promise_in_bounds")
             - chunk_pref.at[c0].get(mode="promise_in_bounds"))
    return (loc1 - loc0) + cross


def rmatvec(x: FeatureMatrix, u: jax.Array) -> jax.Array:
    """X^T @ u -> [d].  The gradient-assembly kernel."""
    if isinstance(x, KroneckerDesign):
        return ((x.factors * u[:, None]).T @ x.x).reshape(-1)
    if isinstance(x, PaddedSparse):
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
        return ((f2 * u[:, None]).T @ (x.x * x.x)).reshape(-1)
    if isinstance(x, PaddedSparse):
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
        # existing row ids stay valid against the grown u
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
        n, d = x.shape
        return jnp.zeros((n, d), x.dtype).at[
            jnp.arange(n)[:, None], x.indices].add(x.values)
    return x.todense() if is_sparse(x) else x
