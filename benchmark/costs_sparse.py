"""Bytes and operations of one value+gradient pass of a GLM over a sparse
design matrix, from its shapes alone.

A pass is priced BY THE WORK, whatever implements it: every stored
non-zero's column index and value read once, and the per-row vectors
(label, offset, weight) read once. The margins, the coefficients and the
gradient are [rows]- and [columns]-sized and small beside the non-zeros. A
program that reads the non-zeros twice (a row-major and a column-sorted
view, as `PaddedSparse` with its csc stream does) or pads them earns no
larger divisor: it reaches at most half of this roofline. Kept with the
benchmark so that no later PR can change what the share is divided by.
"""
from __future__ import annotations

#: per-row operands of a pass: label, offset, weight
ROW_VECTORS = 3


def sparse_value_grad_pass_bytes(rows: int, nnz: int, itemsize: int,
                                 index_itemsize: int = 4) -> int:
    return nnz * (index_itemsize + itemsize) + rows * ROW_VECTORS * itemsize


def sparse_value_grad_pass_flops(nnz: int) -> int:
    """A multiply-add a non-zero for the margins and one for the gradient."""
    return 4 * nnz
