"""Operations and bytes a kernel has to move, from its shapes alone.

Kept with the benchmark so that no later PR can change what a roofline share
is divided by.
"""
from __future__ import annotations


def value_grad_pass_bytes(rows: int, width: int, itemsize: int) -> int:
    """Bytes one fused value+gradient pass of a dense GLM has to read: the
    feature matrix and the labels, once. The margins, the coefficients and
    the gradient are [rows]- and [width]-sized and need not leave the chip,
    so a pass that reads the matrix twice reaches at most half this roofline."""
    return rows * (width + 1) * itemsize


def value_grad_pass_flops(rows: int, width: int) -> int:
    """Multiply-adds of the margin and the gradient matvec, 2 flops each."""
    return 4 * rows * width


def roofline_seconds(nbytes: float, flops: float, peak: dict) -> float:
    """The least time the chip could take: the larger of bytes over peak
    bytes/s and operations over peak FLOP/s."""
    return max(nbytes / peak["hbm_bytes_per_s"],
               flops / peak["bf16_flops_per_s"])
