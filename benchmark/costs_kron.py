"""Bytes and operations of one value+gradient pass of the GLM that refits a
factored random effect's shared projection `P` [k, d], from its shapes alone.

The design row of an active row i of entity e is kron(c_e, x_i): its margin
is c_e . (P x_i) and its share of the gradient the outer product of c_e and
x_i. A pass is priced BY THE WORK, whatever implements it: each REAL active
row's d features and its label, offset and weight read once, each entity's k
factors read once, and a multiply-add per row and coefficient for the margin
and one for the gradient. `P`, the margins and the gradient are [k, d]- and
[rows]-sized and need not leave the chip. A program that pads the rows to
blocks, repeats the factors to one copy a row, or reads the features once
for the margin and once for the gradient earns no larger divisor: it reaches
that much less of this roofline. Kept with the benchmark so that no later PR
can change what the share is divided by.
"""
from __future__ import annotations

#: per-row operands of a pass: label, offset, weight
ROW_VECTORS = 3


def kron_value_grad_pass_bytes(real_rows: int, entities: int, width: int,
                               latent_dim: int, itemsize: int) -> int:
    return (real_rows * (width + ROW_VECTORS)
            + entities * latent_dim) * itemsize


def kron_value_grad_pass_flops(real_rows: int, width: int,
                               latent_dim: int) -> int:
    """A multiply-add a row and coefficient for the margins, one for the
    gradient: 4 k d operations a row."""
    return 4 * real_rows * latent_dim * width
