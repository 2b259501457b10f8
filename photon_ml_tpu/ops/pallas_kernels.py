"""Pallas TPU kernel: single-pass fused GLM value+gradient (measured experiment).

Hypothesis: the XLA aggregator (ops/aggregators.py:value_and_gradient)
needs two X-reads per call — margins z = X c, then gradient assembly
g = X^T (w l'(z)) — so a row-tiled kernel computing both from the same
resident [T, d] tile should halve HBM traffic.

Measured result (v5e, in-loop fori_loop timing that amortizes dispatch):
XLA WINS — 4.2 vs 10.8 ms/pass at [1.64M, 124] and 6.7 vs 10.3 ms/pass at
[200k, 2048].  XLA's fusion already streams matvec-shaped chains in one
pass (matvecs lower to VPU reductions, which fuse through the pointwise
loss into the second reduction), so the premise only holds for shapes
where the margin contraction must be a real MXU matmul.  Per the build
guidance — let XLA fuse, don't hand-schedule what the compiler already
does — the product path stays on the XLA aggregator everywhere.

The kernel is kept as the working Pallas recipe for this codebase
(layouts, accumulation across sequential grid steps, Mosaic constraints),
verified equal to the XLA path by tests/test_pallas_kernel.py:

  - per-row vectors travel as [n, 1] columns so each block's lane
    dimension equals the full array dimension;
  - contractions are VPU multiply+reduce over the tile (an MXU matmul
    with a [*, 1] operand runs at 1/128 lane utilization — measured 2.6x
    slower than the reduce form);
  - loss/gradient accumulate across sequential grid steps into revisited
    output blocks ([1,1] scalar in SMEM, [1, d] gradient row in VMEM);
  - tile rows adapt to the feature width to respect the VMEM budget;
  - padded rows carry weight 0, doubling as the ragged-tail mask.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from photon_ml_tpu.ops.losses import PointwiseLoss

_TILE_ROWS = 2048
_LANE = 128


def _kernel(loss: PointwiseLoss, with_offsets: bool):
    def kernel(*refs):
        if with_offsets:
            x_ref, y_ref, w_ref, o_ref, c_ref, val_ref, grad_ref = refs
        else:
            x_ref, y_ref, w_ref, c_ref, val_ref, grad_ref = refs
            o_ref = None
        i = pl.program_id(0)
        xb = x_ref[:].astype(jnp.float32)                # [T, d]
        # matvecs as VPU multiply+reduce: [*, 1]-shaped MXU matmuls would
        # run at 1/128 lane utilization (measured ~2.6x slower than XLA)
        z = jnp.sum(xb * c_ref[:], axis=1, keepdims=True)   # [T, 1]
        if o_ref is not None:
            z = z + o_ref[:]
        yb = y_ref[:]                                    # [T, 1]
        wb = w_ref[:]                                    # [T, 1]
        l, dl = loss.loss_and_dz(z, yb)
        wdl = wb * dl                                    # [T, 1]
        v = jnp.sum(wb * l)
        g = jnp.sum(xb * wdl, axis=0, keepdims=True)     # [1, d]

        @pl.when(i == 0)
        def _init():
            val_ref[0, 0] = v
            grad_ref[:] = g

        @pl.when(i > 0)
        def _acc():
            val_ref[0, 0] += v
            grad_ref[:] += g

    return kernel


def _pad_to(a: jax.Array, size: int, axis: int) -> jax.Array:
    pad = size - a.shape[axis]
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


def _col(v: jax.Array, n_pad: int) -> jax.Array:
    return _pad_to(v.astype(jnp.float32), n_pad, 0).reshape(n_pad, 1)


@functools.partial(jax.jit, static_argnums=(0, 6))
def fused_value_and_gradient(
    loss: PointwiseLoss,
    x: jax.Array,
    labels: jax.Array,
    coefficients: jax.Array,
    weights: Optional[jax.Array] = None,
    offsets: Optional[jax.Array] = None,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """(sum_i w_i l(z_i, y_i), gradient) in ONE pass over X.

    Matches ops/aggregators.value_and_gradient for dense inputs (no
    normalization/mask arguments — the XLA path covers those)."""
    n, d = x.shape
    d_pad = -(-d // _LANE) * _LANE
    # adapt tile rows to width: the [T, d] tile plus copies must fit in the
    # ~16MB VMEM budget (target <= ~4MB per tile; floor = the 8-row sublane
    # minimum so very wide matrices shrink the tile instead of the budget)
    t_rows = min(_TILE_ROWS,
                 max(8, (4 * 1024 * 1024 // (d_pad * 4)) // 8 * 8))
    nt = -(-n // t_rows)
    n_pad = nt * t_rows

    w = jnp.ones((n,), jnp.float32) if weights is None else weights
    xp = _pad_to(_pad_to(x, n_pad, 0), d_pad, 1)
    cp = _pad_to(coefficients.astype(jnp.float32), d_pad, 0).reshape(1, d_pad)

    col_spec = pl.BlockSpec((t_rows, 1), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    with_offsets = offsets is not None
    inputs = [xp, _col(labels, n_pad), _col(w, n_pad)]
    in_specs = [
        pl.BlockSpec((t_rows, d_pad), lambda i: (i, 0),
                     memory_space=pltpu.VMEM),
        col_spec,
        col_spec,
    ]
    if with_offsets:
        inputs.append(_col(offsets, n_pad))
        in_specs.append(col_spec)
    inputs.append(cp)
    in_specs.append(pl.BlockSpec((1, d_pad), lambda i: (0, 0),
                                 memory_space=pltpu.VMEM))

    val, grad = pl.pallas_call(
        _kernel(loss, with_offsets),
        grid=(nt,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, d_pad), lambda i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, d_pad), jnp.float32),
        ],
        interpret=interpret,
    )(*inputs)
    return val[0, 0], grad[0, :d]
