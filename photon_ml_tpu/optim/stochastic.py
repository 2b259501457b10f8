"""Stochastic per-chunk coordinate descent for streamed GLM fits.

The host-stepped solvers (optim/streaming.py) re-stage EVERY chunk of an
out-of-core objective through the Prefetcher on EVERY LBFGS/OWLQN/TRON
oracle call: one gradient evaluation costs one full pass of staging
bandwidth, and at out-of-core scale — single device or mesh-streamed
(PR 6 put mesh chunks on the same bus) — the staging bus is the
bottleneck.  Snap ML (arXiv:1803.06333) and TPA-SCD (arXiv:1702.07005)
show the fix: do a FULL EPOCH of stochastic local updates on each
resident chunk before eviction, merged hierarchically, so useful work
per staged byte goes up by the local epoch count.

This module is that lane:

  * `_local_epochs` — the per-chunk local solver: TPA-SCD-style primal
    stochastic coordinate descent over the RESIDENT chunk, `fori_loop`-
    batched so `epochs` full epochs (each a seeded random permutation of
    the coordinates) run as ONE device program keyed on the chunk shape
    — never on the chunk index, chunk count, or row count.  Each
    coordinate step is a closed-form 1-D majorized Newton update: for
    losses with a global curvature bound (`PointwiseLoss.d2z_bound`:
    logistic 1/4, squared 1, smoothed hinge 1) the step can never
    overshoot its 1-D subproblem, so every update descends the chunk
    objective; unbounded-curvature losses (Poisson) use current-point
    curvature with a step clip.  The chunk's margin vector is maintained
    incrementally — an epoch costs O(rows * d), the same order as ONE
    gradient pass, so K epochs on a staged chunk do K gradient-passes of
    work for one pass of staging.
  * The merge is hierarchical: WITHIN a chunk, a mesh shards the rows
    over the "data" axis and GSPMD inserts psums into the same dot
    products the accumulation kernels use (the kernel is sharding-
    agnostic); ACROSS the stream, per-chunk models combine either
    sequentially (chunk k warm-starts from chunk k-1 — the default) or
    as a row-weighted delta average (`merge="average"`, the CoCoA-safe
    order-independent rule).  See optim.schedule.StochasticPlan.
  * `solve_stochastic` — the host-stepped pass driver: `passes` full
    passes over the chunk stream (ops/chunked.py stages each chunk ONCE
    per pass and pins it for the local epochs), returning a SolveResult
    whose loss history is the per-pass streaming objective.

The lane is the COARSE mode: it buys cheap early progress per staged
byte, and `SolverSchedule.stochastic_plan` always hands the final outer
iteration(s) to the strict host-stepped solver, whose full-tolerance
polish pins the fixed point (held to 1e-6 in f64 by
tests/test_stochastic.py::test_fixed_point_parity_stochastic_plus_polish).

Determinism: the per-(chunk, epoch) permutation key is PRNGKey(seed)
folded with (pass, chunk, epoch) in turn — a given (plan, seed,
chunking) replays bit-for-bit, on one device or a mesh of the same
shape.
"""
from __future__ import annotations

# photonlint: disable-file=PH001 -- host-stepped BY DESIGN: like
# optim/streaming.py, the pass driver reads back exactly one scalar (the
# per-pass streaming objective) per full pass over the chunk stream

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from photon_ml_tpu.optim.schedule import StochasticPlan
from photon_ml_tpu.optim.types import ConvergenceReason, SolveResult

#: curvature floor: a zero column (or an all-padding chunk) must yield a
#: zero step, not an inf one
_H_FLOOR = 1e-12


@functools.partial(jax.jit, static_argnames=("loss", "epochs"))
def _local_epochs(c, x, labels, weights, offsets, mask, norm, key,
                  l2_local, step_clip, *, loss, epochs):
    """`epochs` epochs of stochastic coordinate descent on ONE resident
    chunk, as one compiled program keyed on the chunk shape.

    Minimizes the chunk's local subproblem
        sum_i mask*w * loss(z_i, y_i) + 0.5 * l2_local * ||c||^2
    in the solver's (normalized) coefficient space: with normalization
    factors f / shifts s the margin is z = x.(c*f) - (c*f).s + offset,
    so coordinate j's column is f_j * (x_j - s_j) — the chunk is never
    materialized in normalized space (the same margin-invariant algebra
    the fused aggregators use).

    Returns (updated c, the chunk's ENTRY data loss) — the entry losses
    summed over a pass are the free streaming objective estimate (no
    extra staging pass to evaluate progress).
    """
    dtype = c.dtype
    d = c.shape[0]
    mw = mask if weights is None else mask * weights
    has_f = norm is not None and norm.factors is not None
    has_s = norm is not None and norm.shifts is not None
    fv = norm.factors.astype(dtype) if has_f else jnp.ones((d,), dtype)
    sv = norm.shifts.astype(dtype) if has_s else jnp.zeros((d,), dtype)
    base = jnp.zeros(x.shape[0], dtype) if offsets is None else offsets
    e = c * fv
    z = x @ e - jnp.dot(e, sv) + base
    entry = jnp.dot(mw, loss.loss(z, labels))

    bound = loss.d2z_bound
    if bound is not None:
        # majorized per-coordinate curvature, constant across the epochs:
        # sum_i mw * (f_j (x_ij - s_j))^2 for every j in one pass
        xsq = mw @ (x * x)
        xs = mw @ x
        msum = jnp.sum(mw)
        colsq = fv * fv * (xsq - 2.0 * sv * xs + sv * sv * msum)
        h = bound * colsq + l2_local

    def coord_step(carry, j):
        c, z = carry
        xj = jax.lax.dynamic_index_in_dim(x, j, axis=1, keepdims=False)
        colj = fv[j] * (xj - sv[j])
        dl = mw * loss.dz(z, labels)
        gj = jnp.dot(colj, dl) + l2_local * c[j]
        if bound is not None:
            hj = h[j]
        else:
            hj = jnp.dot(mw * loss.d2z(z, labels), colj * colj) + l2_local
        delta = -gj / jnp.maximum(hj, jnp.asarray(_H_FLOOR, dtype))
        delta = jnp.clip(delta, -step_clip, step_clip)
        c = c.at[j].add(delta)
        z = z + delta * colj
        return c, z

    def epoch_body(ei, carry):
        perm = jax.random.permutation(jax.random.fold_in(key, ei), d)
        return jax.lax.fori_loop(
            0, d, lambda t, cz: coord_step(cz, perm[t]), carry)

    c, z = jax.lax.fori_loop(0, epochs, epoch_body, (c, z))
    return c, entry


def resolve_step_clip(loss, step_clip: Optional[float]) -> float:
    """Explicit clip wins; otherwise bounded-curvature losses run
    unclipped (the majorized step cannot overshoot) and unbounded ones
    (Poisson) default to 1.0 — current-point curvature under-estimates
    away from the iterate, so a raw Newton step can diverge."""
    if step_clip is not None:
        return float(step_clip)
    return float("inf") if loss.d2z_bound is not None else 1.0


def solve_stochastic(objective, x0: jax.Array,
                     plan: StochasticPlan,
                     max_iterations: Optional[int] = None) -> SolveResult:
    """Run `plan.passes` stochastic passes over a ChunkedGLMObjective's
    chunk stream (each chunk staged once per pass and pinned for
    `plan.local_epochs` local epochs), host-stepped like the streamed
    LBFGS mirror.  `objective.l2_weight` must already carry the L2 term
    (solve_streamed's with_l2 dispatch does this).

    `loss_history[p]` is the streaming objective ENTERING pass p: the
    sum of each chunk's data loss at the model that chunk started from,
    plus the L2 term at the pass-entry model — free to compute (no extra
    staging pass), deterministic, and identical across mesh shapes up to
    float summation order.  `value` repeats the last entry (evaluating
    the exit model exactly would cost one more full staging pass, which
    is the thing this lane exists to avoid); the strict polish lane
    reports exact values.
    """
    import numpy as np

    x = jnp.asarray(x0)
    dtype = x.dtype
    losses = []
    for p in range(plan.passes):
        x, entry = objective.stochastic_pass(
            x, local_epochs=plan.local_epochs, seed=plan.seed,
            pass_index=p, merge=plan.merge, step_clip=plan.step_clip)
        losses.append(float(entry))
    hist_len = (max_iterations if max_iterations is not None
                else max(plan.passes, 1)) + 1
    hist = np.full((hist_len,), np.nan)
    hist[:len(losses)] = losses
    value = losses[-1] if losses else float("nan")
    return SolveResult(
        x=x, value=jnp.asarray(value, dtype),
        gradient_norm=jnp.asarray(float("nan"), dtype),
        iterations=jnp.asarray(plan.passes, jnp.int32),
        reason=jnp.asarray(int(ConvergenceReason.MAX_ITERATIONS),
                           jnp.int32),
        loss_history=jnp.asarray(hist, dtype),
        gnorm_history=jnp.asarray(np.full((hist_len,), np.nan), dtype),
        coefficient_history=None,
        fg_count=jnp.asarray(plan.passes, jnp.int32))
