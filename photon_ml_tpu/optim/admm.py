"""Consensus ADMM over the mesh's feature axis: the wide-model solver lane.

Every other fixed-effect solver in this repo is a MONOLITH in coefficient
space: LBFGS/TRON/OWLQN keep the full [d] iterate (plus history buffers)
replicated on every device, so model width is bounded by one chip's HBM —
exactly the feature-scaling gap the reference sidesteps by staying narrow
(PAPER.md §5.7).  This module is the feature axis's first resident: a
consensus-form ADMM (Boyd et al. §8.3 "sharing"; unwrapped/transpose-
reduction ADMM, PAPERS.md arXiv 1504.02147) that splits the design matrix
into F column blocks X = [X_1 .. X_F] sharded over the mesh "feature" axis
and alternates

  w_j  <- argmin  l2/2 ||w_j||^2 [+ rho/2 ||w_j - v_j + t_j||^2]
              + rho/2 || X_j w_j - X_j w_j^k - r ||^2      (per-shard, local)
  zbar <- prox of the pointwise loss on the AVERAGE margin  (per-row, local)
  ubar <- ubar + mbar - zbar                                (scaled dual)

with r = zbar - mbar - ubar and mbar = (1/F) sum_j X_j w_j.

Communication per iteration is exactly TWO reductions, both inserted by
GSPMD from the sharding of the einsum operands:

  * ONE [n]-vector psum over the FEATURE axis — the margin sum
    ``einsum('nfa,fa->n', X, W)`` that forms mbar (the only place shards
    exchange vector-sized data; tests/test_admm.py::
    test_one_feature_axis_reduction_per_iteration holds it to exactly one
    per iteration);
  * ONE [F, d_F] psum over the DATA axis — the residual product
    ``einsum('nfa,n->fa', X, r)`` (transpose-reduction: together with the
    cached per-shard Gram it reconstructs X_j^T b_j without ever
    materializing b_j per shard).

The w-update is CLOSED FORM via the transpose-reduction trick: the
per-shard Gram G_j = X_j^T X_j is computed once per (coordinate, mesh) and
cached as its eigendecomposition G_j = Q_j diag(lam_j) Q_j^T (staged by
parallel/fixed_effect.fit_fixed_effect_admm through the mesh residency
layer, fault site "admm.stage"), so

    (G_j + c I)^{-1} y  =  Q_j ((Q_j^T y) / (lam_j + c))

solves the shard subproblem for ANY traced shift c = l2/rho (+1 when the
L1 split is active) — adaptive rho re-dispatches the SAME executable,
never refactorizes, never retraces.  Penalty rho, the iteration budget,
and the regularization weights all ride as traced operands per the
SolveBudget/RegWeights discipline (optim/schedule.py).

L1 / elastic net uses the standard extra split v_j = w_j with the
per-shard soft-threshold as the v-update; the reported solution is v
(exact zeros, so the sparsity pattern is directly comparable to OWLQN's).
The z-prox runs a fixed number of guarded 1-D Newton steps per row —
exact in one step for squared loss, and strongly damped by the + F*rho
quadratic for every other loss family (Poisson included).

The consensus step does NO host-visible I/O: duals, consensus variables
and margins live in the lax.while_loop carry on device for the whole
solve, so there is no "solve.consensus" fault site — the only host
boundary is the one-time staging of the column-sharded design grid and
its Gram eigendecomposition, covered by "admm.stage" (utils/faults.py).
"""
from __future__ import annotations

import dataclasses
import functools
import re
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from photon_ml_tpu.ops.losses import PointwiseLoss
from photon_ml_tpu.optim.schedule import SolveBudget
from photon_ml_tpu.optim.types import ConvergenceReason, SolveResult

#: adaptive-rho clamp: residual balancing may scale rho by tau per
#: iteration but never outside this window (a runaway rho would push the
#: eigen-shift c = l2/rho toward 0/inf and de-condition the w-update)
RHO_MIN = 1e-6
RHO_MAX = 1e6


@dataclasses.dataclass(frozen=True)
class ADMMConfig:
    """The ADMM lane's knobs — the feature-axis analogue of
    OptimizerConfig.  `None` means use-the-default (resolved()), matching
    the OptimizerConfig convention.

    `max_iterations` is the STATIC history-buffer ceiling; the effective
    cap/tolerance ride in as a traced SolveBudget so inexactness schedules
    re-dispatch one executable.  `rho` is the INITIAL penalty — a traced
    operand, so sweeping it (or adapting it in-loop) never retraces.
    `adapt_rho` compiles in residual balancing (Boyd §3.4.1: multiply by
    `rho_tau` when the primal residual exceeds `rho_mu` times the dual,
    divide when the reverse holds; scaled duals are rescaled in the same
    step so the iteration stays exact).  `newton_steps` bounds the z-prox
    Newton refinement (exact after 1 for squared loss).

    `polish` runs the strict monolithic solver ONCE after ADMM, warm
    started from the consensus solution — the always-available fallback
    that pins exact parity with the host-stepped lane.  It re-stages the
    UNSPLIT design block and replicates the full [d] iterate, so models
    too wide for one device must set polish=False (the pure-ADMM path is
    the whole point there); see COMPONENTS.md "Feature-axis ADMM"."""

    max_iterations: Optional[int] = None     # None -> 200
    tolerance: Optional[float] = None        # None -> 1e-8 (relative)
    rho: float = 1.0
    adapt_rho: bool = True
    rho_tau: float = 2.0
    rho_mu: float = 10.0
    newton_steps: int = 8
    polish: bool = True

    def __post_init__(self):
        # python floats, not np scalars: a strong-typed float is a fresh
        # trace-cache key for the closed-over constants (the same weak-vs-
        # strong pitfall GLMOptimizationConfig guards its reg weight with)
        for name in ("rho", "rho_tau", "rho_mu"):
            object.__setattr__(self, name, float(getattr(self, name)))
        if self.tolerance is not None:
            object.__setattr__(self, "tolerance", float(self.tolerance))
        if self.rho <= 0:
            raise ValueError("rho must be > 0")
        if self.rho_tau <= 1.0:
            raise ValueError("rho_tau must be > 1 (the balancing step)")
        if self.rho_mu < 1.0:
            raise ValueError("rho_mu must be >= 1")
        if self.newton_steps < 1:
            raise ValueError("newton_steps must be >= 1")

    def resolved(self) -> "ADMMConfig":
        """Fill `None` fields with defaults — duck-types
        OptimizerConfig.resolved() so SolverSchedule.budget_for maps an
        inexactness schedule onto the ADMM lane unchanged."""
        return dataclasses.replace(
            self,
            max_iterations=(self.max_iterations
                            if self.max_iterations is not None else 200),
            tolerance=self.tolerance if self.tolerance is not None else 1e-8)


class ADMMOperands(NamedTuple):
    """Per-solve device operands of the compiled ADMM iteration.  The
    design grid is [n_pad, F, d_F] sharded P("data", "feature", None);
    `q_eig`/`lam_eig` are the cached per-shard Gram eigendecompositions
    [F, d_F, d_F] / [F, d_F] sharded over "feature"."""

    x_grid: jax.Array
    q_eig: jax.Array
    lam_eig: jax.Array
    labels: jax.Array        # [n_pad]
    kappa: jax.Array         # [n_pad] weights*mask (0 on padded rows)
    offsets: jax.Array       # [n_pad]
    l1_weight: jax.Array     # traced scalar
    l2_weight: jax.Array     # traced scalar


class ADMMCarry(NamedTuple):
    """lax.while_loop state: every dual/consensus variable is device
    resident for the whole solve (the carry never crosses the host
    boundary)."""

    k: jax.Array             # int32 iteration counter
    w: jax.Array             # [F, d_F] per-shard coefficients
    v: jax.Array             # [F, d_F] L1 split (== w when has_l1 False)
    t: jax.Array             # [F, d_F] scaled dual of the w=v split
    zbar: jax.Array          # [n_pad] consensus average margin
    ubar: jax.Array          # [n_pad] scaled dual of the margin constraint
    mbar: jax.Array          # [n_pad] current average margin (1/F sum X_j w_j)
    rho: jax.Array           # traced penalty (adapted in-loop)
    prim: jax.Array          # latest primal residual norm
    dual: jax.Array          # latest dual residual norm (proxy)
    prim_scale: jax.Array    # relative-stopping scales (+1 floored)
    dual_scale: jax.Array
    loss_history: jax.Array  # [ceil + 1]
    gnorm_history: jax.Array


def _soft_threshold(x, thresh):
    return jnp.sign(x) * jnp.maximum(jnp.abs(x) - thresh, 0.0)


def _make_kernels(loss: PointwiseLoss, has_l1: bool, newton_steps: int,
                  adapt_rho: bool, rho_tau: float, rho_mu: float):
    """The iteration body + init as pure closures over the STATIC choices
    (loss family, L1 split presence, Newton depth, balancing constants).
    Shared by the compiled while_loop program and the standalone
    single-iteration probe (`cached_step_probe`), so the collective accounting measures the
    exact body the solver runs."""

    def loss_value(ops: ADMMOperands, mbar, w, v):
        F = jnp.asarray(ops.x_grid.shape[1], mbar.dtype)
        off = ops.offsets if ops.offsets is not None else 0.0
        margins = F * mbar + off
        val = jnp.sum(ops.kappa * loss.loss(margins, ops.labels))
        val = val + 0.5 * ops.l2_weight * jnp.sum(w * w)
        if has_l1:
            val = val + ops.l1_weight * jnp.sum(jnp.abs(v))
        return val

    def z_prox(ops: ADMMOperands, zbar, q, rho):
        """Row-wise prox of kappa*l(F z + off, y) + F rho/2 (z - q)^2 by
        fixed Newton steps (warm-started at the incoming zbar; exact in
        one step for squared loss; the + F*rho curvature keeps the step
        well-damped for unbounded-curvature losses)."""
        F = jnp.asarray(ops.x_grid.shape[1], zbar.dtype)
        off = ops.offsets if ops.offsets is not None else 0.0

        def step(_, z):
            m = F * z + off
            g = ops.kappa * F * loss.dz(m, ops.labels) + F * rho * (z - q)
            h = (ops.kappa * (F * F) * loss.d2z(m, ops.labels)
                 + F * rho)
            return z - g / h

        return lax.fori_loop(0, newton_steps, step, zbar)

    def init(ops: ADMMOperands, w0, rho0, ceil: int) -> ADMMCarry:
        dtype = ops.x_grid.dtype
        F = jnp.asarray(ops.x_grid.shape[1], dtype)
        s0 = jnp.einsum("nfa,fa->n", ops.x_grid, w0)   # feature-axis psum
        mbar0 = s0 / F
        zbar0 = mbar0                                  # constraint-feasible
        ubar0 = jnp.zeros_like(zbar0)
        v0 = w0
        t0 = jnp.zeros_like(w0)
        hist = jnp.full((ceil + 1,), jnp.nan, dtype)
        gh = jnp.full((ceil + 1,), jnp.nan, dtype)
        hist = hist.at[0].set(loss_value(ops, mbar0, w0, v0))
        gh = gh.at[0].set(0.0)
        inf = jnp.asarray(jnp.inf, dtype)
        one = jnp.asarray(1.0, dtype)
        return ADMMCarry(jnp.asarray(0, jnp.int32), w0, v0, t0, zbar0,
                         ubar0, mbar0, jnp.asarray(rho0, dtype), inf, inf,
                         one, one, hist, gh)

    def body(ops: ADMMOperands, c: ADMMCarry) -> ADMMCarry:
        dtype = ops.x_grid.dtype
        F = jnp.asarray(ops.x_grid.shape[1], dtype)
        # -- w-update: transpose-reduction closed form ---------------------
        # X_j^T b_j = G_j w_j + X_j^T r with r shared across shards: ONE
        # data-axis psum produces every shard's residual product at once
        r = c.zbar - c.mbar - c.ubar
        xtr = jnp.einsum("nfa,n->fa", ops.x_grid, r)   # data-axis psum
        rhs = xtr + (c.v - c.t) if has_l1 else xtr
        shift = ops.l2_weight / c.rho + (1.0 if has_l1 else 0.0)
        # (G + shift I)^{-1}(G w + rhs) via the cached eigenbasis; the
        # floor zeroes null-space directions (zero-padded columns, exact
        # rank deficiency) instead of dividing by ~0 when shift is tiny
        p = jnp.einsum("fab,fa->fb", ops.q_eig, c.w)
        q2 = jnp.einsum("fab,fa->fb", ops.q_eig, rhs)
        denom = ops.lam_eig + shift
        floor = 1e-12 * (jnp.max(ops.lam_eig) + 1.0)
        coef = jnp.where(denom > floor,
                         (ops.lam_eig * p + q2) / jnp.maximum(denom, floor),
                         jnp.zeros_like(denom))
        w = jnp.einsum("fab,fb->fa", ops.q_eig, coef)
        # -- v-update: per-shard soft threshold (L1 split) -----------------
        if has_l1:
            v = _soft_threshold(w + c.t, ops.l1_weight / c.rho)
            t = c.t + w - v
        else:
            v, t = w, c.t
        # -- consensus: the ONE feature-axis vector reduction --------------
        s = jnp.einsum("nfa,fa->n", ops.x_grid, w)     # feature-axis psum
        mbar = s / F
        zbar = z_prox(ops, c.zbar, mbar + c.ubar, c.rho)
        ubar = c.ubar + mbar - zbar
        # -- residuals + relative stopping scales (scalar reductions) ------
        prim2 = F * jnp.sum((mbar - zbar) ** 2)
        dual2 = (c.rho * F) ** 2 * jnp.sum((zbar - c.zbar) ** 2)
        if has_l1:
            prim2 = prim2 + jnp.sum((w - v) ** 2)
            dual2 = dual2 + c.rho ** 2 * jnp.sum((v - c.v) ** 2)
        prim = jnp.sqrt(prim2)
        dual = jnp.sqrt(dual2)
        prim_scale = jnp.sqrt(jnp.maximum(F * jnp.sum(mbar ** 2),
                                          F * jnp.sum(zbar ** 2))) + 1.0
        dual_scale = c.rho * F * jnp.sqrt(jnp.sum(ubar ** 2)) + 1.0
        hist = c.loss_history.at[c.k + 1].set(loss_value(ops, mbar, w, v))
        gh = c.gnorm_history.at[c.k + 1].set(prim)
        # -- adaptive rho: residual balancing, duals rescaled --------------
        rho = c.rho
        if adapt_rho:
            rho = jnp.where(
                prim > rho_mu * dual, jnp.minimum(rho * rho_tau, RHO_MAX),
                jnp.where(dual > rho_mu * prim,
                          jnp.maximum(rho / rho_tau, RHO_MIN), rho))
            scale = c.rho / rho
            ubar = ubar * scale
            t = t * scale
        return ADMMCarry(c.k + 1, w, v, t, zbar, ubar, mbar, rho, prim,
                         dual, prim_scale, dual_scale, hist, gh)

    return loss_value, init, body


@functools.lru_cache(maxsize=64)
def _cached_admm_program(loss: PointwiseLoss, has_l1: bool, ceil: int,
                         adapt_rho: bool, newton_steps: int,
                         rho_tau: float, rho_mu: float):
    """One persistent jit per static ADMM shape: the iteration cap,
    tolerance, rho and both reg weights are OPERANDS, so warm iterations,
    rho adaptation/sweeps and budget schedules all re-dispatch this one
    executable (regression: tests/test_admm.py zero-trace gates)."""
    loss_value, init, body = _make_kernels(loss, has_l1, newton_steps,
                                           adapt_rho, rho_tau, rho_mu)

    def run(ops: ADMMOperands, w0, rho0, budget: SolveBudget) -> SolveResult:
        carry0 = init(ops, w0, rho0, ceil)
        cap = jnp.minimum(budget.iteration_cap, ceil)
        tol = budget.tolerance

        def cond(c: ADMMCarry):
            live = ((c.prim > tol * c.prim_scale)
                    | (c.dual > tol * c.dual_scale))
            return (c.k < cap) & live

        out = lax.while_loop(cond, lambda c: body(ops, c), carry0)
        x = (out.v if has_l1 else out.w).reshape(-1)
        converged = ((out.prim <= tol * out.prim_scale)
                     & (out.dual <= tol * out.dual_scale))
        reason = jnp.where(
            converged,
            jnp.asarray(ConvergenceReason.FUNCTION_VALUES_CONVERGED,
                        jnp.int32),
            jnp.asarray(ConvergenceReason.MAX_ITERATIONS, jnp.int32))
        return SolveResult(
            x=x, value=loss_value(ops, out.mbar, out.w, out.v),
            gradient_norm=out.prim, iterations=out.k, reason=reason,
            loss_history=out.loss_history, gnorm_history=out.gnorm_history)

    return jax.jit(run)


def admm_solve(loss: PointwiseLoss, has_l1: bool, ops: ADMMOperands,
               w0: jax.Array, config: ADMMConfig,
               budget: Optional[SolveBudget] = None,
               rho0=None) -> SolveResult:
    """Run one consensus-ADMM solve on pre-staged device operands.

    Callers normally go through parallel.fixed_effect.fit_fixed_effect_admm
    (which stages the column grid and Gram eigendecomposition through the
    mesh residency layer); this entry point is the pure-compute surface the
    tests drive directly.  `loss` and `has_l1` are the STATIC
    structural choices (trace-cache keys, like solve()'s reg.has_l1); a
    traced l1 weight of 0 under has_l1=True converges to the same smooth
    optimum.  `w0` is the [F, d_F] warm start; `budget` follows the
    SolveBudget discipline (None = the config's resolved statics, same
    arithmetic); `rho0` overrides the config's initial penalty as a traced
    operand (sweeps re-dispatch one program).  The returned `x` is the
    [F * d_F] flattened, feature-sharded solution — the caller slices off
    column padding.  `gradient_norm` and `gnorm_history` report the PRIMAL
    RESIDUAL norm (ADMM's convergence measure; there is no monolithic
    gradient to take the norm of)."""
    cfg = config.resolved()
    if budget is None:
        budget = SolveBudget.make(cfg.max_iterations, cfg.tolerance)
    if rho0 is None:
        rho0 = cfg.rho
    program = _cached_admm_program(loss, bool(has_l1), cfg.max_iterations,
                                   cfg.adapt_rho, cfg.newton_steps,
                                   cfg.rho_tau, cfg.rho_mu)
    return program(ops, w0, jnp.asarray(rho0, ops.x_grid.dtype), budget)


@functools.lru_cache(maxsize=16)
def cached_step_probe(loss: PointwiseLoss, has_l1: bool, adapt_rho: bool,
                      newton_steps: int, rho_tau: float = 2.0,
                      rho_mu: float = 10.0):
    """A jitted SINGLE ADMM iteration (the exact `body` the while_loop
    runs) as a standalone (ops, carry) -> carry program.

    This is the collective-accounting surface: lowering it with
    the real shardings and inspecting the compiled HLO counts the
    all-reduces one iteration costs — the gate is exactly ONE vector
    ([n]-shaped) all-reduce over the FEATURE axis plus one [F, d_F]
    all-reduce over DATA (scalar residual/history reductions exempt).
    Pair with `make_init` to build a valid carry."""
    _, _, body = _make_kernels(loss, has_l1, newton_steps, adapt_rho,
                               rho_tau, rho_mu)
    return jax.jit(body)


def make_init(loss: PointwiseLoss, has_l1: bool, ops: ADMMOperands,
              w0: jax.Array, rho0, ceil: int,
              newton_steps: int = 8) -> ADMMCarry:
    """Build the iteration-0 carry for `cached_step_probe` (test
    helper; the production program builds its carry inside the jit)."""
    _, init, _ = _make_kernels(loss, has_l1, newton_steps, True, 2.0, 10.0)
    return jax.jit(init, static_argnums=(3,))(ops, w0, rho0, ceil)


# The result type is one `dtype[dims]{layout}` or, where XLA's combiner
# merged several reductions into one op, a tuple of them — possibly with
# `/*index=N*/` comments between elements and, on TPU, tiled layouts such as
# `{0:T(128)}`.  `-start` is the async form the TPU compiler emits (same
# result type).
_ALLREDUCE_RE = re.compile(
    r"= (?P<shape>\((?:[^()]|\([^()]*\))*\)|[a-z]+\d+\[[\d,]*\][^ ]*) "
    r"all-reduce(?:-start)?\("
    r".*?replica_groups=(?P<groups>\{\{[^}]*(?:\},\{[^}]*)*\}\}|"
    r"\[[\d,]+\]<=\[[\d,]+\](?:T\([\d,]+\))?)")
_ELEMENT_RE = re.compile(r"(?P<dtype>[a-z]+\d+)\[(?P<dims>[\d,]*)\]")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s32": 4,
                "u32": 4, "s64": 8, "u64": 8, "pred": 1}


def _decode_replica_groups(spec: str):
    """Replica groups from either HLO syntax: the explicit list-of-lists
    form `{{0,1},{2,3}}` or the iota form `[a,b]<=[c,d]T(perm)` (reshape
    arange over [c,d], transpose by perm, reshape to [a,b]; rows are
    groups)."""
    if spec.startswith("{{"):
        return [tuple(int(t) for t in grp.split(",") if t)
                for grp in spec[2:-2].split("},{")]
    shape_s, _, src = spec.partition("<=")
    out_shape = [int(t) for t in shape_s.strip("[]").split(",")]
    src_body, _, perm_s = src.partition("T(")
    src_shape = [int(t) for t in src_body.strip("[]").split(",")]
    ids = np.arange(int(np.prod(src_shape))).reshape(src_shape)
    if perm_s:
        ids = ids.transpose([int(t) for t in perm_s.rstrip(")").split(",")])
    rows = ids.reshape(out_shape)
    return [tuple(int(v) for v in row) for row in rows]


def collective_summary(compiled_text: str, mesh) -> dict:
    """Classify every all-reduce in a compiled HLO module against the
    mesh's device grid: groups that match a ROW of `mesh.devices`
    (fixed data coordinate, all feature shards) reduce over the FEATURE
    axis; groups matching a COLUMN reduce over DATA; anything else
    (including single-axis meshes where both degenerate) is "global".

    Returns per-axis op lists of (rank, payload_bytes) so callers can
    gate "one [n]-vector feature reduction + one block data reduction
    per iteration" and account the bytes each iteration moves.  Scalar
    residual/ρ bookkeeping reductions show up with rank 0."""
    grid = np.asarray([[d.id for d in row] for row in mesh.devices]) \
        if np.ndim(mesh.devices) == 2 else \
        np.asarray([d.id for d in np.ravel(mesh.devices)]).reshape(
            mesh.devices.shape)
    feature_groups = {tuple(int(v) for v in row) for row in grid}
    data_groups = {tuple(int(v) for v in col) for col in grid.T}
    out = {"feature": [], "data": [], "global": [], "other": []}
    for m in _ALLREDUCE_RE.finditer(compiled_text):
        groups = {g for g in _decode_replica_groups(m.group("groups"))
                  if len(g) > 1}
        if not groups:
            continue  # trivial single-device groups: no wire traffic
        if groups <= feature_groups:
            lane = out["feature"]
        elif groups <= data_groups:
            lane = out["data"]
        elif len(next(iter(groups))) == grid.size:
            lane = out["global"]
        else:
            lane = out["other"]
        # one entry per ELEMENT of a combined (tuple-shaped) all-reduce:
        # the payload on the wire is the same as that many separate ops
        for e in _ELEMENT_RE.finditer(m.group("shape")):
            dims = [int(t) for t in e.group("dims").split(",") if t]
            nbytes = int(np.prod(dims or [1])) * _DTYPE_BYTES.get(
                e.group("dtype"), 8)
            lane.append((len(dims), nbytes))
    return out
