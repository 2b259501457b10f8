"""Prior-anchored per-entity solves: the math core of the online tier.

Production GLMix freshness comes from cheap random-effect-only refits: the
per-entity subproblems are independent (the executor-sharding insight the
distributed coordinate descent literature exploits — arXiv 1611.02101;
Snap ML 1803.06333 shows local sub-solves at micro-batch scale are where
the hardware wins), so a handful of entities with new feedback can be
re-solved without touching the fixed effect or the other entities.

A fresh-feedback refit must not let a few rows blow away the batch
solution, so the subproblem is ANCHORED at the current coefficients c0:

    min_c  sum_s w_s * loss(x_s . c + o_s, y_s)  +  lam/2 * ||c - c0||^2

Solved in DELTA space (c = c0 + delta): folding x.c0 into the offsets
turns the anchor into a plain L2 penalty on delta,

    min_d  sum_s w_s * loss(x_s . d + (o_s + x_s . c0), y_s) + lam/2 ||d||^2

which is exactly the shape the existing batched random-effect solver
(`parallel.random_effect.fit_random_effects`) compiles: the online tier
reuses that vmapped program at micro-batch size, warm-started at delta=0
(i.e. at the current coefficients).  One practical consequence the online
updater leans on: when `o_s` already holds the FULL model margin of the
row (own coordinate included), `o_s + x_s . c0` is just `margin + base
offset` — no per-coordinate margin decomposition is needed.

Also here: per-entity sub-dataset extraction (carve the rows of a set of
entities out of a GameDataset) and the OFFLINE refit reference that
tests/test_online.py compares the online path against — it goes through the
training-side dataset build (`build_random_effect_dataset`), i.e. a
genuinely different block-construction path arriving at the same optimum.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from photon_ml_tpu.optim import (OptimizerConfig, RegularizationContext,
                                 RegularizationType, SolveResult)
from photon_ml_tpu.parallel.random_effect import EntityBlocks, fit_random_effects

#: the anchor is a pure L2 pull toward the prior in delta space
ANCHOR_REG = RegularizationContext(RegularizationType.L2)


@jax.jit
def _fold_prior_offsets(x, prior, offsets, mask):
    """offsets' = offsets + x . prior, masked (padding cells stay 0)."""
    return (offsets + jnp.einsum("esd,ed->es", x, prior)) * mask


@jax.jit
def _add_prior(prior, delta):
    return prior + delta


@jax.jit
def lane_all_finite(rows):
    """[E] finite flag per entity lane — the online quarantine predicate."""
    return jnp.all(jnp.isfinite(rows), axis=-1)


def solve_anchored(blocks: EntityBlocks, prior: jax.Array,
                   loss, config: OptimizerConfig,
                   anchor_weight: float, budget=None,
                   ) -> Tuple[jax.Array, SolveResult]:
    """All entities' anchored subproblems as ONE batched program.

    `prior` is [E, d] (the current coefficient rows); returns
    (new_rows [E, d], delta-space SolveResult).  Reuses the persistent
    compiled batched solver (`_cached_batched_solver` keyed on
    loss/config/reg), so steady-state online updates trace nothing new —
    shapes are bounded by the updater's pow-2 (micro_batch, S-bucket)
    grouping.
    """
    E, S, d = blocks.x.shape
    if prior.shape != (E, d):
        raise ValueError(f"prior must be [{E}, {d}], got {prior.shape}")
    offsets = (blocks.offsets if blocks.offsets is not None
               else jnp.zeros_like(blocks.labels))
    folded = dataclasses.replace(
        blocks, offsets=_fold_prior_offsets(blocks.x, prior, offsets,
                                            blocks.mask))
    res = fit_random_effects(
        folded, loss, x0=jnp.zeros_like(prior), config=config,
        reg=ANCHOR_REG, reg_weight=anchor_weight, budget=budget)
    return _add_prior(prior, res.x), res


# -- per-entity sub-dataset extraction ----------------------------------------

def entity_rows(dataset, re_type: str, entity_ids) -> np.ndarray:
    """Canonical row ids of `dataset` whose `re_type` entity is in
    `entity_ids` (raw id values) — the extraction step of an
    entities-only refit."""
    vocab = np.asarray(dataset.entity_vocabs[re_type])
    wanted = set(np.asarray(entity_ids).tolist())
    vocab_hit = np.asarray([v in wanted for v in vocab.tolist()])
    idx = np.asarray(dataset.entity_indices[re_type])
    return np.flatnonzero((idx >= 0) & vocab_hit[np.maximum(idx, 0)])


def sub_dataset_for_entities(dataset, re_type: str, entity_ids):
    """Row-slice of `dataset` containing exactly the given entities' rows
    (shared vocabularies, canonical order preserved within the slice)."""
    return dataset.subset(entity_rows(dataset, re_type, entity_ids))


def offline_anchored_refit(
    dataset,
    re_type: str,
    feature_shard: str,
    prior_rows: Dict[object, np.ndarray],
    loss,
    config: OptimizerConfig = OptimizerConfig(),
    anchor_weight: float = 1.0,
    dtype=np.float64,
) -> Dict[object, np.ndarray]:
    """The parity REFERENCE for online updates: refit the dataset's
    entities' anchored subproblems through the TRAINING-side machinery.

    `dataset` holds the same feedback rows the online path consumed, with
    `dataset.offsets` already set to (full-model margin + base offset) per
    row — the same residual fold the online updater uses.  Blocks are
    built by `data.batching.build_random_effect_dataset` (identity
    projector, no caps): a different grouping/padding/packing path than
    the online FeedbackBuffer's, converging on the same per-entity optima
    (the anchor makes each subproblem strongly convex, so the f64 parity
    gate is well-posed).  Returns {entity_id: new row [d]}."""
    from photon_ml_tpu.data.batching import (RandomEffectDataConfig,
                                             build_random_effect_dataset)
    if dataset.offsets is None:
        raise ValueError("offline_anchored_refit needs dataset.offsets = "
                         "full-model margins + base offsets (the residual "
                         "fold); build the dataset with offsets")
    red = build_random_effect_dataset(
        dataset, RandomEffectDataConfig(re_type, feature_shard,
                                        projector="identity",
                                        max_buckets=1), dtype=dtype)
    lane_ids = np.asarray(dataset.entity_vocabs[re_type])[red.entity_ids]
    missing = [v for v in lane_ids.tolist() if v not in prior_rows]
    if missing:
        raise ValueError(f"no prior row for entities {missing[:5]!r} — the "
                         "refit anchors every entity at its current row")
    prior = jnp.asarray(np.stack([np.asarray(prior_rows[v], dtype=dtype)
                                  for v in lane_ids.tolist()]))
    new_rows, _res = solve_anchored(red.blocks, prior, loss, config,
                                    anchor_weight)
    out_np = np.asarray(new_rows)
    return {v: out_np[i] for i, v in enumerate(lane_ids.tolist())}


def anchored_objective_np(x, y, w, offsets, c, prior, loss_name: str,
                          anchor_weight: float) -> float:
    """Host-numpy f64 anchored objective at `c` — the independent oracle
    the tests cross-check `solve_anchored` against (no JAX involved)."""
    x = np.asarray(x, np.float64)
    z = x @ np.asarray(c, np.float64) + np.asarray(offsets, np.float64)
    y = np.asarray(y, np.float64)
    w = np.ones_like(z) if w is None else np.asarray(w, np.float64)
    if loss_name == "logistic_regression":
        per = np.logaddexp(0.0, z) - y * z
    elif loss_name == "linear_regression":
        per = 0.5 * (z - y) ** 2
    else:
        raise ValueError(f"unsupported oracle loss {loss_name!r}")
    diff = np.asarray(c, np.float64) - np.asarray(prior, np.float64)
    return float(np.sum(w * per) + 0.5 * anchor_weight * np.sum(diff * diff))
