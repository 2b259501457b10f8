"""Data layer: index maps, libsvm, GAME dataset build, entity blocking,
reservoir cap, Pearson selection, projection round-trips, stats, samplers.

Mirrors reference tests: PalDBIndexMapTest, AvroDataReaderIntegTest (format
level), RandomEffectDataSetTest grouping/cap semantics, LocalDataSetTest
feature filtering, BasicStatisticalSummaryTest, sampler tests.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data import (
    BasicStatisticalSummary, FixedEffectDataConfig, FixedEffectDataset,
    GameDataset, IndexMap, IndexMapCollection, RandomEffectDataConfig,
    binary_classification_downsample, build_game_dataset, build_index_map,
    build_random_effect_dataset, feature_key, read_libsvm,
)
from photon_ml_tpu.data.batching import (_BOUNDARY_LOSS, _SAMPLE_GRANULE,
                                          _bucket_bounds, _padded_samples)
from photon_ml_tpu.ops import LOGISTIC
from photon_ml_tpu.optim import RegularizationContext, RegularizationType
from photon_ml_tpu.parallel import fit_random_effects, score_by_entity
from photon_ml_tpu.utils.math import ceil_pow2


def test_index_map_roundtrip(tmp_path):
    imap = build_index_map([("age", ""), ("height", "cm"), ("age", "bucket1")])
    assert imap.has_intercept and imap.intercept_index == imap.size - 1
    assert imap.index_of("age", "bucket1") >= 0
    assert imap.index_of("nope") == -1
    assert imap.name_term(imap.index_of("height", "cm")) == ("height", "cm")

    p = str(tmp_path / "maps")
    coll = IndexMapCollection({"global": imap})
    coll.save(p)
    loaded = IndexMapCollection.load(p)
    assert loaded.shards["global"].key_to_index == imap.key_to_index


def test_index_map_deterministic():
    a = build_index_map([("b", ""), ("a", ""), ("c", "")])
    b = build_index_map([("c", ""), ("a", ""), ("b", "")])
    assert a.key_to_index == b.key_to_index


def test_libsvm_reader(tmp_path):
    p = tmp_path / "tiny.libsvm"
    p.write_text("+1 1:0.5 3:2.0\n-1 2:1.5\n+1 1:1.0 2:0.25 3:-1\n")
    x, y = read_libsvm(str(p))
    assert x.shape == (3, 4)  # 3 features + intercept
    np.testing.assert_allclose(y, [1, 0, 1])
    np.testing.assert_allclose(x[0], [0.5, 0, 2.0, 1.0])
    np.testing.assert_allclose(x[1], [0, 1.5, 0, 1.0])


def _toy_game_dataset(rng, n=60, d=6, num_users=7):
    x = rng.normal(size=(n, d)); x[:, -1] = 1.0
    y = (rng.uniform(size=n) > 0.5).astype(float)
    users = rng.choice([f"u{i}" for i in range(num_users)], size=n)
    return build_game_dataset(
        y, {"global": x},
        entity_ids={"per_user": users},
        weights=rng.uniform(0.5, 1.5, size=n))


def test_game_dataset_build_and_subset(rng):
    ds = _toy_game_dataset(rng)
    assert ds.num_rows == 60
    assert set(ds.entity_indices) == {"per_user"}
    assert (ds.entity_indices["per_user"] >= 0).all()
    # subset shares vocab
    sub = ds.subset(np.arange(10))
    assert sub.num_rows == 10
    assert sub.entity_vocabs is ds.entity_vocabs


def test_game_dataset_unseen_entities_map_to_minus1(rng):
    ds = _toy_game_dataset(rng)
    ds2 = build_game_dataset(
        np.zeros(3), {"global": np.zeros((3, 6))},
        entity_ids={"per_user": np.asarray(["u0", "zzz_new", "u1"])},
        entity_vocabs=ds.entity_vocabs)
    assert ds2.entity_indices["per_user"][1] == -1
    assert ds2.entity_indices["per_user"][0] >= 0


def test_random_effect_dataset_identity_projector(rng):
    ds = _toy_game_dataset(rng)
    red = build_random_effect_dataset(
        ds, RandomEffectDataConfig("per_user", "global", projector="identity"))
    E = red.num_entities
    assert E == len(np.unique(ds.entity_indices["per_user"]))
    # every real cell holds the right row
    for e in range(E):
        for s in range(red.blocks.samples_per_entity):
            r = red.active_row_ids[e, s]
            if r >= 0:
                np.testing.assert_allclose(np.asarray(red.blocks.x[e, s]),
                                           ds.feature_shards["global"][r])
                assert float(red.blocks.labels[e, s]) == ds.response[r]
    assert red.num_active == ds.num_rows


def test_random_effect_dataset_cap_rescales_weights(rng):
    ds = _toy_game_dataset(rng, n=200, num_users=3)
    cap = 10
    red = build_random_effect_dataset(
        ds, RandomEffectDataConfig("per_user", "global",
                                   active_data_upper_bound=cap,
                                   projector="identity"))
    counts = np.bincount(ds.entity_indices["per_user"])
    for e in range(red.num_entities):
        vocab_idx = red.entity_ids[e]
        kept = int(np.asarray(red.blocks.mask[e]).sum())
        assert kept <= cap
        if counts[vocab_idx] > cap:
            # total weight preserved in expectation: scale = count/cap
            w = np.asarray(red.blocks.weights[e])
            orig_w = ds.weights[red.active_row_ids[e][red.active_row_ids[e] >= 0]]
            np.testing.assert_allclose(
                w[np.asarray(red.blocks.mask[e]) > 0],
                orig_w * counts[vocab_idx] / cap, rtol=1e-12)
    assert red.num_passive > 0


def test_index_map_projection_roundtrip(rng):
    """Projected training must equal identity-projector training once
    coefficients are scattered back to global space."""
    n, d = 80, 12
    x = np.zeros((n, d))
    users = np.asarray([f"u{i % 4}" for i in range(n)])
    # each user only observes its own feature slice (+ shared intercept)
    for i in range(n):
        u = i % 4
        x[i, u * 3: u * 3 + 2] = rng.normal(size=2)
    x[:, -1] = 1.0
    y = (rng.uniform(size=n) > 0.5).astype(float)
    ds = build_game_dataset(y, {"g": x}, entity_ids={"per_user": users})

    red_p = build_random_effect_dataset(
        ds, RandomEffectDataConfig("per_user", "g", projector="index_map"))
    red_i = build_random_effect_dataset(
        ds, RandomEffectDataConfig("per_user", "g", projector="identity"))
    assert red_p.local_dim < d  # actually projected

    reg = RegularizationContext(RegularizationType.L2)
    rp = fit_random_effects(red_p.blocks, LOGISTIC, reg=reg, reg_weight=0.5)
    ri = fit_random_effects(red_i.blocks, LOGISTIC, reg=reg, reg_weight=0.5)
    global_p = red_p.scatter_to_global(rp.x)
    np.testing.assert_allclose(np.asarray(global_p), np.asarray(ri.x),
                               rtol=1e-6, atol=1e-8)

    # flat scoring through entity lanes matches block scoring
    lanes = red_p.flat_entity_lanes(ds.entity_indices["per_user"])
    s_flat = score_by_entity(global_p, jnp.asarray(x), jnp.asarray(lanes))
    assert s_flat.shape == (n,)


def test_pearson_feature_selection(rng):
    n = 40
    d = 30
    x = rng.normal(size=(n, d))
    w_true = np.zeros(d); w_true[:3] = 3.0  # only first 3 informative
    y = (x @ w_true + 0.1 * rng.normal(size=n) > 0).astype(float)
    x[:, -1] = 1.0
    users = np.asarray(["u0"] * n)
    ds = build_game_dataset(y, {"g": x}, entity_ids={"per_user": users})
    red = build_random_effect_dataset(
        ds, RandomEffectDataConfig("per_user", "g",
                                   features_to_samples_ratio=0.2,  # keep 8
                                   projector="index_map"))
    assert red.local_dim <= int(np.ceil(0.2 * n))
    kept = set(red.projection[0][red.projection[0] >= 0].tolist())
    assert {0, 1, 2} <= kept, f"informative features must survive, kept {kept}"
    assert d - 1 in kept, "the intercept must always survive feature selection"


def test_offsets_from_flat(rng):
    ds = _toy_game_dataset(rng)
    red = build_random_effect_dataset(
        ds, RandomEffectDataConfig("per_user", "g" if "g" in ds.feature_shards else "global",
                                   projector="identity"))
    flat = rng.normal(size=ds.num_rows)
    for bucket, blocks in zip(red.buckets, red.blocks_with_offsets(flat)):
        for e in range(bucket.num_entities):
            for s in range(blocks.samples_per_entity):
                r = bucket.row_ids[e, s]
                if r >= 0:
                    assert float(blocks.offsets[e, s]) == pytest.approx(
                        flat[r])
                else:
                    assert float(blocks.offsets[e, s]) == 0.0


def _bucket_cells(samples, bounds):
    """Padded cells of a partition: entities x the first (largest) S."""
    return int(sum((hi - lo) * samples[lo]
                   for lo, hi in zip(bounds[:-1], bounds[1:])))


def _parent_rule_bounds(counts_lane, max_buckets):
    """The rule before PR 30, kept as the reference to beat: ceil-power-of-
    two classes of the raw count, adjacent classes merged in groups of
    ceil(n_classes / max_buckets)."""
    classes, key = np.unique(ceil_pow2(counts_lane), return_inverse=True)
    if len(classes) > max_buckets:
        key = ((len(classes) - 1) - key) // -(-len(classes) // max_buckets)
    return np.concatenate([[0], np.flatnonzero(np.diff(key)) + 1,
                           [len(counts_lane)]])


def _skewed_counts(kind, seed, entities=4000, cap=512):
    """Per-entity row counts, descending, as a capped coordinate has them."""
    rng = np.random.default_rng(seed)
    raw = (rng.lognormal(3.5, 1.3, entities) if kind == "lognormal"
           else rng.zipf(1.3, entities))
    return -np.sort(-np.clip(raw.astype(np.int64), 1, cap))


def _cell_counts(config_name):
    """Training rows per user and, where the configuration has items as
    entities, per item, as its builder's generators draw them from
    `shape_seed` (counts only)."""
    import json
    from benchmark.builders.game_fit import N_USER_FEATS
    from benchmark.builders.game_fit_user_item import quantile_values
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "benchmark", "configs",
                           config_name + ".json")) as f:
        cfg = json.load(f)
    p = cfg["params"]
    n_val = int(round(p["validation_share"] * cfg["rows"]))
    shape = np.random.default_rng(p["shape_seed"])
    prop = shape.lognormal(0.0, 1.1, cfg["users"])
    users = shape.multinomial(cfg["rows"] - n_val, prop / prop.sum())
    drawn = [users]
    if "item_count_quantiles" in p:
        shape.multinomial(n_val, prop / prop.sum())
        shape.normal(size=cfg["genres"] + N_USER_FEATS + 1)
        prop = quantile_values(p["item_count_quantiles"], cfg["items"])
        drawn.append(shape.multinomial(cfg["rows"] - n_val,
                                       prop / prop.sum()))
    cap = p["active_data_upper_bound"]
    return [-np.sort(-np.minimum(c[c > 0], cap)) for c in drawn]


class TestBucketBounds:
    """The rule that places the S-bucket boundaries (`_bucket_bounds`), on
    count vectors alone: NumPy, no build, no device."""

    @pytest.mark.parametrize("cap", [1, 2, 3, 4, 5])
    @pytest.mark.parametrize("seed", range(6))
    def test_partition_equals_brute_force(self, seed, cap):
        import itertools
        rng = np.random.default_rng(seed)
        counts = -np.sort(-rng.integers(1, 200, size=rng.integers(6, 12)))
        samples = _padded_samples(counts)
        bounds = _bucket_bounds(samples, cap)
        E = len(counts)
        best = min(_bucket_cells(samples, [0, *cuts, E])
                   for k in range(cap)
                   for cuts in itertools.combinations(range(1, E), k))
        assert _bucket_cells(samples, bounds) == best
        assert len(bounds) - 1 <= cap
        assert bounds[0] == 0 and bounds[-1] == E
        assert (np.diff(bounds) > 0).all()

    @pytest.mark.parametrize("max_buckets", [2, 4, 6])
    @pytest.mark.parametrize("seed", range(3))
    @pytest.mark.parametrize("kind", ["lognormal", "zipf"])
    def test_cells_never_exceed_the_parent_rule(self, kind, seed,
                                                max_buckets):
        """Against the parent's boundaries on the same padded counts (what
        the chip lays out for either), under the same cap."""
        counts = _skewed_counts(kind, seed)
        samples = _padded_samples(counts)
        ours = _bucket_bounds(samples, max_buckets)
        theirs = _parent_rule_bounds(counts, max_buckets)
        assert len(ours) - 1 <= max_buckets
        assert _bucket_cells(samples, ours) <= _bucket_cells(samples, theirs)

    @pytest.mark.parametrize("seed", [3, 4])
    def test_uncapped_counts_are_thinned_within_the_stated_loss(self, seed):
        """Hundreds of distinct counts above 64 granules: the candidates are
        thinned to one a factor of 1 + _BOUNDARY_LOSS, and the cells stay
        within that share of the exact minimum (a plain dynamic programme
        over every distinct padded count)."""
        rng = np.random.default_rng(seed)
        counts = -np.sort(-np.clip(rng.lognormal(6.5, 1.2, 3000), 1, 50_000
                                   ).astype(np.int64))
        samples = _padded_samples(counts)
        assert len(np.unique(samples[samples > 512])) > 200
        first = np.concatenate([[0], np.flatnonzero(np.diff(samples)) + 1])
        end = np.append(first[1:], len(samples))
        best = [int(samples[0] * e) for e in end]
        for _ in range(3):
            best = [min([best[j]] + [best[i - 1] + int(samples[first[i]])
                                     * int(end[j] - first[i])
                                     for i in range(1, j + 1)])
                    for j in range(len(first))]
        got = _bucket_cells(samples, _bucket_bounds(samples, 4))
        assert best[-1] <= got <= (1 + _BOUNDARY_LOSS) * best[-1]

    def test_no_cap_and_a_cap_of_one(self):
        counts = _skewed_counts("lognormal", 1)
        samples = _padded_samples(counts)
        np.testing.assert_array_equal(_bucket_bounds(samples, 1),
                                      [0, len(counts)])
        bounds = _bucket_bounds(samples, None)
        classes = ceil_pow2(samples[bounds[:-1]]).tolist()  # one a class
        assert classes == sorted(set(ceil_pow2(samples).tolist()),
                                 reverse=True)
        alike = _padded_samples(np.full(100, 37))
        np.testing.assert_array_equal(_bucket_bounds(alike, 4), [0, 100])

    def test_benchmark_cells_shed_the_stated_share_of_cells(self):
        """At the two configurations' own counts: the parent's rule gives
        the bucket shapes the configuration file states (so the counts are
        the cells'), per-user cells fall to at most 0.63 and per-item cells
        to at most 0.82 of them, under a third of all cells stay empty, and
        the search takes no time set-up could see."""
        import time
        users, items = _cell_counts("glmix-ml20m-user-item")
        np.testing.assert_array_equal(users, *_cell_counts("glmix-ml20m"))
        stated = {"users": [(30_518, 512), (23_221, 64), (1_594, 8), (45, 1)],
                  "items": [(7_220, 512), (5_556, 64), (6_475, 8), (3_406, 1)]}
        cells = real = 0
        for name, counts, share in (("users", users, 0.63),
                                    ("items", items, 0.82)):
            parent = _parent_rule_bounds(counts, 4)
            assert [(hi - lo, counts[lo]) for lo, hi in
                    zip(parent[:-1], parent[1:])] == stated[name]
            samples = _padded_samples(counts)
            t0 = time.perf_counter()
            bounds = _bucket_bounds(samples, 4)
            assert time.perf_counter() - t0 < 0.1
            assert len(bounds) - 1 <= 4
            ours = _bucket_cells(samples, bounds)
            assert ours <= share * _bucket_cells(counts, parent)
            cells, real = cells + ours, real + int(counts.sum())
        assert (cells - real) / cells < 0.33


class TestBucketedBuild:
    """S-bucketed RE build (VERDICT r2 item #2): multiple size classes, no
    hot-entity padding blowup, per-bucket solves equal the single-block
    solve."""

    def _skewed_dataset(self, rng, num_small=50, small_n=4, big_n=512, d=5):
        """num_small entities with small_n rows each + one hot entity."""
        sizes = [small_n] * num_small + [big_n]
        users, n = [], sum(sizes)
        for u, sz in enumerate(sizes):
            users += [f"u{u:04d}"] * sz
        x = rng.normal(size=(n, d)); x[:, -1] = 1.0
        y = (rng.uniform(size=n) < 0.5).astype(float)
        return build_game_dataset(y, {"g": x},
                                  entity_ids={"per_user": np.asarray(users)})

    def test_buckets_bound_padding(self, rng):
        ds = self._skewed_dataset(rng, small_n=8)      # one sample granule
        red = build_random_effect_dataset(
            ds, RandomEffectDataConfig("per_user", "g", projector="identity"))
        stats = red.build_counts
        assert len(stats["buckets"]) >= 2
        # single-S layout wastes >90% of cells on this skew; buckets fix it
        assert stats["active_rows"] < 0.1 * red.num_entities * red.max_samples
        assert stats["active_rows"] > 0.9 * stats["cells"]
        # lanes are count-descending and cover all rows exactly once
        per_lane = (np.asarray(red.active_row_ids) >= 0).sum(axis=1)
        assert (np.diff(per_lane) <= 0).all()
        assert red.num_active == ds.num_rows
        ids = np.asarray(red.active_row_ids)
        real = np.sort(ids[ids >= 0])
        np.testing.assert_array_equal(real, np.arange(ds.num_rows))

    @pytest.mark.parametrize("max_buckets", [1, 2, 4, None])
    def test_buckets_are_contiguous_runs_of_granule_multiples(
            self, rng, max_buckets):
        sizes = np.clip(rng.lognormal(2.5, 1.2, 60).astype(int), 1, 300)
        users = np.repeat(np.arange(60), sizes)
        x = rng.normal(size=(len(users), 3))
        ds = build_game_dataset((rng.uniform(size=len(users)) < 0.5) * 1.0,
                                {"g": x}, entity_ids={"per_user": users})
        red = build_random_effect_dataset(
            ds, RandomEffectDataConfig("per_user", "g", projector="identity",
                                       max_buckets=max_buckets))
        if max_buckets is not None:
            assert len(red.buckets) <= max_buckets
        lane, per_lane = 0, []
        for b in red.buckets:
            assert b.lane_start == lane
            lane += b.num_entities
            real = (b.row_ids >= 0).sum(axis=1)
            assert b.samples_per_entity % _SAMPLE_GRANULE == 0
            assert real.max() <= b.samples_per_entity < \
                real.max() + _SAMPLE_GRANULE
            per_lane.append(real)
        assert lane == red.num_entities == 60
        per_lane = np.concatenate(per_lane)
        assert (np.diff(per_lane) <= 0).all()           # count-descending
        np.testing.assert_array_equal(
            per_lane, np.bincount(users)[red.entity_ids])
        assert red.build_counts["cells"] == _bucket_cells(
            _padded_samples(per_lane),
            [b.lane_start for b in red.buckets] + [60])

    def test_bucketed_solve_equals_single_block(self, rng):
        ds = self._skewed_dataset(rng, num_small=10, big_n=64)
        red = build_random_effect_dataset(
            ds, RandomEffectDataConfig("per_user", "g", projector="identity"))
        assert len(red.buckets) >= 2
        reg = RegularizationContext(RegularizationType.L2)
        parts = [fit_random_effects(b.blocks, LOGISTIC, reg=reg, reg_weight=0.5).x
                 for b in red.buckets]
        per_bucket = np.concatenate([np.asarray(p) for p in parts])
        single = np.asarray(fit_random_effects(red.blocks, LOGISTIC, reg=reg,
                                               reg_weight=0.5).x)
        np.testing.assert_allclose(per_bucket, single, rtol=1e-6, atol=1e-8)

    def test_bucketed_game_training_matches_history(self, rng):
        """End-to-end: GAME fit over a skewed dataset produces a finite,
        decreasing objective with the bucketed RE path."""
        from photon_ml_tpu.game import (FixedEffectCoordinateConfig,
                                        GameEstimator, GameTrainingConfig,
                                        GLMOptimizationConfig,
                                        RandomEffectCoordinateConfig)
        ds = self._skewed_dataset(rng, num_small=12, big_n=96)
        cfg = GameTrainingConfig(
            task_type="logistic_regression",
            coordinates={
                "fixed": FixedEffectCoordinateConfig(
                    "g", GLMOptimizationConfig(regularization_weight=0.1)),
                "perUser": RandomEffectCoordinateConfig(
                    random_effect_type="per_user", feature_shard="g",
                    optimization=GLMOptimizationConfig(regularization_weight=1.0)),
            },
            updating_sequence=["fixed", "perUser"], num_outer_iterations=2)
        res = GameEstimator(cfg).fit(ds)
        hist = res.objective_history
        assert np.isfinite(hist).all() and hist[-1] <= hist[0]

    def test_million_entity_build_seconds(self, rng):
        # VERDICT r2 item #2 gate: 1e6-entity build in seconds, not O(E) loops
        import time
        E, d = 1_000_000, 8
        n = 3 * E
        users = rng.integers(0, E, size=n)
        x = rng.normal(size=(n, d)).astype(np.float32)
        y = (rng.uniform(size=n) < 0.5).astype(np.float32)
        ds = build_game_dataset(y, {"g": x}, entity_ids={"per_user": users})
        t0 = time.perf_counter()
        red = build_random_effect_dataset(
            ds, RandomEffectDataConfig("per_user", "g", projector="identity",
                                       active_data_upper_bound=16),
            dtype=np.float32)
        dt = time.perf_counter() - t0
        assert red.num_entities <= E
        # three rows an entity, a granule of cells each: all but the few
        # entities with more than a granule share the S = granule bucket
        assert red.build_counts["cells"] < \
            1.01 * _SAMPLE_GRANULE * red.num_entities
        assert dt < 60.0, f"1e6-entity build took {dt:.1f}s"


def test_stats_summary(rng):
    x = rng.normal(size=(50, 4)); x[:, 2] = 0.0
    s = BasicStatisticalSummary.from_features(x)
    np.testing.assert_allclose(s.mean, x.mean(0))
    np.testing.assert_allclose(s.variance, x.var(0, ddof=1))
    assert s.num_nonzeros[2] == 0
    assert s.count == 50
    np.testing.assert_allclose(s.max_magnitude, np.abs(x).max(0))


def test_binary_downsampler_unbiased(rng):
    labels = jnp.asarray((np.arange(10000) % 4 == 0).astype(float))  # 25% pos
    key = jax.random.PRNGKey(0)
    mask, w = binary_classification_downsample(key, labels, None, 0.3)
    # all positives kept
    assert bool(jnp.all(mask[labels > 0.5] == 1.0))
    # negative weight sum approximately preserved
    neg = labels < 0.5
    kept_negative_weight = float(jnp.sum(mask[neg] * w[neg]))
    assert abs(kept_negative_weight - float(jnp.sum(neg))) / float(jnp.sum(neg)) < 0.05
    with pytest.raises(ValueError):
        binary_classification_downsample(key, labels, None, 1.5)


def test_sparse_summary_matches_dense(rng):
    """BasicStatisticalSummary.from_sparse == from_features on the
    densified shard (the wide-regime stats path never densifies)."""
    import scipy.sparse as sp

    from photon_ml_tpu.data.stats import BasicStatisticalSummary

    x = sp.random(50, 12, density=0.3, format="csr", random_state=5)
    w = rng.uniform(0.5, 2.0, 50)
    for weights in (None, w):
        a = BasicStatisticalSummary.from_sparse(x, weights)
        b = BasicStatisticalSummary.from_features(x.toarray(), weights)
        for field in ("mean", "variance", "num_nonzeros", "max", "min",
                      "norm_l1", "norm_l2", "mean_abs"):
            np.testing.assert_allclose(getattr(a, field), getattr(b, field),
                                       rtol=1e-10, atol=1e-12, err_msg=field)


# -- what a fit's prologue derives from the dataset alone is kept with it -----

def _lane_dataset(rng, n=240, users=12):
    x = rng.normal(size=(n, 5)); x[:, -1] = 1.0
    ids = np.asarray([f"u{rng.integers(users):02d}" for _ in range(n)])
    y = (rng.uniform(size=n) > 0.5).astype(float)
    return build_game_dataset(y, {"g": x}, entity_ids={"userId": ids},
                              weights=rng.uniform(0.5, 2.0, size=n))


def _lane_coordinate(ds, cap, seed=7):
    from photon_ml_tpu.game import (GLMOptimizationConfig,
                                    RandomEffectCoordinateConfig)
    from photon_ml_tpu.game.coordinates import RandomEffectCoordinate
    cfg = RandomEffectCoordinateConfig("userId", "g", GLMOptimizationConfig(),
                                       projector="identity",
                                       active_data_upper_bound=cap)
    return RandomEffectCoordinate("perUser", ds, cfg, "logistic_regression",
                                  seed=seed)


@pytest.mark.parametrize("other", ["same", "subset", "validation", "cap",
                                   "seed"])
def test_lane_map_is_made_once_a_dataset_and_configuration(rng, other):
    """Two coordinates over one dataset and one data configuration read ONE
    row -> lane map, the memoised build's; another dataset (a subset, a
    validation split) or another configuration (cap, seed) gets its own, and
    every one of them is `flat_entity_lanes` of its own entity column."""
    ds = _lane_dataset(rng)
    first = _lane_coordinate(ds, 16)
    if other == "same":
        second = _lane_coordinate(ds, 16)
        assert second.red is first.red
        assert second.lanes is first.lanes
    elif other in ("subset", "validation"):
        rows = (np.arange(0, ds.num_rows, 2) if other == "subset"
                else np.arange(ds.num_rows - 60, ds.num_rows))
        part = ds.subset(rows)
        second = _lane_coordinate(part, 16)
        assert second.lanes is not first.lanes
        assert second.lanes.shape == (len(rows),)
        ds = part
    elif other == "cap":
        second = _lane_coordinate(ds, 8)
        assert second.red is not first.red
        assert second.lanes is not first.lanes
    else:
        second = _lane_coordinate(ds, 16, seed=11)
        assert second.red is not first.red
        assert second.lanes is not first.lanes
    np.testing.assert_array_equal(
        np.asarray(second.lanes),
        second.red.flat_entity_lanes(ds.entity_indices["userId"]))


@pytest.mark.parametrize("name", ["response", "weights", "offsets"])
def test_device_vector_is_one_copy_a_dataset_and_dtype(rng, name):
    """The flat labels / weights / offsets a coordinate or the descent reads
    are ONE device copy a (dataset, dtype): the same object at every call,
    another one a dtype, made anew when the field is another array, None
    where the dataset has no such vector, and a subset's own."""
    ds = _lane_dataset(rng)
    host = getattr(ds, name)
    if host is None:
        assert ds.device_vector(name) is None
        return
    dev = ds.device_vector(name)
    assert ds.device_vector(name) is dev
    assert dev.dtype == jax.dtypes.canonicalize_dtype(host.dtype)
    np.testing.assert_array_equal(np.asarray(dev), host.astype(dev.dtype))
    single = ds.device_vector(name, jnp.float32)
    assert single.dtype == jnp.float32
    assert ds.device_vector(name, jnp.float32) is single
    assert (single is dev) == (dev.dtype == jnp.float32)
    assert ds.subset(np.arange(10)).device_vector(name) is not dev
    setattr(ds, name, host * 2.0)
    again = ds.device_vector(name)
    assert again is not dev
    np.testing.assert_array_equal(np.asarray(again),
                                  (host * 2.0).astype(dev.dtype))
