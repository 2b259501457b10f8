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
from photon_ml_tpu.ops import LOGISTIC
from photon_ml_tpu.optim import RegularizationContext, RegularizationType
from photon_ml_tpu.parallel import fit_random_effects, score_by_entity


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
    blocks = red.with_offsets_from_flat(flat)
    for e in range(red.num_entities):
        for s in range(blocks.samples_per_entity):
            r = red.active_row_ids[e, s]
            if r >= 0:
                assert float(blocks.offsets[e, s]) == pytest.approx(flat[r])
            else:
                assert float(blocks.offsets[e, s]) == 0.0


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
        ds = self._skewed_dataset(rng)
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
        assert red.build_counts["active_rows"] > \
            0.5 * red.build_counts["cells"]
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
