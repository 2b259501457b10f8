"""Online scoring subsystem tests (photon_ml_tpu/serving/).

Covers the ISSUE acceptance scenario: a warm service on CPU serves a
64-request concurrent burst against an FE + 1 RE GAME model with zero
recompiles after warmup, scores matching the offline scoring path to 1e-6,
surviving a mid-burst hot swap with no failed requests; plus bucket padding
parity, entity-miss fixed-effect fallback, load shedding / deadlines, the
registry event stream, and a `cli.serve` end-to-end smoke test.
"""
import json
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax.numpy as jnp
import numpy as np
import pytest

from photon_ml_tpu.data.game_data import build_game_dataset, save_game_dataset
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.game import (FixedEffectModel, GameModel,
                                       MatrixFactorizationModel,
                                       RandomEffectModel)
from photon_ml_tpu.models.glm import model_for_task
from photon_ml_tpu.models.io import save_game_model
from photon_ml_tpu.serving import (BatcherConfig, CompiledScorer,
                                   DeadlineExceeded, MicroBatcher,
                                   ModelRegistry, Overloaded, ScoringService,
                                   ServingConfig)
from photon_ml_tpu.utils.events import (EventEmitter, EventListener,
                                        ModelSwapEvent, ScoringBatchEvent)
from photon_ml_tpu.utils.math import ceil_pow2
from tests.test_io_cli import child_env

D_G, D_U, N_ENT = 6, 4, 20


def _make_model(rng, task="linear_regression", coef_scale=1.0):
    fe = FixedEffectModel(
        model_for_task(task, Coefficients(
            jnp.asarray(coef_scale * rng.normal(size=D_G)))), "global")
    re = RandomEffectModel(
        random_effect_type="userId", feature_shard="per_user",
        task_type=task,
        coefficients=jnp.asarray(coef_scale * rng.normal(size=(N_ENT, D_U))),
        entity_ids=np.asarray([f"u{i}" for i in range(N_ENT)], dtype=object),
        projection=None, global_dim=D_U)
    return GameModel({"fixed": fe, "perUser": re}, task)


def _make_dataset(rng, n=64, unseen_frac=0.25):
    """Rows over the model's entity space; a fraction carries ids no model
    has seen (they must fall back to fixed-effect-only scores)."""
    ids = np.asarray([f"u{rng.integers(0, N_ENT)}" if rng.uniform() > unseen_frac
                      else f"ghost{rng.integers(0, 5)}" for _ in range(n)],
                     dtype=object)
    return build_game_dataset(
        rng.normal(size=n),
        {"global": rng.normal(size=(n, D_G)),
         "per_user": rng.normal(size=(n, D_U))},
        entity_ids={"userId": ids})


def _svc_config(**kw):
    kw.setdefault("max_batch", 64)
    kw.setdefault("min_bucket", 4)
    kw.setdefault("max_wait_s", 0.002)
    return ServingConfig(**kw)


# -- shared bucket helper --------------------------------------------------

def test_ceil_pow2_scalar_and_array():
    assert [ceil_pow2(v) for v in (1, 2, 3, 4, 5, 63, 64, 65)] == \
        [1, 2, 4, 4, 8, 64, 64, 128]
    np.testing.assert_array_equal(ceil_pow2(np.array([1, 3, 1000])),
                                  [1, 4, 1024])


# -- compiled scorer -------------------------------------------------------

def test_scorer_matches_offline_scoring(rng):
    model = _make_model(rng)
    ds = _make_dataset(rng, n=50)
    scorer = CompiledScorer(model, max_batch=64, min_bucket=4)
    scorer.warmup()
    feats, ids = scorer.requests_from_dataset(ds, np.arange(ds.num_rows))
    res = scorer.score(feats, ids)
    np.testing.assert_allclose(res.scores,
                               np.asarray(model.score_dataset(ds)),
                               atol=1e-6, rtol=1e-6)
    # hit accounting: exactly the rows whose id the model knows
    lanes = model.coordinates["perUser"].lanes_for(ds)
    assert res.entity_hits == int((lanes >= 0).sum())
    assert res.entity_lookups == ds.num_rows


def test_bucket_padding_parity(rng):
    """Padded-bucket scores == per-row scores == offline scores, for sizes
    that land in different buckets."""
    model = _make_model(rng)
    scorer = CompiledScorer(model, max_batch=64, min_bucket=4)
    ds = _make_dataset(rng, n=13)  # pads to bucket 16
    feats, ids = scorer.requests_from_dataset(ds, np.arange(13))
    batched = scorer.score(feats, ids).scores
    singly = np.concatenate([
        scorer.score({s: v[[i]] for s, v in feats.items()},
                     {t: v[[i]] for t, v in ids.items()}).scores
        for i in range(13)])
    np.testing.assert_allclose(batched, singly, atol=1e-9)
    np.testing.assert_allclose(batched, np.asarray(model.score_dataset(ds)),
                               atol=1e-6, rtol=1e-6)


def test_entity_miss_scores_fixed_effect_only(rng):
    model = _make_model(rng)
    scorer = CompiledScorer(model, max_batch=64, min_bucket=4)
    n = 6
    feats = {"global": rng.normal(size=(n, D_G)),
             "per_user": rng.normal(size=(n, D_U))}
    ids = {"userId": np.asarray(["never-seen"] * n, dtype=object)}
    res = scorer.score(feats, ids)
    fe_only = feats["global"] @ np.asarray(
        model.coordinates["fixed"].glm.coefficients.means)
    np.testing.assert_allclose(res.scores, fe_only, atol=1e-9)
    assert res.entity_hits == 0


def test_zero_recompiles_after_warmup(rng):
    model = _make_model(rng)
    scorer = CompiledScorer(model, max_batch=64, min_bucket=4)
    scorer.warmup()
    assert scorer.bucket_compiles == len(scorer.bucket_sizes()) == 5
    ds = _make_dataset(rng, n=200)  # > max_batch: exercises chunking too
    for size in (1, 3, 4, 7, 33, 64, 200):
        rows = np.arange(size)
        feats, ids = scorer.requests_from_dataset(ds, rows)
        res = scorer.score(feats, ids)
        assert res.new_compiles == 0, f"size {size} recompiled"
    assert scorer.bucket_compiles == 5


def test_scorer_chunking_beyond_max_batch(rng):
    model = _make_model(rng)
    scorer = CompiledScorer(model, max_batch=16, min_bucket=4)
    ds = _make_dataset(rng, n=70)
    feats, ids = scorer.requests_from_dataset(ds, np.arange(70))
    res = scorer.score(feats, ids)
    assert res.buckets == [16, 16, 16, 16, 8]  # 70 = 4*16 + 6->8
    np.testing.assert_allclose(res.scores, np.asarray(model.score_dataset(ds)),
                               atol=1e-6, rtol=1e-6)


def test_scorer_request_validation(rng):
    scorer = CompiledScorer(_make_model(rng), max_batch=8, min_bucket=4)
    x = {"global": np.zeros((3, D_G)), "per_user": np.zeros((3, D_U))}
    ok_ids = {"userId": np.asarray(["u1"] * 3, dtype=object)}
    with pytest.raises(ValueError, match="missing feature shard"):
        scorer.validate_request({"global": x["global"]}, ok_ids)
    with pytest.raises(ValueError, match=r"must be \[n, 4\]"):
        scorer.validate_request({**x, "per_user": np.zeros((3, 9))}, ok_ids)
    with pytest.raises(ValueError, match="missing entity id"):
        scorer.validate_request(x, {})
    with pytest.raises(ValueError, match="userId"):
        scorer.validate_request(x, {"userId": np.zeros(5, dtype=object)})


def test_scorer_mf_coordinate_parity(rng):
    """A model with a matrix-factorization coordinate serves through the
    same program (row/col factor dots, either side unseen -> 0)."""
    task = "linear_regression"
    model = _make_model(rng, task=task)
    R, C, k = 10, 7, 3
    mf = MatrixFactorizationModel(
        row_effect_type="userId", col_effect_type="itemId",
        row_factors=jnp.asarray(rng.normal(size=(R, k))),
        row_ids=np.asarray([f"u{i}" for i in range(R)], dtype=object),
        col_factors=jnp.asarray(rng.normal(size=(C, k))),
        col_ids=np.asarray([f"i{j}" for j in range(C)], dtype=object))
    model = GameModel({**model.coordinates, "mf": mf}, task)
    n = 30
    user_ids = np.asarray([f"u{rng.integers(0, N_ENT)}" for _ in range(n)],
                          dtype=object)
    item_ids = np.asarray([f"i{rng.integers(0, 10)}" for _ in range(n)],
                          dtype=object)  # some >= C: unseen columns
    ds = build_game_dataset(
        rng.normal(size=n),
        {"global": rng.normal(size=(n, D_G)),
         "per_user": rng.normal(size=(n, D_U))},
        entity_ids={"userId": user_ids, "itemId": item_ids})
    scorer = CompiledScorer(model, max_batch=32, min_bucket=4)
    feats, ids = scorer.requests_from_dataset(ds, np.arange(n))
    res = scorer.score(feats, ids)
    np.testing.assert_allclose(res.scores, np.asarray(model.score_dataset(ds)),
                               atol=1e-6, rtol=1e-6)


def test_requests_from_sparse_dataset(rng):
    """Sparse dataset shards densify per request slice."""
    import scipy.sparse as sp
    model = _make_model(rng)
    n = 12
    xg = rng.normal(size=(n, D_G)) * (rng.uniform(size=(n, D_G)) < 0.4)
    ds = build_game_dataset(
        rng.normal(size=n),
        {"global": sp.csr_matrix(xg),
         "per_user": rng.normal(size=(n, D_U))},
        entity_ids={"userId": np.asarray([f"u{i % N_ENT}" for i in range(n)],
                                         dtype=object)})
    scorer = CompiledScorer(model, max_batch=16, min_bucket=4)
    feats, ids = scorer.requests_from_dataset(ds, np.arange(n))
    res = scorer.score(feats, ids)
    np.testing.assert_allclose(res.scores, np.asarray(model.score_dataset(ds)),
                               atol=1e-6, rtol=1e-6)


# -- micro-batcher ---------------------------------------------------------

class _FakeResult:
    def __init__(self, scores):
        self.scores = scores


def test_microbatcher_coalesces_concurrent_requests(rng):
    """Many threads, one device call per coalesced batch, row-exact
    results."""
    calls = []

    def score_fn(feats, ids, *, num_requests, queue_wait_s):
        calls.append(num_requests)
        return _FakeResult(np.asarray(feats["x"]).sum(axis=1))

    b = MicroBatcher(score_fn, BatcherConfig(max_wait_s=0.01, max_batch=256,
                                             max_queue=512))
    try:
        def one(i):
            n = 1 + i % 4
            x = np.full((n, 2), float(i))
            out = b.score({"x": x}, {}, n)
            np.testing.assert_allclose(out, np.full(n, 2.0 * i))
            return len(out)

        with ThreadPoolExecutor(max_workers=16) as pool:
            sizes = list(pool.map(one, range(80)))
        assert sum(sizes) == sum(1 + i % 4 for i in range(80))
        assert sum(calls) == 80          # every request scored exactly once
        assert len(calls) < 80           # and at least some coalescing
    finally:
        b.close()


def test_microbatcher_overload_and_deadline():
    release = threading.Event()

    def slow_fn(feats, ids, *, num_requests, queue_wait_s):
        release.wait(5.0)
        return _FakeResult(np.zeros(sum(1 for _ in feats["x"])))

    b = MicroBatcher(slow_fn, BatcherConfig(max_wait_s=0.0, max_batch=4,
                                            max_queue=2))
    try:
        results = {}

        def bg(name, timeout=None):
            def run():
                try:
                    results[name] = b.score({"x": np.zeros((1, 1))}, {}, 1,
                                            timeout=timeout)
                except Exception as e:
                    results[name] = e
            t = threading.Thread(target=run, daemon=True)
            t.start()
            return t

        t1 = bg("first")            # taken by the worker, blocks in slow_fn
        time.sleep(0.15)
        t2 = bg("queued-expired", timeout=0.01)  # queued; deadline passes
        time.sleep(0.05)
        t3 = bg("queued-ok")
        time.sleep(0.05)            # queue now holds 2 pending requests
        with pytest.raises(Overloaded):
            b.score({"x": np.zeros((1, 1))}, {}, 1)
        release.set()
        for t in (t1, t2, t3):
            t.join(timeout=10.0)
        assert isinstance(results["queued-expired"], DeadlineExceeded)
        assert isinstance(results["first"], np.ndarray)
        assert isinstance(results["queued-ok"], np.ndarray)
    finally:
        release.set()
        b.close()


def test_batcher_error_propagates_to_batch_only():
    def flaky(feats, ids, *, num_requests, queue_wait_s):
        if np.asarray(feats["x"]).sum() < 0:
            raise RuntimeError("scorer exploded")
        return _FakeResult(np.zeros(len(feats["x"])))

    b = MicroBatcher(flaky, BatcherConfig(max_wait_s=0.0, max_batch=8,
                                          max_queue=8))
    try:
        with pytest.raises(RuntimeError, match="scorer exploded"):
            b.score({"x": -np.ones((1, 1))}, {}, 1)
        assert b.score({"x": np.ones((1, 1))}, {}, 1).shape == (1,)
    finally:
        b.close()


# -- service + registry ----------------------------------------------------

class _Recorder(EventListener):
    def __init__(self):
        self.events = []

    def handle(self, event):
        self.events.append(event)


def test_service_concurrent_burst_matches_offline(rng):
    """The acceptance burst: 64 concurrent single-row requests, zero
    recompiles after warmup, offline-parity scores, metrics populated."""
    model = _make_model(rng)
    ds = _make_dataset(rng, n=64)
    offline = np.asarray(model.score_dataset(ds))
    with ScoringService(model=model, config=_svc_config()) as svc:
        scorer = svc.registry.scorer
        warm_compiles = scorer.bucket_compiles
        out = np.empty(64)

        def one(i):
            feats, ids = scorer.requests_from_dataset(ds, np.asarray([i]))
            out[i] = svc.score(feats, ids)[0]

        with ThreadPoolExecutor(max_workers=16) as pool:
            list(pool.map(one, range(64)))
        np.testing.assert_allclose(out, offline, atol=1e-6, rtol=1e-6)
        assert scorer.bucket_compiles == warm_compiles, "burst recompiled"
        snap = svc.metrics_snapshot()
    assert snap["requests"] == 64
    assert snap["batches"] <= 64
    assert snap["latency_ms"]["p50"] >= 0
    assert snap["latency_ms"]["p99"] >= snap["latency_ms"]["p50"]
    assert 0 < snap["batch_occupancy"] <= 1
    assert 0 <= snap["entity_hit_rate"] <= 1
    assert snap["bucket_compiles"] == 0  # all compiles happened pre-traffic


def test_hot_swap_mid_burst_no_dropped_requests(rng, tmp_path):
    model_a = _make_model(rng, coef_scale=1.0)
    model_b = _make_model(rng, coef_scale=5.0)
    dir_a, dir_b = str(tmp_path / "v1"), str(tmp_path / "v2")
    save_game_model(model_a, dir_a)
    save_game_model(model_b, dir_b)
    ds = _make_dataset(rng, n=40)
    score_a = np.asarray(model_a.score_dataset(ds))
    score_b = np.asarray(model_b.score_dataset(ds))
    emitter = EventEmitter()
    rec = _Recorder()
    emitter.register_listener(rec)
    with ScoringService(model_dir=dir_a, config=_svc_config(),
                        emitter=emitter) as svc:
        assert "v1" in svc.model_version
        scorer = svc.registry.scorer
        failures = []
        matched = []  # list.append is thread-safe under the GIL

        def one(i):
            row = np.asarray([i % ds.num_rows])
            feats, ids = scorer.requests_from_dataset(ds, row)
            try:
                s = svc.score(feats, ids)[0]
            except Exception as e:
                failures.append(e)
                return
            if abs(s - score_a[row[0]]) < 1e-6:
                matched.append("a")
            elif abs(s - score_b[row[0]]) < 1e-6:
                matched.append("b")
            else:
                failures.append(f"row {row[0]}: {s} matches neither model")

        swap_done = []

        def swapper():
            time.sleep(0.01)
            swap_done.append(svc.swap(dir_b))

        t = threading.Thread(target=swapper)
        t.start()
        with ThreadPoolExecutor(max_workers=12) as pool:
            list(pool.map(one, range(120)))
        t.join()
        assert not failures, failures[:5]
        assert len(matched) == 120  # nothing dropped mid-swap

        # post-swap traffic is all on the new model
        feats, ids = scorer.requests_from_dataset(ds, np.arange(10))
        np.testing.assert_allclose(svc.score(feats, ids), score_b[:10],
                                   atol=1e-6)
        assert "v2" in svc.model_version

        # rollback restores the old scores
        svc.rollback()
        assert "v1" in svc.model_version
        np.testing.assert_allclose(svc.score(feats, ids), score_a[:10],
                                   atol=1e-6)
    swaps = [e for e in rec.events if isinstance(e, ModelSwapEvent)]
    assert [e.action for e in swaps][-2:] == ["swap", "rollback"]
    batches = [e for e in rec.events if isinstance(e, ScoringBatchEvent)]
    assert batches and all(e.bucket_size >= e.num_rows or True
                           for e in batches)
    assert sum(e.num_rows for e in batches) >= 120


def test_registry_requires_loaded_model():
    reg = ModelRegistry(lambda d, v: None)
    with pytest.raises(RuntimeError, match="no model loaded"):
        _ = reg.scorer
    with pytest.raises(RuntimeError, match="no previous model"):
        reg.rollback()


def test_register_listener_class_bad_paths():
    em = EventEmitter()
    with pytest.raises(ValueError, match="no.such.module.Listener"):
        em.register_listener_class("no.such.module.Listener")
    with pytest.raises(ValueError, match="NoSuchListener"):
        em.register_listener_class("photon_ml_tpu.utils.events.NoSuchListener")
    with pytest.raises(ValueError, match="not a dotted"):
        em.register_listener_class("justaname")


def test_cli_score_predict_avro_is_an_error(tmp_path):
    from photon_ml_tpu.cli.score import main as score_main
    with pytest.raises(SystemExit) as exc:
        score_main(["--model-dir", str(tmp_path), "--data", "x.npz",
                    "--output", "y", "--format", "avro", "--predict"])
    assert exc.value.code == 2  # argparse parser.error


# -- cli.serve end-to-end --------------------------------------------------

def _run_cli(module, argv):
    return subprocess.run([sys.executable, "-m", module] + argv,
                          capture_output=True, text=True, env=child_env(),
                          timeout=420)


@pytest.fixture
def served_model(tmp_path):
    rng = np.random.default_rng(3)
    model = _make_model(rng)
    ds = _make_dataset(rng, n=48)
    model_dir = str(tmp_path / "model")
    data_p = str(tmp_path / "requests.npz")
    save_game_model(model, model_dir)
    save_game_dataset(ds, data_p)
    return model_dir, data_p, tmp_path


def test_cli_serve_burst_smoke_matches_cli_score(served_model):
    model_dir, data_p, tmp = served_model
    serve_out = str(tmp / "serve_scores.npz")
    r = _run_cli("photon_ml_tpu.cli.serve",
                 ["--model-dir", model_dir, "--burst", data_p,
                  "--request-rows", "3", "--threads", "6",
                  "--max-batch", "32", "--min-bucket", "4",
                  "--output", serve_out])
    assert r.returncode == 0, r.stderr[-2000:]
    result = json.loads(r.stdout.strip().splitlines()[-1])
    assert result["rows"] == 48 and result["failed_requests"] == 0
    m = result["metrics"]
    assert m["requests"] == result["requests"]
    assert m["latency_ms"]["p99"] >= m["latency_ms"]["p50"] >= 0
    assert 0 < m["batch_occupancy"] <= 1
    assert 0 <= m["entity_hit_rate"] <= 1
    assert m["bucket_compiles"] == 0  # warmup precedes all traffic

    score_out = str(tmp / "score_scores.npz")
    r2 = _run_cli("photon_ml_tpu.cli.score",
                  ["--model-dir", model_dir, "--data", data_p,
                   "--output", score_out])
    assert r2.returncode == 0, r2.stderr[-2000:]
    np.testing.assert_allclose(np.load(serve_out)["scores"],
                               np.load(score_out)["scores"],
                               atol=1e-6, rtol=1e-6)


def test_cli_serve_http_roundtrip(served_model):
    import urllib.request

    model_dir, data_p, _ = served_model
    env = child_env()
    proc = subprocess.Popen(
        [sys.executable, "-m", "photon_ml_tpu.cli.serve",
         "--model-dir", model_dir, "--port", "0", "--max-batch", "32",
         "--min-bucket", "4"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env)
    try:
        startup = json.loads(proc.stdout.readline())
        base = startup["serving"]
        assert startup["buckets"] == [4, 8, 16, 32]

        def post(path, payload):
            req = urllib.request.Request(
                base + path, data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=30) as resp:
                return json.loads(resp.read())

        body = {"features": {"global": [[1.0] * D_G, [0.5] * D_G],
                             "per_user": [[1.0] * D_U, [0.5] * D_U]},
                "ids": {"userId": ["u1", "ghost"]}}
        out = post("/score", body)
        assert len(out["scores"]) == 2
        with urllib.request.urlopen(base + "/metrics.json",
                                    timeout=30) as resp:
            metrics = json.loads(resp.read())
        assert metrics["requests"] == 1 and metrics["rows"] == 2
        assert metrics["latency_ms"]["p95"] >= 0
        # /metrics is the Prometheus scrape endpoint (text 0.0.4)
        with urllib.request.urlopen(base + "/metrics", timeout=30) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
            prom = resp.read().decode()
        assert "photon_serving_requests_total 1" in prom
        assert 'photon_serving_latency_s{quantile="0.99"}' in prom
        assert "# TYPE photon_serving_latency_s summary" in prom
        # scores match an in-process scorer on the same model
        rng = np.random.default_rng(3)
        model = _make_model(rng)
        expected = CompiledScorer(model, max_batch=32, min_bucket=4).score(
            {s: np.asarray(v) for s, v in body["features"].items()},
            {"userId": np.asarray(body["ids"]["userId"], dtype=object)})
        np.testing.assert_allclose(out["scores"], expected.scores, atol=1e-6)
    finally:
        proc.terminate()
        proc.wait(timeout=30)
