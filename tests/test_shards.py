"""Entity-sharded serving tests (fleet/shards.py + the sharded fleet
stack) — ISSUE 20.

Covers the deterministic shard map (stability, version round-trip,
spec_id agreement checks), the fan-out margin merge's BIT-PARITY with a
monolithic scorer (in-process and over the real HTTP front), per-shard
sha256-exact audits (sharded replica vs the publisher's filtered full
model), the shard.route / shard.merge fault sites, degradation policies
when a shard goes dark, the subprocess SIGKILL -> survivors keep serving
-> rejoin -> exact-audit lifecycle, armed-locktrace concurrent failover
stress, and the ISSUE 20 satellite: non-idempotent publisher routes are
never hedged or blindly retried by the front.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import jax.numpy as jnp
import numpy as np
import pytest

import photon_ml_tpu

from photon_ml_tpu.fleet import (Front, FrontConfig, NoReadyReplica,
                                 ShardAssignment, ShardMergeError,
                                 ShardSpec, merge_margins, shards_touched)
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.game import (FixedEffectModel, GameModel,
                                       RandomEffectModel)
from photon_ml_tpu.models.glm import model_for_task
from photon_ml_tpu.models.io import save_game_model
from photon_ml_tpu.serving import ScoringService, ServingConfig
from photon_ml_tpu.utils import faults, locktrace

D_G, D_U, N_ENT = 6, 4, 30
TASK = "logistic_regression"
PACKAGE_DIR = os.path.dirname(os.path.abspath(photon_ml_tpu.__file__))
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def rng():
    return np.random.default_rng(7)


def _make_model(rng, coef_scale=1.0):
    fe = FixedEffectModel(
        model_for_task(TASK, Coefficients(
            jnp.asarray(coef_scale * rng.normal(size=D_G)))), "global")
    re = RandomEffectModel(
        random_effect_type="userId", feature_shard="per_user",
        task_type=TASK,
        coefficients=jnp.asarray(coef_scale * rng.normal(size=(N_ENT, D_U))),
        entity_ids=np.asarray([f"u{i}" for i in range(N_ENT)], dtype=object),
        projection=None, global_dim=D_U)
    return GameModel({"fixed": fe, "perUser": re}, TASK)


def _save_model(rng, tmp_path, name="model", coef_scale=1.0):
    mdir = str(tmp_path / name)
    save_game_model(_make_model(rng, coef_scale), mdir)
    return mdir


def _service(mdir):
    return ScoringService(
        model_dir=mdir, config=ServingConfig(max_batch=64, min_bucket=4))


def _shard_service(mdir, index, count):
    return ScoringService(
        model_dir=mdir,
        config=ServingConfig(max_batch=64, min_bucket=4,
                             shard_index=index, shard_count=count))


def _request(rng, n=12, users=None):
    feats = {"global": rng.normal(size=(n, D_G)),
             "per_user": rng.normal(size=(n, D_U))}
    if users is None:
        users = [f"u{rng.integers(0, N_ENT)}" for _ in range(n)]
    ids = {"userId": np.asarray(users, dtype=object)}
    return feats, ids


def _users_of_shard(spec, shard, count):
    """`count` entity ids owned by `shard` (model entities u0..u29)."""
    owned = [f"u{i}" for i in range(N_ENT)
             if spec.shard_of(f"u{i}") == shard]
    assert len(owned) >= count, "seeded partition left a shard too empty"
    return owned[:count]


def _serve_http(service):
    """A real serve-CLI HTTP server around an in-process service."""
    from photon_ml_tpu.cli.serve import _make_http_server
    httpd = _make_http_server(service, "127.0.0.1", 0)
    thread = threading.Thread(target=httpd.serve_forever,
                              kwargs={"poll_interval": 0.05}, daemon=True)
    thread.start()
    return httpd, thread, f"http://127.0.0.1:{httpd.server_address[1]}"


def _get(url, timeout=15):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


# --------------------------------------------------------------------------
# the shard map
# --------------------------------------------------------------------------

def test_shard_spec_deterministic_and_total():
    spec = ShardSpec(num_shards=4)
    ids = [f"u{i}" for i in range(200)] + [123, "z", ""]
    owners = [spec.shard_of(e) for e in ids]
    assert owners == [spec.shard_of(e) for e in ids]   # stable
    assert set(owners) == set(range(4))                # every shard used
    for e, o in zip(ids, owners):
        assert 0 <= o < 4
    # owned masks partition the id space: each id owned exactly once
    masks = np.stack([spec.owned_mask(ids, k) for k in range(4)])
    assert (masks.sum(axis=0) == 1).all()


def test_shard_spec_version_and_salt_change_the_partition():
    a = ShardSpec(num_shards=4)
    b = ShardSpec(num_shards=4, version=2)
    c = ShardSpec(num_shards=4, salt="other")
    ids = [f"u{i}" for i in range(100)]
    assert [a.shard_of(e) for e in ids] != [b.shard_of(e) for e in ids]
    assert [a.shard_of(e) for e in ids] != [c.shard_of(e) for e in ids]
    assert len({a.spec_id(), b.spec_id(), c.spec_id()}) == 3


def test_shard_spec_roundtrip_and_spec_id_mismatch():
    spec = ShardSpec(num_shards=3, salt="s", version=7)
    assert ShardSpec.from_dict(spec.to_dict()) == spec
    bad = dict(spec.to_dict(), spec_id="0" * 16)
    with pytest.raises(ValueError, match="spec_id mismatch"):
        ShardSpec.from_dict(bad)
    with pytest.raises(ValueError, match="out of range"):
        ShardAssignment(spec=spec, index=3)
    with pytest.raises(ValueError, match="num_shards"):
        ShardSpec(num_shards=0)


def test_shards_touched_only_names_owning_shards():
    spec = ShardSpec(num_shards=4)
    meta = [{"name": "fixed", "kind": "fixed"},
            {"name": "perUser", "kind": "random", "entity_type": "userId"}]
    users = ["u1", "u2", "u3"]
    touched = shards_touched(spec, meta, {"userId": users})
    assert touched == sorted({spec.shard_of(u) for u in users})
    assert shards_touched(spec, meta, {}) == []
    assert shards_touched(spec, [meta[0]], {"userId": users}) == []


# --------------------------------------------------------------------------
# fan-out merge: bit-parity with the monolithic scorer
# --------------------------------------------------------------------------

def test_fanout_merge_bit_parity_and_per_shard_audits(tmp_path, rng):
    """The tentpole invariant, in-process: per-shard margin legs re-fold
    to the monolithic scorer's scores EXACTLY (same bits), and each
    sharded replica's table hashes equal the full model filtered to its
    owned rows."""
    mdir = _save_model(rng, tmp_path)
    n_shards = 3
    spec = ShardSpec(num_shards=n_shards)
    mono = _service(mdir)
    svcs = [_shard_service(mdir, k, n_shards) for k in range(n_shards)]
    try:
        # rows spread over every shard, plus an unseen entity (scores
        # with a zero RE contribution on every leg)
        users = [f"u{i}" for i in range(10)] + ["nobody", "u1"]
        feats, ids = _request(rng, n=len(users), users=users)
        expected = np.asarray(mono.score(feats, ids), np.float64)
        legs = {k: svcs[k].score_margins(feats, ids)["margins"]
                for k in range(n_shards)}
        meta = svcs[0].registry.scorer.coordinate_meta()
        out = merge_margins(spec, meta, ids, legs, primary=0)
        got = np.asarray(out["scores"], np.float64)
        assert got.tobytes() == expected.tobytes()
        assert out["partial_rows"] == [] and out["missing_shards"] == []
        # any healthy primary leg gives the same bits (FE/MF replicate)
        got2 = merge_margins(spec, meta, ids, legs, primary=2)["scores"]
        assert np.asarray(got2).tobytes() == expected.tobytes()
        # per-shard audits: sharded replica's resident tables ARE the
        # publisher's full tables filtered to its owned rows
        full = mono.registry.scorer
        for k in range(n_shards):
            assert svcs[k].registry.scorer.table_hashes() == \
                full.shard_table_hashes(spec, k)
        # the shard gauges landed on the replica metric surface
        snap = svcs[0].metrics_snapshot()
        assert snap["fleet"]["shard_index"] == 0
        assert snap["fleet"]["shard_count"] == n_shards
        assert snap["fleet"]["shard_owned_rows"] >= 1
    finally:
        mono.close()
        for s in svcs:
            s.close()


def test_warm_fanout_scoring_traces_nothing(tmp_path, rng):
    """After one warm round, per-shard margin legs and the merge run on
    cached programs only."""
    from test_pipeline import _compile_counting
    mdir = _save_model(rng, tmp_path)
    spec = ShardSpec(num_shards=2)
    svcs = [_shard_service(mdir, k, 2) for k in range(2)]
    meta = svcs[0].registry.scorer.coordinate_meta()

    def fan_out():
        feats, ids = _request(rng)
        legs = {k: svcs[k].score_margins(feats, ids)["margins"]
                for k in range(2)}
        return merge_margins(spec, meta, ids, legs, primary=0)["scores"]

    try:
        fan_out()
        with _compile_counting() as counter:
            for _ in range(3):
                assert len(fan_out()) == 12
        assert counter.count == 0
    finally:
        for s in svcs:
            s.close()


def test_shard_filtered_replay_is_trace_free_and_sha256_exact(tmp_path, rng):
    """Sharded replicas tail the fleet's one log and apply only their
    owned slice: steady-state replay compiles nothing, and each replica's
    table hashes equal the publisher's per-shard filter of its full
    tables (the /fleet/audit?shard=K contract)."""
    from photon_ml_tpu.fleet import (FleetPublisher, Replica, ReplicaConfig,
                                     ReplicationLog)
    from photon_ml_tpu.online import OnlineUpdateConfig
    from test_pipeline import _compile_counting
    mdir = _save_model(rng, tmp_path)
    spec = ShardSpec(num_shards=2)
    svc = ScoringService(
        model_dir=mdir, config=ServingConfig(max_batch=64, min_bucket=4),
        updates=OnlineUpdateConfig(micro_batch=8), start_updater=False)
    log = ReplicationLog(str(tmp_path / "log"))
    pub = FleetPublisher(svc, log, model_dir=mdir, shard_spec=spec)
    reps = [Replica(_shard_service(mdir, k, 2), log, str(tmp_path / f"s{k}"),
                    ReplicaConfig()) for k in range(2)]

    def feedback_round():
        feats, ids = _request(rng, n=24)
        svc.feedback(feats, ids, (rng.uniform(size=24) < 0.5).astype(float))
        svc.updater.flush()

    try:
        for rep in reps:
            rep.join()
        svc.updater.warmup()
        for _ in range(2):      # warm: publisher solve + replica scatter
            feedback_round()
            for rep in reps:
                rep.poll_once()
        fresh = applied = 0
        for _ in range(4):
            feedback_round()
            with _compile_counting() as counter:
                for rep in reps:
                    applied += rep.poll_once()
            fresh += counter.count
        assert fresh == 0 and applied >= 4
        for k, rep in enumerate(reps):
            assert rep.service.audit()["table_hashes"] == \
                pub.shard_audit(k)["table_hashes"]
            assert rep.service.version_vector() == svc.version_vector()
    finally:
        svc.close()
        for rep in reps:
            rep.service.close()


def test_four_budgeted_shards_serve_four_times_one_store_bit_exactly(
        tmp_path, rng):
    """The capacity claim: four sharded services, each behind a tiered
    store whose hot tier holds a quarter of the random-effect rows, serve
    the whole table with the monolithic scorer's bits."""
    n_shards = 4
    budget = -(-N_ENT // n_shards)
    mdir = _save_model(rng, tmp_path)
    spec = ShardSpec(num_shards=n_shards)
    mono = _service(mdir)
    svcs = [ScoringService(model_dir=mdir, config=ServingConfig(
        max_batch=64, min_bucket=4, shard_index=k, shard_count=n_shards,
        store_budget_rows=budget, store_dir=str(tmp_path / f"store{k}")))
        for k in range(n_shards)]
    meta = svcs[0].registry.scorer.coordinate_meta()
    try:
        for _ in range(4):
            feats, ids = _request(rng, n=16)
            legs = {k: svcs[k].score_margins(feats, ids)["margins"]
                    for k in range(n_shards)}
            got = np.asarray(merge_margins(spec, meta, ids, legs,
                                           primary=0)["scores"], np.float64)
            expected = np.asarray(mono.score(feats, ids), np.float64)
            assert got.tobytes() == expected.tobytes()
        owned = [sum(s.registry.scorer.shard_info()["owned_rows"].values())
                 for s in svcs]
        assert sum(owned) == N_ENT
        assert N_ENT / budget >= 3.75      # 30 rows over stores of 8
    finally:
        mono.close()
        for s in svcs:
            s.close()


def test_merge_missing_owner_policies(tmp_path, rng):
    mdir = _save_model(rng, tmp_path)
    spec = ShardSpec(num_shards=2)
    svcs = [_shard_service(mdir, k, 2) for k in range(2)]
    try:
        users = _users_of_shard(spec, 0, 2) + _users_of_shard(spec, 1, 2)
        feats, ids = _request(rng, n=4, users=users)
        legs = {1: svcs[1].score_margins(feats, ids)["margins"]}
        meta = svcs[1].registry.scorer.coordinate_meta()
        with pytest.raises(ShardMergeError, match="no healthy replica"):
            merge_margins(spec, meta, ids, legs, primary=1)
        out = merge_margins(spec, meta, ids, legs, primary=1,
                            missing_policy="partial")
        assert out["missing_shards"] == [0]
        assert out["partial_rows"] == [0, 1]     # only shard-0 rows
        # and the primary leg itself must be present
        with pytest.raises(ShardMergeError, match="primary"):
            merge_margins(spec, meta, ids, legs, primary=0,
                          missing_policy="partial")
    finally:
        for s in svcs:
            s.close()


# --------------------------------------------------------------------------
# the front over sharded HTTP replicas
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sharded_http(tmp_path_factory):
    """3 sharded services behind real serve-CLI HTTP servers, plus a
    monolithic reference service: built once, the tests below only send
    requests (the one that takes a shard's server down puts a new one in
    its place)."""
    mdir = _save_model(np.random.default_rng(7),
                       tmp_path_factory.mktemp("sharded_http"))
    mono = _service(mdir)
    svcs = [_shard_service(mdir, k, 3) for k in range(3)]
    servers = [_serve_http(s) for s in svcs]
    yield {"mono": mono, "svcs": svcs, "servers": servers,
           "spec": ShardSpec(num_shards=3)}
    for httpd, thread, _url in servers:
        try:
            httpd.shutdown()
            httpd.server_close()
            thread.join(timeout=5)
        except Exception:
            pass
    mono.close()
    for s in svcs:
        s.close()


def _sharded_front(sharded_http, **cfg_kw):
    cfg_kw.setdefault("probe_interval_s", 0.05)
    cfg_kw.setdefault("unhealthy_after", 1)
    cfg_kw.setdefault("hedge_after_s", 5.0)
    cfg_kw.setdefault("request_timeout_s", 15.0)
    front = Front([url for _h, _t, url in sharded_http["servers"]],
                  config=FrontConfig(**cfg_kw), start_probes=False)
    front.probe_once()
    return front


def test_front_sharded_scoring_bit_parity_http(sharded_http, rng):
    front = _sharded_front(sharded_http)
    mono = sharded_http["mono"]
    try:
        users = [f"u{i}" for i in range(8)] + ["ghost"]
        feats, ids = _request(rng, n=len(users), users=users)
        body = {"features": {k: v.tolist() for k, v in feats.items()},
                "ids": {"userId": users}}
        status, payload = front.route("/score", body)
        assert status == 200
        assert payload["sharded"] is True
        assert "degraded" not in payload
        expected = np.asarray(mono.score(feats, ids), np.float64)
        got = np.asarray(payload["scores"], np.float64)
        assert got.tobytes() == expected.tobytes()
        # /predict applies the identical host-side inverse link
        status, payload = front.route("/predict", body)
        assert status == 200
        exp_pred = np.asarray(mono.predict(feats, ids), np.float64)
        got_pred = np.asarray(payload["predictions"], np.float64)
        assert got_pred.tobytes() == exp_pred.tobytes()
        # fan-out accounting landed on the front surface
        snap = front.front_snapshot()
        assert snap["shard_coverage"] == 1.0
        assert any(v > 0 for v in snap["shard_requests"].values())
        assert front.status()["shards"]["shards_down"] == []
    finally:
        front.close()


def test_front_sharded_fault_sites_absorbed(sharded_http, rng):
    """shard.route / shard.merge transient faults are absorbed by the
    leg retry and merge retry loops — the response stays bit-exact."""
    front = _sharded_front(sharded_http)
    mono = sharded_http["mono"]
    try:
        users = [f"u{i}" for i in range(6)]
        feats, ids = _request(rng, n=len(users), users=users)
        body = {"features": {k: v.tolist() for k, v in feats.items()},
                "ids": {"userId": users}}
        plan = faults.FaultPlan([
            {"site": "shard.route", "action": "transient", "hits": [1]},
            {"site": "shard.merge", "action": "transient", "hits": [1]},
        ])
        with faults.injected(plan):
            status, payload = front.route("/score", body)
        assert status == 200
        assert plan.report()["total_fired"] == 2
        expected = np.asarray(mono.score(feats, ids), np.float64)
        assert np.asarray(payload["scores"],
                          np.float64).tobytes() == expected.tobytes()
    finally:
        front.close()


def test_front_lost_shard_degrades_only_that_shard(sharded_http, rng):
    """Robustness core: killing every replica of ONE shard degrades only
    requests touching that shard's entities; under policy 'error' those
    requests 503; everything else stays bit-exact."""
    spec = sharded_http["spec"]
    mono = sharded_http["mono"]
    front = _sharded_front(sharded_http)
    front_err = _sharded_front(sharded_http, degraded_policy="error")
    lost = 1
    try:
        # prime the fold-order cache while all shards are up
        warm_users = [f"u{i}" for i in range(6)]
        wfeats, _ = _request(rng, n=len(warm_users), users=warm_users)
        warm_body = {"features": {k: v.tolist()
                                  for k, v in wfeats.items()},
                     "ids": {"userId": warm_users}}
        assert front.route("/score", warm_body)[0] == 200
        assert front_err.route("/score", warm_body)[0] == 200
        httpd, thread, _url = sharded_http["servers"][lost]
        httpd.shutdown()
        httpd.server_close()
        thread.join(timeout=5)
        front.probe_once()
        front_err.probe_once()
        assert front.status()["shards"]["shards_down"] == [lost]
        assert front.front_snapshot()["shard_coverage"] == 0.0
        # a request that avoids the lost shard: exact, not degraded
        safe = (_users_of_shard(spec, (lost + 1) % 3, 2)
                + _users_of_shard(spec, (lost + 2) % 3, 2))
        feats, ids = _request(rng, n=len(safe), users=safe)
        body = {"features": {k: v.tolist() for k, v in feats.items()},
                "ids": {"userId": safe}}
        status, payload = front.route("/score", body)
        assert status == 200 and "degraded" not in payload
        expected = np.asarray(mono.score(feats, ids), np.float64)
        assert np.asarray(payload["scores"],
                          np.float64).tobytes() == expected.tobytes()
        # a request touching the lost shard: partial under the default
        # policy, 503 under 'error' — and the partial rows are exactly
        # the rows owned by the lost shard
        mixed = safe[:2] + _users_of_shard(spec, lost, 2)
        mfeats, mids = _request(rng, n=len(mixed), users=mixed)
        mbody = {"features": {k: v.tolist() for k, v in mfeats.items()},
                 "ids": {"userId": mixed}}
        status, payload = front.route("/score", mbody)
        assert status == 200
        assert payload["degraded"] is True
        assert payload["missing_shards"] == [lost]
        assert payload["partial_rows"] == [2, 3]
        # the surviving rows still carry the exact monolithic bits
        expected_mixed = np.asarray(mono.score(mfeats, mids), np.float64)
        got = np.asarray(payload["scores"], np.float64)
        assert got[:2].tobytes() == expected_mixed[:2].tobytes()
        status, payload = front_err.route("/score", mbody)
        assert status == 503
        assert payload["missing_shards"] == [lost]
        assert front.front_snapshot()["shard_degraded"] >= 1
    finally:
        front.close()
        front_err.close()
        sharded_http["servers"][lost] = _serve_http(
            sharded_http["svcs"][lost])


def test_front_rejects_mismatched_shard_spec(sharded_http, tmp_path, rng):
    """A replica on an incompatible partition (same version, different
    salt -> different spec_id) is treated as a failed probe and leaves
    rotation — its margins are never merged."""
    front = _sharded_front(sharded_http)
    mdir = _save_model(rng, tmp_path, name="model_alt")
    alien = ScoringService(
        model_dir=mdir,
        config=ServingConfig(max_batch=64, min_bucket=4, shard_index=0,
                             shard_count=3, shard_salt="other"))
    httpd, thread, url = _serve_http(alien)
    try:
        front.attach(url)
        for _ in range(3):
            results = front.probe_once()
        assert results[url] is False
        state = [h for h in front.status()["replicas"]
                 if h["url"] == url][0]
        assert "does not match the fleet partition" in state["last_error"]
    finally:
        front.close()
        httpd.shutdown()
        httpd.server_close()
        thread.join(timeout=5)
        alien.close()


def test_front_locktrace_armed_concurrent_failover_stress(tmp_path, rng):
    """ISSUE 20 satellite: concurrent sharded scoring + a mid-stress
    shard loss under the ARMED lock tracker; every observed acquisition
    order must be an edge consistent with the static lock graph.  The
    whole stack is built INSIDE the armed tracker — locks constructed
    before arming stay raw and would go unobserved."""
    spec = ShardSpec(num_shards=3)
    with locktrace.enabled() as tracker:
        mdir = _save_model(rng, tmp_path)
        svcs = [_shard_service(mdir, k, 3) for k in range(3)]
        servers = [_serve_http(s) for s in svcs]
        front = Front([url for _h, _t, url in servers],
                      config=FrontConfig(probe_interval_s=0.05,
                                         unhealthy_after=1,
                                         hedge_after_s=5.0,
                                         request_timeout_s=15.0),
                      start_probes=False)
        front.probe_once()
        errors, stop = [], threading.Event()
        safe = (_users_of_shard(spec, 0, 2)
                + _users_of_shard(spec, 2, 2))
        feats, _ = _request(rng, n=len(safe), users=safe)
        body = {"features": {k: v.tolist() for k, v in feats.items()},
                "ids": {"userId": safe}}

        def score_loop():
            while not stop.is_set():
                try:
                    status, payload = front.route("/score", body)
                    if status != 200 or payload.get("degraded"):
                        errors.append(f"http {status}: {payload}")
                except Exception as e:
                    errors.append(f"{type(e).__name__}: {e}")

        threads = [threading.Thread(target=score_loop, daemon=True)
                   for _ in range(4)]
        try:
            for t in threads:
                t.start()
            time.sleep(0.3)
            httpd, th, _url = servers[1]           # lose shard 1
            httpd.shutdown()
            httpd.server_close()
            th.join(timeout=5)
            front.probe_once()
            time.sleep(0.3)
        finally:
            stop.set()
            for t in threads:
                t.join(timeout=10)
            front.close()
            for httpd, th, _url in servers[:1] + servers[2:]:
                httpd.shutdown()
                httpd.server_close()
                th.join(timeout=5)
            for s in svcs:
                s.close()
        assert errors == []     # shard-1 loss never touched these rows
    from photon_ml_tpu.analysis.concurrency import lock_order_edges
    tracker.assert_consistent(lock_order_edges([PACKAGE_DIR]))
    acq = tracker.acquisitions()
    assert acq.get("Front._lock", 0) > 0
    assert acq.get("ScoringService._margins_lock", 0) > 0


# --------------------------------------------------------------------------
# satellite: non-idempotent publisher routes are never hedged/retried
# --------------------------------------------------------------------------

class _SlowPublisherStub:
    """One stub replica that counts /feedback hits and can sleep through
    the front's timeout — the probe for blind-retry bugs."""

    def __init__(self):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
        stub = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *a):
                pass

            def _reply(self, code, payload):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                self._reply(200, {"status": "ok"})

            def do_POST(self):
                length = int(self.headers.get("Content-Length") or 0)
                self.rfile.read(length)
                stub.hits += 1
                if stub.delay_s:
                    time.sleep(stub.delay_s)
                self._reply(202, {"ok": True})

        self.hits = 0
        self.delay_s = 0.0
        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        kwargs={"poll_interval": 0.05},
                                        daemon=True)
        self._thread.start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        self._thread.join(timeout=5)


def test_route_never_hedges_or_retries_publisher_paths():
    stub = _SlowPublisherStub()
    front = Front([stub.url], config=FrontConfig(
        probe_interval_s=0.05, hedge_after_s=0.01,
        request_timeout_s=0.3), start_probes=False)
    try:
        front.probe_once()
        # the scoring router refuses model-state paths outright
        for path in ("/feedback", "/swap", "/rollback"):
            with pytest.raises(ValueError, match="route_publisher"):
                front.route(path, {})
        assert stub.hits == 0
        # route_publisher sends EXACTLY ONCE even when the publisher
        # sleeps through the timeout: an ambiguous timeout must never
        # become a duplicate feedback batch / double swap
        stub.delay_s = 1.0
        with pytest.raises(NoReadyReplica):
            front.route_publisher("POST", "/feedback", {"labels": [1.0]},
                                  timeout=0.2)
        time.sleep(1.2)          # let the slow handler finish counting
        assert stub.hits == 1
        snap = front.front_snapshot()
        assert snap["hedges"] == 0 and snap["retries"] == 0
    finally:
        front.close()
        stub.close()


# --------------------------------------------------------------------------
# subprocess fleet: SIGKILL a shard's replica, survive, rejoin, audit
# --------------------------------------------------------------------------

def _spawn_serve(extra, env):
    proc = subprocess.Popen(
        [sys.executable, "-m", "photon_ml_tpu.cli.serve",
         "--port", "0", "--max-batch", "32", "--min-bucket", "4"] + extra,
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        text=True)
    return proc


def _read_startup(proc, timeout=180):
    line = [None]

    def read():
        line[0] = proc.stdout.readline()

    t = threading.Thread(target=read, daemon=True)
    t.start()
    t.join(timeout)
    if line[0] is None or not line[0].strip():
        raise AssertionError("serve subprocess produced no startup line")
    return json.loads(line[0])


def test_shard_fleet_sigkill_rejoin_sha256_audit(tmp_path, rng):
    """ISSUE 20 acceptance core, end to end over subprocesses: a 2-shard
    fleet serves exactly; SIGKILL of one shard's replica leaves the
    OTHER shard serving bit-exact scores; the restarted replica catches
    up from the shard-filtered log to a sha256-exact per-shard audit
    against the publisher's filtered full model."""
    mdir = _save_model(rng, tmp_path)
    log_dir = str(tmp_path / "log")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    # conftest enables x64 in THIS process; the spawned fleet must score
    # in the same compute dtype or bit-parity against the in-process
    # monolithic reference is impossible by construction
    env["JAX_ENABLE_X64"] = "1"
    spec = ShardSpec(num_shards=2)
    procs = {}
    front = None

    def spawn_replica(k):
        return _spawn_serve(
            ["--model-dir", mdir, "--replica", "--shard", f"{k}/2",
             "--replication-log", log_dir,
             "--replica-state", str(tmp_path / f"s{k}"),
             "--replica-poll-ms", "25"], env)

    try:
        procs["pub"] = _spawn_serve(
            ["--model-dir", mdir, "--replica", "--publish",
             "--shard-count", "2", "--replication-log", log_dir,
             "--replica-state", str(tmp_path / "sp"),
             "--enable-updates", "--update-interval-ms", "50",
             # keep the updater's warmup cheap: 2 small solver buckets
             "--update-micro-batch", "4",
             "--update-max-rows-per-entity", "8"], env)
        procs[0] = spawn_replica(0)
        procs[1] = spawn_replica(1)
        urls = {}
        for key in ("pub", 0, 1):
            info = _read_startup(procs[key])
            urls[key] = info["serving"]
            if key != "pub":
                assert info["shard"]["index"] == key
                assert info["shard"]["num_shards"] == 2
        front = Front([urls["pub"], urls[0], urls[1]],
                      publisher_url=urls["pub"],
                      config=FrontConfig(probe_interval_s=0.05,
                                         unhealthy_after=1,
                                         request_timeout_s=30.0,
                                         hedge_after_s=10.0),
                      start_probes=False)
        deadline = time.time() + 120
        while time.time() < deadline:
            if all(front.probe_once().values()):
                break
            time.sleep(0.2)
        else:
            raise AssertionError("fleet never became ready")
        # push online deltas through the publisher so the replicas have
        # shard-filtered log state to converge on
        n = 16
        fb = {"features": {
            "global": rng.normal(size=(n, D_G)).tolist(),
            "per_user": rng.normal(size=(n, D_U)).tolist()},
            "ids": {"userId": [f"u{i % N_ENT}" for i in range(n)]},
            "labels": [0.0] * n}
        status, _p, _h = front.route_publisher("POST", "/feedback", fb)
        assert status == 202
        # first drain the publisher's updater COMPLETELY (more delta
        # records may trail the first one), then wait for every replica
        # to reach the settled head — scoring before the log stops
        # growing would race the monolithic reference below
        deadline = time.time() + 120
        while time.time() < deadline:
            _s, snap = _get(urls["pub"] + "/metrics.json")
            online = snap.get("online") or {}
            if online.get("pending_rows") == 0 and \
                    online.get("deltas_published", 0) > 0:
                break
            time.sleep(0.1)
        else:
            raise AssertionError("publisher never drained its updater")
        # pending_rows hits 0 when the LAST cycle drains the buffer —
        # before that cycle's delta lands on the log.  Wait until the
        # head stops moving for a full settle window AND every replica
        # has applied it, else the monolithic reference below (which
        # reads the log later) would see one more delta than the fleet.
        deadline = time.time() + 60
        head, stable_since = None, time.time()
        while time.time() < deadline:
            front.probe_once()
            lag = front._fleet_lag()
            if lag["publisher_head_seq"] != head:
                head, stable_since = lag["publisher_head_seq"], time.time()
            elif head is not None and head >= 3 and \
                    time.time() - stable_since > 1.0 and all(
                        st["lag_records"] == 0
                        for st in lag["replicas"].values()):
                break
            time.sleep(0.1)
        else:
            raise AssertionError("replicas never caught up")
        # bit-parity vs a local monolithic follower of the SAME log
        from photon_ml_tpu.fleet import Replica, ReplicaConfig, \
            ReplicationLog
        mono = _service(mdir)
        rep = Replica(mono, ReplicationLog(log_dir),
                      str(tmp_path / "s_mono"), ReplicaConfig())
        rep.join()
        users = [f"u{i}" for i in range(8)]
        feats, ids = _request(rng, n=len(users), users=users)
        body = {"features": {k: v.tolist() for k, v in feats.items()},
                "ids": {"userId": users}}
        status, payload = front.route("/score", body)
        assert status == 200 and payload["sharded"] is True
        expected = np.asarray(mono.score(feats, ids), np.float64)
        assert np.asarray(payload["scores"],
                          np.float64).tobytes() == expected.tobytes()
        # sha256-exact per-shard audits: replica vs publisher's filter
        for k in (0, 1):
            _s, mine = _get(urls[k] + "/fleet/audit")
            _s, theirs = _get(urls["pub"] + f"/fleet/audit?shard={k}")
            assert mine["table_hashes"] == theirs["table_hashes"]
            assert mine["version_vector"] == theirs["version_vector"]
        # SIGKILL shard 0's only replica: shard 1 keeps serving exactly
        procs[0].send_signal(signal.SIGKILL)
        procs[0].wait(timeout=30)
        deadline = time.time() + 30
        while time.time() < deadline:
            front.probe_once()
            if front.status()["shards"]["shards_down"] == [0]:
                break
            time.sleep(0.1)
        else:
            raise AssertionError("front never noticed the lost shard")
        safe = _users_of_shard(spec, 1, 4)
        sfeats, sids = _request(rng, n=len(safe), users=safe)
        sbody = {"features": {k: v.tolist() for k, v in sfeats.items()},
                 "ids": {"userId": safe}}
        status, payload = front.route("/score", sbody)
        assert status == 200 and "degraded" not in payload
        sexp = np.asarray(mono.score(sfeats, sids), np.float64)
        assert np.asarray(payload["scores"],
                          np.float64).tobytes() == sexp.tobytes()
        # errors confined: only requests touching shard 0 degrade
        touch0 = _users_of_shard(spec, 0, 2) + safe[:2]
        tfeats, _tids = _request(rng, n=len(touch0), users=touch0)
        tbody = {"features": {k: v.tolist() for k, v in tfeats.items()},
                 "ids": {"userId": touch0}}
        status, payload = front.route("/score", tbody)
        assert status == 200 and payload["degraded"] is True
        assert payload["missing_shards"] == [0]
        # rejoin: the restarted replica catches up from the
        # shard-filtered log and audits sha256-exact again
        procs[0] = spawn_replica(0)
        urls[0] = _read_startup(procs[0])["serving"]
        front.attach(urls[0])
        deadline = time.time() + 120
        while time.time() < deadline:
            front.probe_once()
            if front.status()["shards"]["shards_down"] == []:
                break
            time.sleep(0.2)
        else:
            raise AssertionError("rejoined replica never became ready")
        _s, mine = _get(urls[0] + "/fleet/audit")
        _s, theirs = _get(urls["pub"] + "/fleet/audit?shard=0")
        assert mine["table_hashes"] == theirs["table_hashes"]
        status, payload = front.route("/score", tbody)
        assert status == 200 and "degraded" not in payload
        rep.close()
        mono.close()
    finally:
        if front is not None:
            front.close()
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
                try:
                    proc.communicate(timeout=15)
                except Exception:
                    pass
