"""Replicated serving fleet tests (photon_ml_tpu/fleet/) — ISSUE 12.

Covers the replication log's durability discipline (bit-exact array round
trips, torn-tail recovery, segment rotation, gap/corruption detection,
compaction folding), the replica lifecycle (join -> catch-up -> ready ->
drain -> crash -> rejoin, run with the lock tracker ARMED and validated
against the static lock-order graph), bit-identical convergence across
deltas / rollbacks / swaps, the `replog.*`/`replica.apply` fault sites,
the front's probe/failover/hedge/backpressure behavior against stub
replicas, and the ISSUE 12 satellites: graceful SIGTERM drain (via
subprocess), loud undo-log-overflow degradation, the StaleDeltaError
re-enqueue racing a concurrent full install, and feedback 429s carrying
Retry-After derived from the updater's drain rate.
"""
import json
import logging
import os
import signal
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import photon_ml_tpu

from photon_ml_tpu.fleet import (FleetPublisher, Front, FrontConfig,
                                 NoReadyReplica, Replica, ReplicaConfig,
                                 ReplicationLog, ReplicationLogError,
                                 decode_array, encode_array)
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.game import (FixedEffectModel, GameModel,
                                       RandomEffectModel)
from photon_ml_tpu.models.glm import model_for_task
from photon_ml_tpu.models.io import save_game_model
from photon_ml_tpu.online import OnlineUpdateConfig
from photon_ml_tpu.serving import (Overloaded, ScoringService,
                                   ServingConfig)
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


def _service(mdir, *, updates=False):
    return ScoringService(
        model_dir=mdir, config=ServingConfig(max_batch=64, min_bucket=4),
        updates=OnlineUpdateConfig(micro_batch=8) if updates else None,
        start_updater=False)


def _publisher(mdir, log_dir):
    svc = _service(mdir, updates=True)
    log = ReplicationLog(str(log_dir))
    pub = FleetPublisher(svc, log, model_dir=mdir)
    return svc, log, pub


def _follower(mdir, log, state_dir, join=True):
    svc = _service(mdir)
    rep = Replica(svc, log, str(state_dir), ReplicaConfig())
    if join:
        rep.join()
    return rep


def _feedback(svc, seed, n=16):
    r = np.random.default_rng(seed)
    feats = {"global": r.normal(size=(n, D_G)),
             "per_user": r.normal(size=(n, D_U))}
    ids = {"userId": np.asarray(
        [f"u{r.integers(0, N_ENT)}" for _ in range(n)], dtype=object)}
    labels = (r.uniform(size=n) < 0.5).astype(float)
    svc.feedback(feats, ids, labels)
    svc.updater.flush()


def _audits_equal(*services):
    audits = [s.audit() for s in services]
    return all(a["version_vector"] == audits[0]["version_vector"]
               and a["table_hashes"] == audits[0]["table_hashes"]
               for a in audits[1:])


# --------------------------------------------------------------------------
# replication log
# --------------------------------------------------------------------------

def test_array_codec_bit_exact(rng):
    for a in (rng.normal(size=(5, 3)),
              rng.normal(size=7).astype(np.float32),
              np.arange(4, dtype=np.int64)):
        b = decode_array(encode_array(a))
        assert b.dtype == a.dtype and b.shape == a.shape
        assert a.tobytes() == b.tobytes()


def test_replog_append_read_roundtrip(tmp_path, rng):
    log = ReplicationLog(str(tmp_path / "log"))
    values = rng.normal(size=(3, D_U))
    seq1 = log.append({"kind": "swap", "version": "v1",
                       "previous_version": None, "source_dir": "/m"})
    seq2 = log.append({"kind": "delta", "version": "v1",
                       "base_version": "v1", "delta_seq": 1,
                       "created_at": 0.0,
                       "coordinates": {"perUser": {
                           "rows": encode_array(np.arange(3)),
                           "values": encode_array(values),
                           "prior": encode_array(values * 0)}}})
    assert (seq1, seq2) == (1, 2)
    assert log.head_seq() == 2
    records = log.read(0)
    assert [r["log_seq"] for r in records] == [1, 2]
    got = decode_array(
        records[1]["record"]["coordinates"]["perUser"]["values"])
    assert got.tobytes() == values.tobytes()   # bit-exact round trip
    assert log.read(2) == []


def test_replog_torn_tail_ignored_and_recovered(tmp_path):
    log = ReplicationLog(str(tmp_path / "log"))
    for k in range(3):
        log.append({"kind": "rollback", "version": f"v{k}",
                    "previous_version": None, "degraded": False})
    seg = [f for f in os.listdir(log.log_dir) if f.startswith("segment")]
    path = os.path.join(log.log_dir, seg[0])
    with open(path, "a") as f:
        f.write('{"log_seq": 4, "t": 0, "record"')  # torn mid-append
    reader = ReplicationLog(str(tmp_path / "log"))
    assert [r["log_seq"] for r in reader.read(0)] == [1, 2, 3]
    # publisher-side open repairs the tail and appends cleanly after
    writer = ReplicationLog(str(tmp_path / "log"))
    assert writer.recover() > 0
    assert writer.recover() == 0
    assert writer.append({"kind": "rollback", "version": "v3",
                          "previous_version": None,
                          "degraded": False}) == 4


def test_replog_mid_file_corruption_raises(tmp_path):
    log = ReplicationLog(str(tmp_path / "log"))
    for k in range(2):
        log.append({"kind": "rollback", "version": f"v{k}",
                    "previous_version": None, "degraded": False})
    seg = [f for f in os.listdir(log.log_dir) if f.startswith("segment")]
    path = os.path.join(log.log_dir, seg[0])
    lines = open(path).readlines()
    lines[0] = lines[0].replace("v0", "vX")  # checksum now mismatches
    with open(path, "w") as f:
        f.writelines(lines)
    with pytest.raises(ReplicationLogError, match="corrupt"):
        ReplicationLog(str(tmp_path / "log")).read(0)


def test_replog_segment_rotation_and_order(tmp_path):
    log = ReplicationLog(str(tmp_path / "log"), segment_records=2)
    for k in range(5):
        log.append({"kind": "rollback", "version": f"v{k}",
                    "previous_version": None, "degraded": False})
    segs = [f for f in os.listdir(log.log_dir) if f.startswith("segment")]
    assert len(segs) == 3
    assert [r["log_seq"] for r in log.read(0)] == [1, 2, 3, 4, 5]
    assert [r["record"]["version"] for r in log.read(3)] == ["v3", "v4"]


def test_replog_fault_sites_fire(tmp_path):
    log = ReplicationLog(str(tmp_path / "log"))
    plan = faults.FaultPlan([
        {"site": "replog.append", "action": "fatal", "hits": [1]},
        {"site": "replog.read", "action": "transient", "hits": [1]},
    ])
    with faults.injected(plan):
        with pytest.raises(faults.FatalFault):
            log.append({"kind": "rollback", "version": "v",
                        "previous_version": None, "degraded": False})
        with pytest.raises(faults.TransientFault):
            log.read(0)
    assert plan.report()["total_fired"] == 2
    # the fatal append wrote NOTHING (fires before the write)
    assert log.head_seq() == 0


# --------------------------------------------------------------------------
# replica runtime: convergence, crash resume, compaction
# --------------------------------------------------------------------------

def test_replica_converges_bit_identically(tmp_path, rng):
    mdir = _save_model(rng, tmp_path)
    svc, log, _pub = _publisher(mdir, tmp_path / "log")
    rep = _follower(mdir, log, tmp_path / "s0")
    try:
        for s in range(3):
            _feedback(svc, 100 + s)
        assert rep.poll_once() > 0
        assert _audits_equal(svc, rep.service)
        assert rep.status()["lag_seq"] == 0
        # replica-side fleet gauges landed on the metric surface
        snap = rep.service.metrics_snapshot()
        assert snap["fleet"]["applied_seq"] == log.head_seq()
        assert snap["fleet"]["ready"] == 1
        assert snap["fleet"]["records_applied"] > 0
    finally:
        svc.close()
        rep.service.close()


def test_replica_replays_swap_and_full_rollback(tmp_path, rng):
    mdir = _save_model(rng, tmp_path)
    mdir2 = _save_model(np.random.default_rng(11), tmp_path, "model2", 1.5)
    svc, log, _pub = _publisher(mdir, tmp_path / "log")
    rep = _follower(mdir, log, tmp_path / "s0")
    try:
        _feedback(svc, 200)
        svc.swap(mdir2, version="v2")      # full swap rides the log
        _feedback(svc, 201)
        rep.poll_once()
        assert _audits_equal(svc, rep.service)
        assert rep.service.model_version == "v2"
        svc.rollback()                      # delta-aware (v2's deltas)
        svc.rollback()                      # full-model: back to v1
        rep.poll_once()
        assert _audits_equal(svc, rep.service)
        assert rep.service.model_version == svc.model_version != "v2"
    finally:
        svc.close()
        rep.service.close()


def test_replica_crash_resume_is_idempotent(tmp_path, rng):
    """A restart resumes from the durable (applied seq + folded table
    state) pair; a STALE-but-consistent durable state — the crash landed
    between an apply and its ack — replays the already-applied tail
    idempotently and still converges bit-identically."""
    mdir = _save_model(rng, tmp_path)
    svc, log, _pub = _publisher(mdir, tmp_path / "log")
    rep = _follower(mdir, log, tmp_path / "s0")
    _feedback(svc, 300)
    rep.poll_once()
    early_state = (tmp_path / "s0" / "applied.json").read_text()
    early_applied = rep.status()["applied_seq"]
    for s in range(1, 3):
        _feedback(svc, 300 + s)
    rep.poll_once()
    assert rep.status()["applied_seq"] == log.head_seq()
    rep.service.close()
    # crash: the process dies AFTER applying the newest records but
    # BEFORE their ack became durable — the state dir still holds the
    # earlier (seq, fold) pair
    (tmp_path / "s0" / "applied.json").write_text(early_state)
    rep2 = _follower(mdir, log, tmp_path / "s0")
    services = [rep2.service]
    try:
        info2 = rep2.status()
        assert info2["applied_seq"] == log.head_seq()
        assert info2["applied_seq"] > early_applied
        assert _audits_equal(svc, rep2.service)
        # and a clean (non-stale) restart resumes without replaying
        rep3 = _follower(mdir, log, tmp_path / "s0")
        services.append(rep3.service)
        assert _audits_equal(svc, rep3.service)
    finally:
        svc.close()
        for s in services:
            s.close()


def test_steady_state_replay_traces_nothing(tmp_path, rng):
    """Tailing the log on a joined replica touches only cached programs:
    the join-time delta warm-up compiled every pow-2 scatter shape."""
    from test_pipeline import _compile_counting
    mdir = _save_model(rng, tmp_path)
    svc, log, _pub = _publisher(mdir, tmp_path / "log")
    rep = _follower(mdir, log, tmp_path / "s0")
    try:
        svc.updater.warmup()
        for s in range(2):      # warm publisher programs + replica replay
            _feedback(svc, 1000 + s, n=24)
            rep.poll_once()
        fresh = applied = 0
        for s in range(4):
            _feedback(svc, 2000 + s, n=24)
            with _compile_counting() as counter:
                applied += rep.poll_once()
            fresh += counter.count
        assert fresh == 0
        assert applied >= 4
        assert _audits_equal(svc, rep.service)
    finally:
        svc.close()
        rep.service.close()


def test_restarted_follower_reports_its_lag_until_it_has_caught_up(tmp_path,
                                                                  rng):
    """What a front federates into `fleet.front_max_lag_seq` is each
    replica's reported `applied_seq`: a follower that was down while the
    publisher advanced comes back behind the log head and not ready, and
    joins to the head from its durable state."""
    mdir = _save_model(rng, tmp_path)
    svc, log, _pub = _publisher(mdir, tmp_path / "log")
    rep = _follower(mdir, log, tmp_path / "s0")
    services = [rep.service]
    try:
        _feedback(svc, 400)
        rep.poll_once()
        assert rep.status()["applied_seq"] == log.head_seq()
        rep.service.close()                      # the follower goes down
        for s in range(1, 3):
            _feedback(svc, 400 + s)
        rep2 = _follower(mdir, log, tmp_path / "s0", join=False)
        services.append(rep2.service)
        assert not rep2.healthy()
        assert rep2.status()["applied_seq"] < log.head_seq()
        rep2.join()
        assert rep2.healthy()
        assert rep2.status()["applied_seq"] == log.head_seq()
        assert rep2.status()["lag_seq"] == 0
        assert _audits_equal(svc, rep2.service)
    finally:
        svc.close()
        for s in services:
            s.close()


def test_compaction_snapshot_join(tmp_path, rng):
    mdir = _save_model(rng, tmp_path)
    svc, log, _pub = _publisher(mdir, tmp_path / "log")
    try:
        for s in range(3):
            _feedback(svc, 400 + s)
        svc.rollback()
        _feedback(svc, 403)
        snap = log.compact(log.head_seq())
        assert snap["upto_seq"] == log.head_seq()
        assert not [f for f in os.listdir(log.log_dir)
                    if f.startswith("segment")]
        # a fresh replica bootstraps from the snapshot alone
        rep = _follower(mdir, log, tmp_path / "s_new")
        try:
            assert _audits_equal(svc, rep.service)
        finally:
            rep.service.close()
        # compacted history refuses a read that predates the snapshot
        _feedback(svc, 404)
        with pytest.raises(ReplicationLogError, match="compacted"):
            log.read(1)
    finally:
        svc.close()


def test_replica_transient_apply_faults_absorbed(tmp_path, rng):
    mdir = _save_model(rng, tmp_path)
    svc, log, _pub = _publisher(mdir, tmp_path / "log")
    rep = _follower(mdir, log, tmp_path / "s0")
    try:
        for s in range(2):
            _feedback(svc, 500 + s)
        plan = faults.FaultPlan([
            {"site": "replica.apply", "action": "transient",
             "hits": [1, 2]},
            {"site": "replog.read", "action": "transient", "hits": [1]},
        ])
        with faults.injected(plan):
            rep.poll_once()
        assert plan.report()["total_fired"] == 3
        assert _audits_equal(svc, rep.service)
        assert rep.service.metrics_snapshot()["fleet"]["apply_retries"] >= 3
        assert rep.healthy()
    finally:
        svc.close()
        rep.service.close()


def test_replica_fatal_apply_marks_failed(tmp_path, rng, caplog):
    mdir = _save_model(rng, tmp_path)
    svc, log, _pub = _publisher(mdir, tmp_path / "log")
    rep = _follower(mdir, log, tmp_path / "s0")
    try:
        _feedback(svc, 600)
        plan = faults.FaultPlan([
            {"site": "replica.apply", "action": "fatal",
             "probability": 1.0},
        ])
        with caplog.at_level(logging.ERROR, logger="photon_ml_tpu"):
            with faults.injected(plan):
                assert rep.poll_once() == 0
        assert not rep.healthy()
        assert rep.status()["failed"] is not None
        assert any("FAILED" in r.message for r in caplog.records)
        assert rep.poll_once() == 0   # failed replicas stop applying
    finally:
        svc.close()
        rep.service.close()


def test_fleet_lifecycle_with_locktrace_armed(tmp_path):
    """ISSUE 12 acceptance: the full lifecycle — join -> catch-up ->
    ready -> drain -> crash -> rejoin — under the ARMED lock tracker,
    with every observed acquisition order an edge consistent with the
    static lock-order graph, and all three fleet locks actually
    exercised."""
    r = np.random.default_rng(21)
    with locktrace.enabled() as tracker:
        mdir = _save_model(r, tmp_path)
        svc, log, _pub = _publisher(mdir, tmp_path / "log")
        rep = _follower(mdir, log, tmp_path / "s0", join=False)
        errors = []
        stop = threading.Event()

        def score_loop():
            rr = np.random.default_rng(23)
            while not stop.is_set():
                try:
                    rep.service.score(
                        {"global": rr.normal(size=(2, D_G)),
                         "per_user": rr.normal(size=(2, D_U))},
                        {"userId": np.asarray(["u1", "u2"], dtype=object)})
                except Exception as e:  # pragma: no cover
                    errors.append(f"{type(e).__name__}: {e}")

        try:
            _feedback(svc, 700)
            info = rep.join()                       # join -> catch-up
            assert info["records_replayed"] >= 1
            assert rep.healthy()                    # ready
            t = threading.Thread(target=score_loop, daemon=True)
            t.start()
            _feedback(svc, 701)
            rep.start()                             # background apply
            deadline = time.time() + 10
            while rep.status()["applied_seq"] < log.head_seq() \
                    and time.time() < deadline:
                time.sleep(0.02)
            rep.drain()                             # drain
            assert not rep.healthy()
            assert rep.poll_once() == 0
            stop.set()
            t.join(timeout=5)
            rep.close()
            rep.service.close()                     # crash (abrupt stop)
            svc2 = _service(mdir)
            rep2 = Replica(svc2, log, str(tmp_path / "s0"),
                           ReplicaConfig())
            rep2.join()                             # rejoin
            assert _audits_equal(svc, rep2.service)
            svc2.close()
        finally:
            stop.set()
            svc.close()
    assert errors == []
    from photon_ml_tpu.analysis.concurrency import lock_order_edges
    tracker.assert_consistent(lock_order_edges([PACKAGE_DIR]))
    acq = tracker.acquisitions()
    assert acq.get("Replica._lock", 0) > 0
    assert acq.get("ReplicationLog._lock", 0) > 0
    assert acq.get("FleetPublisher._lock", 0) > 0


# --------------------------------------------------------------------------
# front: probes, failover, hedging, backpressure, drain (stub replicas)
# --------------------------------------------------------------------------

class _StubReplica:
    """A minimal HTTP replica: switchable health, optional latency,
    canned /score responses — the front's behavior is protocol-level, so
    stubs make failover/hedging deterministic and fast."""

    def __init__(self, name):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
        stub = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, fmt, *a):
                pass

            def _reply(self, code, payload, headers=None):
                body = json.dumps(payload).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                for k, v in (headers or {}).items():
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/healthz":
                    ok = stub.healthy
                    self._reply(200 if ok else 503, {
                        "status": "ok" if ok else "degraded",
                        "fleet": {"applied_seq": stub.applied_seq}})
                else:
                    self._reply(404, {})

            def do_POST(self):
                length = int(self.headers.get("Content-Length") or 0)
                body = self.rfile.read(length)
                if self.path == "/flight/dump":
                    stub.flight_dumps.append(json.loads(body))
                    self._reply(200, {})
                    return
                if stub.delay_s:
                    time.sleep(stub.delay_s)
                stub.hits += 1
                if self.path == "/score":
                    self._reply(200, {"scores": [0.0], "served_by": name})
                elif self.path == "/feedback":
                    self._reply(stub.feedback_status,
                                {"served_by": name},
                                {"Retry-After": "7"}
                                if stub.feedback_status == 429 else None)
                elif self.path == "/fleet/drain":
                    stub.drained = True
                    self._reply(200, {"draining": True})
                else:
                    self._reply(404, {})

        self.name = name
        self.healthy = True
        self.applied_seq = 0
        self.delay_s = 0.0
        self.hits = 0
        self.drained = False
        self.flight_dumps = []
        self.feedback_status = 202
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


@pytest.fixture
def stubs():
    pair = [_StubReplica("a"), _StubReplica("b")]
    yield pair
    for s in pair:
        s.close()


def _front(stubs, **cfg_kw):
    cfg_kw.setdefault("probe_interval_s", 0.05)
    cfg_kw.setdefault("hedge_after_s", 5.0)
    cfg_kw.setdefault("request_timeout_s", 5.0)
    front = Front([s.url for s in stubs], config=FrontConfig(**cfg_kw),
                  start_probes=False)
    front.probe_once()
    return front


def test_front_round_robin_over_ready(stubs):
    front = _front(stubs)
    try:
        for _ in range(6):
            status, payload = front.route("/score", {})
            assert status == 200
        assert stubs[0].hits == 3 and stubs[1].hits == 3
        assert front.status()["ready_replicas"] == 2
    finally:
        front.close()


def test_front_unready_replica_leaves_rotation(stubs):
    front = _front(stubs, unhealthy_after=1)
    try:
        stubs[1].healthy = False               # e.g. a PR 11 health gate
        front.probe_once()
        for _ in range(4):
            assert front.route("/score", {})[0] == 200
        assert stubs[1].hits == 0
        stubs[1].healthy = True                # recovers
        front.probe_once()
        for _ in range(2):
            front.route("/score", {})
        assert stubs[1].hits > 0
        # probe payloads feed the lag gauge
        stubs[0].applied_seq, stubs[1].applied_seq = 9, 4
        front.probe_once()
        assert front.metrics_snapshot()["gauges"][
            "fleet.front_max_lag_seq"] == 5
    finally:
        front.close()


def test_front_dumps_and_broadcasts_one_flight_trigger_when_a_replica_leaves(
        stubs, tmp_path):
    """A replica leaving rotation is a fleet-level flight trigger: the
    front dumps its own ring and asks every attached replica to dump
    under the SAME trigger id, so the bundles can be laid side by side."""
    from photon_ml_tpu.telemetry import flight
    dumps = tmp_path / "dumps"
    with flight.enabled(str(dumps), proc="front"):
        front = _front(stubs, unhealthy_after=1)
        try:
            stubs[1].healthy = False
            front.probe_once()
            deadline = time.time() + 10
            while not stubs[0].flight_dumps and time.time() < deadline:
                time.sleep(0.01)
        finally:
            front.close()
    (bundle,) = [json.loads(f.read_text()) for f in dumps.iterdir()]
    assert bundle["reason"] == "replica.unhealthy"
    assert stubs[0].flight_dumps
    for sent in stubs[0].flight_dumps:
        assert sent["reason"] == "replica.unhealthy"
        assert sent["trigger_id"] == bundle["trigger_id"]


def test_front_failover_on_dead_replica(stubs):
    front = _front(stubs)
    try:
        stubs[0].close()                       # transport-level death
        ok = 0
        for _ in range(4):
            status, payload = front.route("/score", {})
            assert status == 200 and payload["served_by"] == "b"
            ok += 1
        assert ok == 4
        snap = front.metrics_snapshot()["counters"]
        assert snap["fleet.front_failovers"] >= 1
    finally:
        front.close()


def test_front_hedges_slow_replica(stubs):
    front = _front(stubs, hedge_after_s=0.1)
    try:
        stubs[0].delay_s = 2.0                 # slow, not dead
        t0 = time.monotonic()
        status, payload = front.route("/score", {})
        elapsed = time.monotonic() - t0
        assert status == 200
        assert payload["served_by"] == "b"     # the hedge won
        assert elapsed < 1.5                   # did not wait out the slow one
        assert front.metrics_snapshot()["counters"][
            "fleet.front_hedges"] >= 1
    finally:
        front.close()


def test_front_backpressure_sheds(stubs):
    front = _front(stubs, max_inflight=0)
    try:
        with pytest.raises(Overloaded):
            front.route("/score", {})
        assert front.metrics_snapshot()["counters"][
            "fleet.front_shed"] == 1
    finally:
        front.close()


def test_front_no_ready_replica_raises(stubs):
    front = _front(stubs, unhealthy_after=1)
    try:
        stubs[0].healthy = stubs[1].healthy = False
        front.probe_once()
        with pytest.raises(NoReadyReplica):
            front.route("/score", {})
    finally:
        front.close()


def test_front_publisher_routing_and_retry_after_passthrough(stubs):
    front = _front(stubs)
    try:
        status, payload, headers = front.route_publisher(
            "POST", "/feedback", {"labels": [1.0]})
        assert status == 202
        assert payload["served_by"] == "a"     # first URL is the publisher
        stubs[0].feedback_status = 429
        status, _payload, headers = front.route_publisher(
            "POST", "/feedback", {"labels": [1.0]})
        assert status == 429
        assert headers["Retry-After"] == "7"   # backpressure hint rides up
    finally:
        front.close()


def test_front_drain_detaches(stubs):
    front = _front(stubs)
    try:
        out = front.drain(stubs[0].url)
        assert out["detached"] is True
        assert stubs[0].drained is True
        hits0 = stubs[0].hits
        for _ in range(3):
            assert front.route("/score", {})[0] == 200
        assert stubs[0].hits == hits0          # no longer routed to
        assert front.status()["ready_replicas"] == 1
    finally:
        front.close()


# --------------------------------------------------------------------------
# satellites
# --------------------------------------------------------------------------

def test_registry_overflow_degrades_loudly(tmp_path, rng, caplog):
    """Satellite: undo-log overflow is configurable and LOUD — the
    overflow logs an error, rollback degrades to the full-model path,
    and serve.rollback_degraded lands on both metric surfaces."""
    mdir = _save_model(rng, tmp_path)
    mdir2 = _save_model(np.random.default_rng(31), tmp_path, "m2", 1.5)
    svc = ScoringService(
        model_dir=mdir,
        config=ServingConfig(max_batch=64, min_bucket=4, max_delta_log=2),
        updates=OnlineUpdateConfig(micro_batch=4), start_updater=False)
    try:
        v1 = svc.model_version
        svc.swap(mdir2, version="v2")
        with caplog.at_level(logging.ERROR, logger="photon_ml_tpu"):
            while svc.registry.pending_deltas() < 2 or \
                    not svc.registry._delta_log_truncated:
                _feedback(svc, int(svc.version_vector()["delta_seq"]))
        assert any("overflowed" in r.message for r in caplog.records)
        table_before = np.asarray(
            svc.registry.scorer.re_table("perUser")).copy()
        with caplog.at_level(logging.ERROR, logger="photon_ml_tpu"):
            got = svc.rollback()
        assert got == v1                       # degraded to full-model
        assert any("DEGRADED" in r.message for r in caplog.records)
        snap = svc.metrics_snapshot()
        assert snap["rollback_degraded"] == 1
        assert "photon_serve_rollback_degraded_total 1" in \
            svc.prometheus_metrics()
        # the exact pre-delta rows are NOT restored (that is the point
        # of the degradation being loud)
        assert not np.array_equal(
            np.asarray(svc.registry.scorer.re_table("perUser")),
            table_before)
    finally:
        svc.close()


def test_registry_overflow_without_previous_raises(tmp_path, rng):
    mdir = _save_model(rng, tmp_path)
    svc = ScoringService(
        model_dir=mdir,
        config=ServingConfig(max_batch=64, min_bucket=4, max_delta_log=1),
        updates=OnlineUpdateConfig(micro_batch=4), start_updater=False)
    try:
        while not svc.registry._delta_log_truncated:
            _feedback(svc, int(svc.version_vector()["delta_seq"]) + 40)
        with pytest.raises(RuntimeError, match="known-good"):
            svc.rollback()
        assert svc.metrics_snapshot()["rollback_degraded"] == 0
    finally:
        svc.close()


def test_exact_rollback_path_keeps_degraded_counter_zero(tmp_path, rng):
    mdir = _save_model(rng, tmp_path)
    svc = ScoringService(
        model_dir=mdir,
        config=ServingConfig(max_batch=64, min_bucket=4,
                             max_delta_log=64),
        updates=OnlineUpdateConfig(micro_batch=8), start_updater=False)
    try:
        table0 = np.asarray(svc.registry.scorer.re_table("perUser")).copy()
        _feedback(svc, 800)
        svc.rollback()
        assert np.array_equal(
            np.asarray(svc.registry.scorer.re_table("perUser")), table0)
        assert svc.metrics_snapshot()["rollback_degraded"] == 0
    finally:
        svc.close()


def test_stale_delta_reenqueue_races_concurrent_install(tmp_path, rng):
    """Satellite: a full install() landing between the updater's solve
    and its publish surfaces as StaleDeltaError — the feedback
    re-enqueues, the re-solve runs against the NEW version, and no delta
    from the old base ever lands.  Run with locktrace ARMED and
    validated against the static lock graph."""
    from photon_ml_tpu.serving import CompiledScorer
    with locktrace.enabled() as tracker:
        mdir = _save_model(rng, tmp_path)
        svc = ScoringService(
            model_dir=mdir,
            config=ServingConfig(max_batch=64, min_bucket=4),
            updates=OnlineUpdateConfig(micro_batch=8),
            start_updater=False)
        try:
            v1 = svc.model_version
            scorer2 = CompiledScorer(_make_model(np.random.default_rng(41)),
                                     max_batch=64, min_bucket=4)
            scorer2.warmup()
            real_solve = svc.updater._solve_with_retry
            installed = []

            def solve_then_install(lane, blocks, prior):
                out = real_solve(lane, blocks, prior)
                if not installed:      # exactly one racing install
                    svc.registry.install(scorer2, "v2")
                    installed.append(True)
                return out

            svc.updater._solve_with_retry = solve_then_install
            r = np.random.default_rng(43)
            feats = {"global": r.normal(size=(8, D_G)),
                     "per_user": r.normal(size=(8, D_U))}
            ids = {"userId": np.asarray(
                [f"u{i}" for i in range(8)], dtype=object)}
            labels = (r.uniform(size=8) < 0.5).astype(float)
            svc.feedback(feats, ids, labels)
            out1 = svc.updater.run_once()
            # the racing install won: nothing published this cycle
            assert out1["deltas"] == 0
            snap = svc.metrics_snapshot()
            assert snap["online"]["stale_deltas"] == 1
            assert svc.model_version == "v2"
            # the re-enqueued feedback re-solves against v2 next cycle
            out2 = svc.updater.run_once()
            assert out2["deltas"] >= 1
            assert svc.updater.buffer.pending_rows == 0
            deltas = svc.registry.applied_deltas()
            assert deltas and all(d.base_version == "v2" for d in deltas)
            assert v1 not in {d.base_version for d in deltas}
        finally:
            svc.close()
    from photon_ml_tpu.analysis.concurrency import lock_order_edges
    tracker.assert_consistent(lock_order_edges([PACKAGE_DIR]))
    assert tracker.acquisitions().get("ModelRegistry._lock", 0) > 0


def test_feedback_429_carries_retry_after(tmp_path, rng):
    """Satellite: a whole-batch feedback rejection carries a drain-rate
    derived retry_after_s and counts online.feedback_rejected on both
    metric surfaces."""
    mdir = _save_model(rng, tmp_path)
    svc = ScoringService(
        model_dir=mdir, config=ServingConfig(max_batch=64, min_bucket=4),
        updates=OnlineUpdateConfig(micro_batch=4, max_pending_rows=4),
        start_updater=False)
    try:
        r = np.random.default_rng(53)
        n = 16                                 # > max_pending_rows: whole
        feats = {"global": r.normal(size=(n, D_G)),  # batch rejected
                 "per_user": r.normal(size=(n, D_U))}
        ids = {"userId": np.asarray(
            [f"u{i % N_ENT}" for i in range(n)], dtype=object)}
        labels = np.zeros(n)
        with pytest.raises(Overloaded) as exc:
            svc.feedback(feats, ids, labels)
        assert exc.value.retry_after_s > 0
        snap = svc.metrics_snapshot()
        assert snap["online"]["feedback_rejected"] == 1
        assert "photon_online_feedback_rejected_total 1" in \
            svc.prometheus_metrics()
        # once the updater has drained, the estimate follows the
        # observed rate instead of the poll-interval floor
        _feedback(svc, 900, n=4)
        assert svc.updater.retry_after_s() >= \
            svc.updater.config.interval_s
    finally:
        svc.close()


def test_table_hashes_track_delta_state(tmp_path, rng):
    mdir = _save_model(rng, tmp_path)
    svc = ScoringService(
        model_dir=mdir, config=ServingConfig(max_batch=64, min_bucket=4),
        updates=OnlineUpdateConfig(micro_batch=8), start_updater=False)
    try:
        h0 = svc.registry.scorer.table_hashes()
        assert set(h0) == {"fixed", "perUser"}
        _feedback(svc, 950)
        h1 = svc.registry.scorer.table_hashes()
        assert h1["perUser"] != h0["perUser"]
        assert h1["fixed"] == h0["fixed"]      # FE untouched by deltas
        svc.rollback()
        assert svc.registry.scorer.table_hashes() == h0  # bit-exact
    finally:
        svc.close()


@pytest.mark.parametrize("fill_buffer", [False, True])
def test_graceful_drain_sigterm_subprocess(tmp_path, fill_buffer):
    """Satellite: SIGTERM drains the serve CLI cleanly — stop accepting,
    finish in-flight, flush the FeedbackBuffer through the updater,
    close, exit 0 with a final drained line.  The fill_buffer variant
    also exercises the HTTP 429 + Retry-After path first."""
    import urllib.error
    import urllib.request

    r = np.random.default_rng(61)
    mdir = str(tmp_path / "model")
    save_game_model(_make_model(r), mdir)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    stderr_path = tmp_path / "serve.stderr"
    with open(stderr_path, "w") as stderr_file:
        proc = subprocess.Popen(
            [sys.executable, "-m", "photon_ml_tpu.cli.serve",
             "--model-dir", mdir, "--port", "0", "--max-batch", "32",
             "--min-bucket", "4", "--enable-updates",
             "--feedback-max-pending", "8" if fill_buffer else "1024",
             "--update-interval-ms", "50"],
            stdout=subprocess.PIPE, stderr=stderr_file, env=env, text=True)
    try:
        info = json.loads(proc.stdout.readline())
        url = info["serving"]

        def post(path, body):
            req = urllib.request.Request(
                url + path, data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=15) as resp:
                    return resp.status, dict(resp.headers), \
                        json.loads(resp.read())
            except urllib.error.HTTPError as e:
                return e.code, dict(e.headers), json.loads(e.read())

        n = 16
        body = {"features": {
            "global": r.normal(size=(n, D_G)).tolist(),
            "per_user": r.normal(size=(n, D_U)).tolist()},
            "ids": {"userId": [f"u{i % N_ENT}" for i in range(n)]},
            "labels": [0.0] * n}
        if fill_buffer:
            status, headers, payload = post("/feedback", body)
            assert status == 429
            assert int(headers["Retry-After"]) >= 1
            assert payload["retry_after_s"] > 0
        else:
            status, _headers, _payload = post("/feedback", body)
            assert status == 202
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate(timeout=10)
    assert proc.returncode == 0, (
        f"drained serve child exited {proc.returncode}; stdout tail "
        f"{out[-300:]!r}; stderr tail {stderr_path.read_text()[-2000:]!r}")
    last = json.loads(out.strip().splitlines()[-1])
    assert last["drained"] is True and last["aborted"] is False
    if not fill_buffer:
        # the drain flushed the buffered feedback before exit
        assert last["feedback_flushed"] is not None
        assert last["version_vector"]["delta_seq"] >= 1
