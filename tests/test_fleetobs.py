"""Fleet-wide observability tests (ISSUE 13): cross-process trace
propagation (telemetry/distributed), the multi-process run-log merge,
the front's federated metric surfaces + parity contract, and the
dump-on-anomaly flight recorder (telemetry/flight).

The subprocess leg is the ISSUE 13 satellite: two subprocess replicas
and a front under load, every process tracing to its own run log, the
merged trace passing `validate_chrome_trace`, every sampled request id
one connected tree, and clock-offset alignment keeping child spans
inside their parents.
"""
import json
import os
import re
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

from photon_ml_tpu import telemetry
from photon_ml_tpu.telemetry import distributed, flight
from photon_ml_tpu.telemetry.distributed import (PARENT_HEADER,
                                                 TRACE_HEADER,
                                                 merge_run_logs,
                                                 parse_run_log)
from photon_ml_tpu.fleet import FRONT_SNAPSHOT_PATHS, Front, FrontConfig
from photon_ml_tpu.models.coefficients import Coefficients
from photon_ml_tpu.models.game import (FixedEffectModel, GameModel,
                                       RandomEffectModel)
from photon_ml_tpu.models.glm import model_for_task
from photon_ml_tpu.models.io import save_game_model
from photon_ml_tpu.utils import faults

D_G, D_U, N_ENT = 6, 4, 30
TASK = "logistic_regression"
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_model(rng):
    fe = FixedEffectModel(
        model_for_task(TASK, Coefficients(
            jnp.asarray(rng.normal(size=D_G)))), "global")
    re_m = RandomEffectModel(
        random_effect_type="userId", feature_shard="per_user",
        task_type=TASK,
        coefficients=jnp.asarray(rng.normal(size=(N_ENT, D_U))),
        entity_ids=np.asarray([f"u{i}" for i in range(N_ENT)],
                              dtype=object),
        projection=None, global_dim=D_U)
    return GameModel({"fixed": fe, "perUser": re_m}, TASK)


# --------------------------------------------------------------------------
# trace context + propagation primitives
# --------------------------------------------------------------------------

def test_server_span_adopts_headers_and_scopes_context(tmp_path):
    log = str(tmp_path / "run.jsonl")
    with telemetry.enabled(run_log=log, watch_compiles=False,
                           proc="testproc"):
        assert distributed.current_request_id() is None
        headers = {TRACE_HEADER: "aaaabbbbccccdddd",
                   PARENT_HEADER: "777:3"}
        with distributed.server_span("serve_request", headers,
                                     path="/score") as scope:
            assert scope.request_id == "aaaabbbbccccdddd"
            assert distributed.current_request_id() == scope.request_id
            out = distributed.outbound_headers()
            assert out[TRACE_HEADER] == "aaaabbbbccccdddd"
            # the outbound parent is THIS span's ref, not the incoming
            assert out[PARENT_HEADER] == \
                f"{os.getpid()}:{telemetry.current_span_id()}"
        assert distributed.current_request_id() is None
    records = [json.loads(l) for l in open(log)]
    assert records[0]["kind"] == "meta"
    assert records[0]["proc"] == "testproc"
    span = next(r for r in records if r["kind"] == "span")
    assert span["attrs"]["request_id"] == "aaaabbbbccccdddd"
    assert span["attrs"]["remote_parent"] == "777:3"


def test_server_span_mints_when_no_header_and_disarmed_tracer():
    with distributed.server_span("serve_request", None) as scope:
        assert len(scope.request_id) == 16
        assert distributed.current_request_id() == scope.request_id
    assert distributed.current_request_id() is None


# --------------------------------------------------------------------------
# merge: synthetic multi-process run logs (deterministic)
# --------------------------------------------------------------------------

def _write_log(path, meta, records):
    with open(path, "w") as f:
        f.write(json.dumps({"kind": "meta", "name": "process_meta",
                            "span": None, **meta}) + "\n")
        for r in records:
            f.write(json.dumps(r) + "\n")


def _span(sid, name, t0, dur, parent=None, attrs=None, tid=1):
    return {"kind": "span", "name": name, "span": sid, "parent": parent,
            "tid": tid, "thread": "main", "t0_s": t0, "dur_s": dur,
            "attrs": attrs or {}}


def test_merge_connectivity_and_clock_alignment(tmp_path):
    """A front + remote process whose wall anchor is 0.5s off: the
    clock_probe event corrects it, the request is one connected tree,
    and the child lands inside its parent.  Without the probe the child
    would sit half a second outside."""
    rid = "feed000000000001"
    front_log = str(tmp_path / "front.jsonl")
    rep_log = str(tmp_path / "rep.jsonl")
    _write_log(front_log, {"proc": "front", "pid": 100,
                           "wall0_unix_s": 1000.0}, [
        _span(1, "front_request", 1.0, 0.2,
              attrs={"request_id": rid, "path": "/score"}),
        {"kind": "event", "name": "clock_probe", "span": None, "tid": 1,
         "t_s": 0.5,
         "attrs": {"pid": 200, "proc": "replica", "offset_s": 0.5,
                   "rtt_s": 0.002}},
    ])
    # the replica's own anchor claims wall0=1000.5 (0.5s fast); its span
    # at rel t0=1.05 is REALLY at front-time 1001.05
    _write_log(rep_log, {"proc": "replica", "pid": 200,
                         "wall0_unix_s": 1000.5}, [
        _span(7, "serve_request", 1.05, 0.1,
              attrs={"request_id": rid, "remote_parent": "100:1"}),
    ])
    report = merge_run_logs([front_log, rep_log],
                            out_path=str(tmp_path / "merged.json"))
    assert report["problems"] == []
    assert report["clock_offsets"]["200"]["offset_s"] == 0.5
    tree = report["requests"][rid]
    assert tree["connected"] is True
    assert tree["processes"] == [100, 200]
    assert report["containment"]["checked"] == 1
    assert report["containment"]["violations"] == []
    # the written trace validates and carries both process tracks
    payload = json.load(open(tmp_path / "merged.json"))
    assert telemetry.validate_chrome_trace(payload) == []
    names = {e["args"]["name"] for e in payload["traceEvents"]
             if e.get("name") == "process_name"}
    assert names == {"front (100)", "replica (200)"}
    # ... and WITHOUT the probe, the same logs violate containment
    _write_log(front_log, {"proc": "front", "pid": 100,
                           "wall0_unix_s": 1000.0}, [
        _span(1, "front_request", 1.0, 0.2,
              attrs={"request_id": rid, "path": "/score"}),
    ])
    report2 = merge_run_logs([front_log, rep_log])
    assert report2["containment"]["violations"]


def test_merge_async_feedback_chain_connects(tmp_path):
    """The asynchronous half: serve_request -> (same-process flow) ->
    online_update -> (record trace parent) -> replica_apply on another
    process, all joined under one request id."""
    rid = "feed000000000002"
    pub = str(tmp_path / "pub.jsonl")
    rep = str(tmp_path / "rep.jsonl")
    _write_log(pub, {"proc": "publisher", "pid": 300,
                     "wall0_unix_s": 2000.0}, [
        _span(1, "serve_request", 1.0, 0.01,
              attrs={"request_id": rid, "path": "/feedback"}),
        _span(2, "online_update", 2.0, 0.5,
              attrs={"request_ids": rid + ",otherid", "coordinate": "x"}),
    ])
    _write_log(rep, {"proc": "replica", "pid": 301,
                     "wall0_unix_s": 2000.0}, [
        _span(9, "replica_apply", 3.0, 0.05,
              attrs={"request_ids": rid, "remote_parent": "300:2"}),
    ])
    report = merge_run_logs([pub, rep])
    tree = report["requests"][rid]
    assert tree["connected"] is True
    assert tree["processes"] == [300, 301]
    assert set(tree["span_names"]) == {"serve_request", "online_update",
                                       "replica_apply"}
    # async cross-process links are NOT containment-checked
    assert report["containment"]["checked"] == 0


def test_merge_torn_tail_and_missing_meta(tmp_path):
    good = str(tmp_path / "good.jsonl")
    _write_log(good, {"proc": "p", "pid": 1, "wall0_unix_s": 0.0},
               [_span(1, "a", 0.0, 1.0)])
    with open(good, "a") as f:
        f.write('{"kind": "span", "name": "torn')  # SIGKILL mid-write
    parsed = parse_run_log(good)
    assert [s["name"] for s in parsed["spans"]] == ["a"]
    bad = str(tmp_path / "bad.jsonl")
    with open(bad, "w") as f:
        f.write(json.dumps(_span(1, "a", 0.0, 1.0)) + "\n")
    with pytest.raises(ValueError, match="process_meta"):
        parse_run_log(bad)


# --------------------------------------------------------------------------
# front: metric-surface parity (satellite) + labeled counters
# --------------------------------------------------------------------------

def _flatten_paths(d, prefix=()):
    out = set()
    for k, v in d.items():
        out.add(prefix + (k,))
        if isinstance(v, dict):
            out |= _flatten_paths(v, prefix + (k,))
    return out


def test_front_metric_surface_parity_prometheus_vs_json():
    """ISSUE 13 satellite: the front's registry rides the same
    SNAPSHOT_PATHS parity contract as ServingMetrics — every instrument
    has a declared front_snapshot() path, every path resolves, and every
    instrument renders in the Prometheus exposition (labeled families
    included)."""
    front = Front(["http://127.0.0.1:1"], start_probes=False)
    try:
        front._m_by_replica.inc(replica="http://127.0.0.1:1",
                                outcome="ok")
        names = set(front.registry.names())
        assert names == set(FRONT_SNAPSHOT_PATHS), (
            "every front instrument needs a FRONT_SNAPSHOT_PATHS entry "
            f"(missing: {sorted(names - set(FRONT_SNAPSHOT_PATHS))}, "
            f"stale: {sorted(set(FRONT_SNAPSHOT_PATHS) - names)})")
        snap = front.front_snapshot()
        paths = _flatten_paths(snap)
        for name, path in FRONT_SNAPSHOT_PATHS.items():
            assert path in paths, (
                f"instrument {name!r} declares JSON path {path} but "
                "front_snapshot() has no such key")
        reg = front.registry.snapshot()
        prom = front.prometheus_metrics()
        series = set(re.findall(r"^photon_[a-zA-Z0-9_]+", prom,
                                flags=re.M))
        clean = lambda n: "photon_" + re.sub(r"[^a-zA-Z0-9_]", "_", n)
        for name in reg["counters"]:
            assert clean(name) + "_total" in series, name
        for name in reg["gauges"]:
            assert clean(name) in series, name
        for name in reg["labeled"]:
            assert clean(name) + "_total" in series, name
        # the labeled family renders per-(replica, outcome) series
        assert ('photon_front_requests_total{outcome="ok",'
                'replica="http://127.0.0.1:1"} 1') in prom
    finally:
        front.close()


def test_front_outcome_counters_and_hedge_wins():
    """front.requests{replica,outcome} separates ok / error / abandoned
    hedges, and a hedge that beats the original counts as a win."""
    class Stub:
        def __init__(self, delay_s=0.0):
            from http.server import (BaseHTTPRequestHandler,
                                     ThreadingHTTPServer)
            stub = self

            class H(BaseHTTPRequestHandler):
                protocol_version = "HTTP/1.1"

                def log_message(self, fmt, *a):
                    pass

                def do_POST(self):
                    n = int(self.headers.get("Content-Length") or 0)
                    self.rfile.read(n)
                    stub.trace_headers.append(
                        self.headers.get(TRACE_HEADER))
                    if stub.delay_s:
                        time.sleep(stub.delay_s)
                    body = b'{"scores": [0.0]}'
                    self.send_response(200)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)

                def do_GET(self):
                    body = b'{"status": "ok"}'
                    self.send_response(200)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)

            self.delay_s = delay_s
            self.trace_headers = []
            self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
            self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"
            self._t = threading.Thread(target=self.httpd.serve_forever,
                                       kwargs={"poll_interval": 0.05},
                                       daemon=True)
            self._t.start()

        def close(self):
            self.httpd.shutdown()
            self.httpd.server_close()
            self._t.join(timeout=5)

    slow, fast = Stub(delay_s=1.0), Stub()
    front = Front([slow.url, fast.url],
                  config=FrontConfig(hedge_after_s=0.1,
                                     request_timeout_s=5.0),
                  start_probes=False)
    try:
        front.probe_once()
        status, _ = front.route("/score", {})
        assert status == 200
        by = front.front_snapshot()["requests_by_replica"]
        assert by.get(f"replica={fast.url},outcome=ok") == 1
        # the slow original was abandoned, and the hedge won
        assert by.get(f"replica={slow.url},outcome=abandoned") == 1
        assert front.front_snapshot()["hedge_wins"] == 1
        assert front.front_snapshot()["hedges"] == 1
        # both attempts carried the SAME propagated request id
        deadline = time.time() + 5
        while len(slow.trace_headers) < 1 and time.time() < deadline:
            time.sleep(0.05)
        assert fast.trace_headers[0] is not None
        assert slow.trace_headers[0] == fast.trace_headers[0]
    finally:
        front.close()
        slow.close()
        fast.close()


# --------------------------------------------------------------------------
# flight recorder
# --------------------------------------------------------------------------

def test_flight_ring_bounded_and_dump_bundle(tmp_path):
    with telemetry.enabled(watch_compiles=False):
        with flight.enabled(str(tmp_path / "dumps"), proc="t",
                            ring_records=16) as rec:
            for k in range(64):
                telemetry.event("tick", k=k)
            assert len(rec.snapshot()) == 16   # bounded: newest-N
            path = flight.trigger("serve.drain", note="test")
    bundle = json.load(open(path))
    assert bundle["reason"] == "serve.drain"
    assert bundle["proc"] == "t"
    assert bundle["window_s"][0] <= bundle["window_s"][1]
    names = [r.get("name") for r in bundle["records"]]
    assert "flight_dump" in names          # the trigger itself is in-ring
    assert bundle["attrs"]["note"] == "test"
    assert "metrics" in bundle


def test_flight_trigger_disarmed_is_noop_and_unknown_raises(tmp_path):
    assert not flight.armed()
    assert flight.trigger("serve.drain") is None   # disarmed: no-op
    with flight.enabled(str(tmp_path)):
        with pytest.raises(ValueError, match="unknown flight trigger"):
            flight.trigger("not.a.trigger")


def test_flight_triggers_have_event_constants():
    from photon_ml_tpu.telemetry.events import EVENTS
    assert set(flight.TRIGGERS) <= set(EVENTS)
    assert set(faults.SITES) <= set(EVENTS)


def test_replica_failure_dumps_flight_bundle(tmp_path, rng=None):
    """A fatal apply marks the replica failed AND dumps its ring — the
    replica.failed trigger wired in fleet/replica.py."""
    from photon_ml_tpu.fleet import (FleetPublisher, Replica,
                                     ReplicaConfig, ReplicationLog)
    from photon_ml_tpu.online import OnlineUpdateConfig
    from photon_ml_tpu.serving import ScoringService, ServingConfig
    r = np.random.default_rng(17)
    mdir = str(tmp_path / "model")
    save_game_model(_make_model(r), mdir)

    def service(updates):
        return ScoringService(
            model_dir=mdir, config=ServingConfig(max_batch=64,
                                                 min_bucket=4),
            updates=OnlineUpdateConfig(micro_batch=8) if updates
            else None, start_updater=False)

    dumps = str(tmp_path / "dumps")
    svc = service(updates=True)
    log = ReplicationLog(str(tmp_path / "log"))
    FleetPublisher(svc, log, model_dir=mdir)
    rep = Replica(service(updates=False), log, str(tmp_path / "s0"),
                  ReplicaConfig())
    rep.join()
    try:
        feats = {"global": r.normal(size=(8, D_G)),
                 "per_user": r.normal(size=(8, D_U))}
        ids = {"userId": np.asarray([f"u{i}" for i in range(8)],
                                    dtype=object)}
        svc.feedback(feats, ids, np.zeros(8))
        svc.updater.flush()
        with flight.enabled(dumps, proc="replica"):
            plan = faults.FaultPlan([{"site": "replica.apply",
                                      "action": "fatal",
                                      "probability": 1.0}])
            with faults.injected(plan):
                assert rep.poll_once() == 0
        assert not rep.healthy()
        bundles = [json.load(open(os.path.join(dumps, f)))
                   for f in os.listdir(dumps)]
        assert len(bundles) == 1
        assert bundles[0]["reason"] == "replica.failed"
        assert "FatalFault" in bundles[0]["attrs"]["error"]
    finally:
        svc.close()
        rep.service.close()


def test_request_tracing_adds_no_fresh_traces_armed_or_disarmed():
    """Warm scoring traces nothing with fleet observability off, and
    nothing with the tracer, the flight ring and a per-request
    `server_span` context armed: no span name or request id reaches a jit
    boundary."""
    from photon_ml_tpu.serving import ScoringService, ServingConfig
    from test_pipeline import _compile_counting
    r = np.random.default_rng(29)
    svc = ScoringService(model=_make_model(r),
                         config=ServingConfig(max_batch=64, min_bucket=4))

    def one_round(armed):
        for k in range(24):
            feats = {"global": r.normal(size=(4, D_G)),
                     "per_user": r.normal(size=(4, D_U))}
            ids = {"userId": np.asarray(
                [f"u{r.integers(0, N_ENT)}" for _ in range(4)],
                dtype=object)}
            if armed:
                with distributed.server_span(
                        "serve_request", {TRACE_HEADER: f"{k:016x}"},
                        path="/score"):
                    svc.score(feats, ids)
            else:
                svc.score(feats, ids)

    try:
        one_round(False)                            # warm the bucket
        with _compile_counting() as disarmed:
            one_round(False)
        with telemetry.enabled(watch_compiles=False) as tracer:
            with flight.enabled(None, proc="serve"):
                with _compile_counting() as armed:
                    one_round(True)
        assert (disarmed.count, armed.count) == (0, 0)
        assert any(s.name == "serve_request" for s in tracer.spans)
    finally:
        svc.close()


# --------------------------------------------------------------------------
# the subprocess merge satellite: 2 replicas + front under load
# --------------------------------------------------------------------------

def _spawn_serve(args, env):
    proc = subprocess.Popen(
        [sys.executable, "-m", "photon_ml_tpu.cli.serve"] + args,
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, env=env,
        text=True)
    line = proc.stdout.readline()
    if not line:
        proc.wait(timeout=10)
        raise RuntimeError(f"serve child died rc={proc.returncode}")
    return proc, json.loads(line)["serving"]


def _http(url, path, body=None, headers=None, timeout=20.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url + path, data=data,
        method="POST" if data is not None else "GET",
        headers={"Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _wait(pred, timeout_s=120.0, step_s=0.2):
    t0 = time.time()
    while time.time() - t0 < timeout_s:
        try:
            if pred():
                return True
        except Exception:
            pass
        time.sleep(step_s)
    return False


def test_two_subprocess_replicas_front_merge(tmp_path):
    """ISSUE 13 satellite: two subprocess replicas + a front under load;
    the merged trace validates, every sampled request id is one
    connected tree crossing processes, the feedback flow reaches the
    follower's apply, and clock alignment keeps children inside their
    parents."""
    r = np.random.default_rng(23)
    mdir = str(tmp_path / "model")
    save_game_model(_make_model(r), mdir)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    logs = {n: str(tmp_path / f"{n}.jsonl")
            for n in ("front", "pub", "f0")}
    common = ["--model-dir", mdir, "--port", "0", "--max-batch", "32",
              "--min-bucket", "4",
              "--replication-log", str(tmp_path / "replog")]
    pub, pub_url = _spawn_serve(
        common + ["--replica", "--publish", "--enable-updates",
                  "--update-interval-ms", "10",
                  "--replica-state", str(tmp_path / "pub"),
                  "--run-log", logs["pub"]], env)
    f0, f0_url = _spawn_serve(
        common + ["--replica", "--replica-poll-ms", "20",
                  "--replica-state", str(tmp_path / "f0"),
                  "--run-log", logs["f0"]], env)
    front = None
    try:
        assert _wait(lambda: _http(pub_url, "/healthz")[0] == 200)
        assert _wait(lambda: _http(f0_url, "/healthz")[0] == 200)
        front, front_url = _spawn_serve(
            ["--front", "--replica-url", pub_url,
             "--replica-url", f0_url, "--port", "0",
             "--probe-interval-ms", "100",
             "--run-log", logs["front"]], env)
        assert _wait(lambda: _http(front_url, "/healthz")[0] == 200)
        score_ids = [f"{k:016x}" for k in range(1, 7)]
        for rid in score_ids:
            body = {"features": {
                "global": r.normal(size=(2, D_G)).tolist(),
                "per_user": r.normal(size=(2, D_U)).tolist()},
                "ids": {"userId": ["u1", "u2"]}}
            status, _ = _http(front_url, "/score", body,
                              headers={TRACE_HEADER: rid})
            assert status == 200
        fb_rid = "fb00000000000001"
        n = 8
        body = {"features": {
            "global": r.normal(size=(n, D_G)).tolist(),
            "per_user": r.normal(size=(n, D_U)).tolist()},
            "ids": {"userId": [f"u{i}" for i in range(n)]},
            "labels": [0.0, 1.0] * (n // 2)}
        applied0 = _http(f0_url, "/metrics.json")[1]["fleet"][
            "records_applied"]
        status, _ = _http(front_url, "/feedback", body,
                          headers={TRACE_HEADER: fb_rid})
        assert status == 202
        # the feedback's DELTA must land on the follower before we drain
        # (>= applied0 + 1: the bootstrap swap record already counts
        # toward records_applied, so an absolute >= 1 races the drain)
        assert _wait(lambda: _http(f0_url, "/metrics.json")[1]
                     ["fleet"]["records_applied"] >= applied0 + 1)
    finally:
        for proc in (front, pub, f0):
            if proc is not None:
                p = proc[0] if isinstance(proc, tuple) else proc
                p.send_signal(signal.SIGTERM)
        for proc in (front, pub, f0):
            if proc is not None:
                p = proc[0] if isinstance(proc, tuple) else proc
                try:
                    p.communicate(timeout=60)
                except subprocess.TimeoutExpired:
                    p.kill()
    report = merge_run_logs(list(logs.values()),
                            out_path=str(tmp_path / "merged.json"))
    assert report["problems"] == []
    assert len(report["processes"]) == 3
    # clock probes produced offsets for both probed replicas
    assert len(report["clock_offsets"]) >= 2
    for rid in score_ids:
        tree = report["requests"][rid]
        assert tree["connected"] is True, rid
        assert len(tree["processes"]) >= 2
        assert {"front_request", "serve_request"} <= \
            set(tree["span_names"])
    fb = report["requests"][fb_rid]
    assert fb["connected"] is True
    assert len(fb["processes"]) == 3
    assert {"front_request", "serve_request", "online_update",
            "replica_apply"} <= set(fb["span_names"])
    # alignment: synchronous children inside their front parents
    assert report["containment"]["checked"] >= len(score_ids)
    assert report["containment"]["violations"] == []
    # the new replica-side instruments made it to both surfaces (the
    # run happened over HTTP, so check the merged JSON snapshot shape
    # via a fresh ServingMetrics instead)
    from photon_ml_tpu.serving.metrics import SNAPSHOT_PATHS
    assert "fleet.apply_latency_s" in SNAPSHOT_PATHS
    assert "fleet.feedback_visible_s" in SNAPSHOT_PATHS
