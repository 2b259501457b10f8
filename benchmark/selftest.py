"""Checks of the yardstick itself, in seconds, on the CPU, without JAX:

    python benchmark/selftest.py

Interval and idle-share arithmetic on hand-made intervals; the float64
certificate on a tiny problem solved to machine precision; and that every
name in BENCHMARK.json resolves to a file and to a metric the cell reports.
"""
from __future__ import annotations

import glob
import os
import re
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import costs, reference, trace_reduce as tr  # noqa: E402
from benchmark.run import load_json, load_module, metrics_of  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def close(a, b, tol=1e-12):
    return abs(a - b) <= tol * max(1.0, abs(a), abs(b))


def test_intervals():
    merged = tr.merge([(1, 2), (1.5, 3), (5, 6), (6, 7), (9, 9), (10, 11)])
    assert merged == [(1, 3), (5, 7), (10, 11)], merged
    assert close(tr.busy_seconds(merged, 0, 12), 5)
    assert close(tr.busy_seconds(merged, 2, 10.5), 1 + 2 + 0.5)
    assert tr.gaps(merged, 0, 12) == [(0, 1), (3, 5), (7, 10), (11, 12)]
    assert tr.gaps(merged, 2, 6) == [(3, 5)]
    assert tr.gaps([], 0, 1) == [(0, 1)]
    # idle share over [0, 12]: 1 - 5/12
    assert close(1 - tr.busy_seconds(merged, 0, 12) / 12, 7 / 12)


def test_self_seconds():
    # a while of 10 s that holds two bodies of 3 s and 4 s, then a lone op
    events = [("while", 0, 10), ("fusion.1", 1, 4), ("fusion.2", 5, 9),
              ("copy", 11, 12), ("fusion.1", 12, 12.5)]
    got = tr.self_seconds(events)
    assert close(got["while"], 3) and close(got["fusion.1"], 3.5), got
    assert close(got["fusion.2"], 4) and close(got["copy"], 1), got
    assert close(sum(got.values()), 11.5)     # the union, counted once


def test_short_name():
    op = ("%multiply_reduce_fusion.30 = f32[124]{0:T(128)S(1)} fusion(f32[9,"
          "124]{1,0:T(8,128)} %get-tuple-element.1687), kind=kLoop")
    assert tr.short_name(op) == "multiply_reduce_fusion.30 f32[124]"
    assert tr.short_name("while.3") == "while.3"


def test_gap_names():
    marks = [("bench/fit", 0, 10), ("bench/fit", 10, 20)]
    host = [("stage", 1, 4), ("solve", 4.5, 5), ("stage", 11, 12)]
    idle = [(1, 4), (6, 7), (11, 12), (25, 26)]
    got = dict(tr.name_gaps(idle, marks, host))
    assert close(got["bench/fit | stage"], 4), got
    assert close(got["bench/fit"], 1), got
    assert close(got["outside bench/*"], 1), got


def test_certificate():
    rng = np.random.default_rng(3)
    n, d, lam = 4000, 12, 1.0
    x = (rng.random((n, d)) < 0.3).astype(np.float32)
    x[:, -1] = 1.0
    truth = rng.normal(size=d)
    y = (rng.random(n) < reference.sigmoid(x @ truth)).astype(np.float32)
    chunks, labels = [x[:2500], x[2500:]], [y[:2500], y[2500:]]
    w = np.zeros(d)
    for _ in range(30):                       # Newton to machine precision
        _, g = reference.logistic_pass(chunks, labels, w, lam)
        w = w - np.linalg.solve(
            reference.logistic_hessian(chunks, w, lam), g)
    f_star, g_star = reference.logistic_pass(chunks, labels, w, lam)
    assert np.linalg.norm(g_star) < 1e-6 * n, np.linalg.norm(g_star)
    # the pass agrees with a plain one-line evaluation
    z = x.astype(np.float64) @ w
    plain = np.sum(np.log1p(np.exp(z)) - y * z) + 0.5 * lam * w @ w
    assert close(f_star, plain, 1e-10), (f_star, plain)
    # the bound holds at perturbed points
    for scale in (1e-3, 1e-2, 1e-1, 1.0):
        v = w + scale * rng.normal(size=d)
        f, g = reference.logistic_pass(chunks, labels, v, lam)
        assert f - f_star <= reference.suboptimality_bound(g, lam) + 1e-9
    near = reference.certify_logistic(chunks, labels, w + 1e-4, lam, 1e-4)
    assert near["ok"], near
    far = reference.certify_logistic(chunks, labels, w + 0.05, lam, 1e-4)
    assert not far["ok"] and far["newton_steps"] >= 1, far
    assert far["f_star_lower"] <= f_star + 1e-9, (far, f_star)
    assert close(far["gap"], far["f"] - f_star, 1e-6), (far, f_star)


def test_glmix_objective():
    rng = np.random.default_rng(4)
    n, dg, du, e = 500, 5, 3, 7
    xg, xu = rng.normal(size=(n, dg)), rng.normal(size=(n, du))
    lanes = rng.integers(-1, e, n)
    y = (rng.random(n) < 0.4).astype(np.float64)
    w, table = rng.normal(size=dg), rng.normal(size=(e, du))
    z = xg @ w + np.array([xu[i] @ table[l] if l >= 0 else 0.0
                           for i, l in enumerate(lanes)])
    plain = (np.sum(np.log1p(np.exp(z)) - y * z) + 0.5 * 2.0 * w @ w
             + 0.5 * 3.0 * np.sum(table ** 2))
    got = reference.glmix_objective(xg, xu, lanes, y, w, table, 2.0, 3.0)
    assert close(got, plain, 1e-10), (got, plain)
    assert reference.same_to([1.0, 2.0], [1.0, 2.0 + 1e-7], 1e-6)
    assert not reference.same_to([1.0, 2.0], [1.0, 2.1], 1e-6)
    assert not reference.same_to([1.0, np.nan], [1.0, 2.0], 1e-6)


def test_costs():
    assert costs.value_grad_pass_bytes(10, 4, 4) == 10 * 5 * 4
    peak = load_json(os.path.join(HERE, "peaks.json"))["TPU v5 lite"]
    assert close(costs.roofline_seconds(819e9, 1.0, peak), 1.0)


def test_benchmark_json():
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}, set(spec)
    assert isinstance(spec["run_seconds"], int) and 1 <= spec["run_seconds"] <= 51
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names)), "two metrics share a name"
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher"), m
    for m in spec["end_to_end"]:
        assert 0 < m["bound"] <= 0.1, m
        assert m["source"] in ("host_clock", "device_trace"), m
    configs = {c["name"]: c for c in spec["configs"]}
    cells = {w["name"]: w for w in spec["workloads"]}
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(
        1, len(cells) // 4)
    assert {w["config"] for w in cells.values()} == set(configs)
    under = tuple(p.rstrip("/") + "/" for p in spec["paths"])
    for c in configs.values():
        assert NAME.match(c["name"]) and c["file"].startswith(under), c
        body = load_json(os.path.join(ROOT, c["file"]))
        assert sorted(body["reduced"]) == sorted(c["reduced"]), c["name"]
        for key in c["reduced"]:
            assert NAME.match(key) and key in body, (c["name"], key)
        for key, value in body.get("published", {}).items():
            if key not in c["reduced"]:
                assert body[key] == value, (c["name"], key)
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200, c["name"]
    readers = {}
    for path in glob.glob(os.path.join(HERE, "layer_metrics", "*.py")):
        module = load_module("layer_metrics", os.path.basename(path)[:-3])
        assert os.path.basename(path)[:-3] == module.META["name"], path
        readers[module.META["name"]] = module.META
    for name, w in cells.items():
        assert NAME.match(name) and NAME.match(w["traffic"]), w
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200, w
        body = load_json(os.path.join(ROOT, configs[w["config"]]["file"]))
        traffic = load_json(os.path.join(HERE, "traffic",
                                         w["traffic"] + ".json"))
        driver = load_module("drivers", traffic["driver"])
        assert driver.ROLE in body["builders"], (name, driver.ROLE)
        assert os.path.exists(os.path.join(
            HERE, "builders", body["builders"][driver.ROLE] + ".py")), name
        reported = {m["name"] for m in metrics_of(spec, "end_to_end", name)}
        assert "setup_s" in reported and len(reported) >= 2, name
        layer = metrics_of(spec, "per_layer", name)
        assert layer, f"{name} reports no per-layer metric"
        for m in layer:
            assert m["moves"] in reported, (name, m["name"], m["moves"])
    for m in spec["per_layer"]:
        meta = readers.get(m["name"])
        assert meta, f"no reader benchmark/layer_metrics/{m['name']}.py"
        for key in ("unit", "layer", "moves"):
            assert meta[key] == m[key], (m["name"], key)
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock"), m
        for cell in m.get("workloads", []):
            assert cell in cells, (m["name"], cell)
    for path in glob.glob(os.path.join(HERE, "**", "*"), recursive=True):
        rel = os.path.relpath(path, ROOT)
        if "__pycache__" in rel or rel.startswith(os.path.join(
                "benchmark", "out")):
            continue
        assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def main():
    tests = [v for k, v in sorted(globals().items())
             if k.startswith("test_") and callable(v)]
    for test in tests:
        test()
        print("ok", test.__name__)
    print(f"{len(tests)} checks passed")


if __name__ == "__main__":
    main()
