"""The benchmark's reading of the program's own tracing (ISSUE 25):
`benchmark/span_reduce.py` on hand-made intervals, and the per-layer metric
files against BENCHMARK.json. No JAX: the one function that reads a trace is
not called here."""
import glob
import json
import os

import pytest

from benchmark import span_reduce as sr
from benchmark.run import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PLANE = "/device:TPU:0"
NEW_METRICS = ["re_solve_device_s.fit", "fe_solve_device_s.fit",
               "other_device_s.fit", "idle_dispatch_s.fit",
               "idle_staging_s.fit", "trace_lower_s"]


def _metric_files():
    return sorted(os.path.basename(p)[:-3] for p in glob.glob(
        os.path.join(ROOT, "benchmark", "layer_metrics", "*.py")))


DISPATCH = ("re/dispatch", "re/solve_call", "fe/dispatch")
STAGING = ("build/coordinates", "fe/stage", "re/stage_static")


def _trace():
    """Two fits, [0, 10] and [10, 20]. Fit 0: the FE solve runs [1, 3] with
    an op-free hole [2, 2.5], the bucket solve [4, 8] in two runs, an eager
    op [8.5, 9]. Fit 1: the bucket solve [12, 19] in two runs, both called
    by 10.3 (the pipelined descent: the host runs ahead of the device)."""
    ops = [("fusion.1", 1, 2), ("fusion.2", 2.5, 3),
           ("while.1", 4, 6), ("fusion.3", 4.5, 5.5), ("while.1", 6, 8),
           ("copy.1", 8.5, 9), ("while.1", 12, 19)]
    modules = [("jit_fe_solve(111)", 1, 3),
               ("jit_re_bucket_solve(222)", 4, 6),
               ("jit_re_bucket_solve(333)", 6, 8),
               ("jit_copy(4)", 8.5, 9),
               ("jit_re_bucket_solve(222)", 12, 15),
               ("jit_re_bucket_solve(333)", 15, 19)]
    host = [("bench/fit", 0, 10), ("bench/fit", 10, 20),
            ("photon/build/coordinates", 0, 0.4),
            ("photon/0/fixed/solve", 0.4, 3.4),
            ("photon/fe/stage", 0.4, 0.6), ("photon/fe/dispatch", 0.6, 0.7),
            ("photon/0/perUser/solve", 3.4, 9.5),
            ("photon/re/solve_call", 3.4, 4.2),
            ("photon/re/dispatch", 3.9, 4.2),
            ("PjitFunction(re_bucket_solve)", 3.9, 4.2),
            ("photon/re/dispatch", 4.3, 4.4),
            ("photon/0/perUser/solve", 10, 19.5),
            ("photon/re/dispatch", 10.1, 10.2),
            ("photon/re/dispatch", 10.3, 14.9)]
    return {PLANE: {"XLA Ops": ops, "XLA Modules": modules}}, host


def test_program_seconds_strip_the_fingerprint_and_close():
    devices, host = _trace()
    first, second = sr.reduce_fits(devices, host, [(0, 10), (10, 20)])
    assert sr.program_name("jit_fe_solve(1234567890)") == "jit_fe_solve"
    assert first["programs"] == pytest.approx(
        {"jit_fe_solve": 1.5, "jit_re_bucket_solve": 4.0, "jit_copy": 0.5})
    assert second["programs"] == pytest.approx({"jit_re_bucket_solve": 7.0})
    assert first["program_runs"] == {"jit_fe_solve": 1, "jit_copy": 1,
                                     "jit_re_bucket_solve": 2}
    for fit in (first, second):     # closure: the programs are the busy time
        assert sum(fit["programs"].values()) == pytest.approx(fit["busy_s"])
        assert sr.closed(fit)
    assert first["busy_s"] == pytest.approx(6.0)
    assert first["idle_s"] == pytest.approx(4.0)
    assert sr.solve_seconds(first, sr.FE_SOLVE) == pytest.approx(1.5)
    assert sr.solve_seconds(second, sr.FE_SOLVE) is None
    assert sr.other_seconds(first) == pytest.approx(0.5)
    assert sr.other_seconds(second) == pytest.approx(0.0)


def test_ops_outside_every_program_are_still_counted():
    devices, host = _trace()
    devices[PLANE]["XLA Ops"].append(("stray", 9.2, 9.4))
    (fit,) = sr.reduce_fits(devices, host, [(0, 10)])
    assert fit["programs"][sr.NO_PROGRAM] == pytest.approx(0.2)
    assert sr.closed(fit)
    assert sr.other_seconds(fit) == pytest.approx(0.7)


def test_runs_that_overlap_do_not_close_and_read_as_nothing():
    """The three device sums are worth reading only where the programs add
    up to the busy seconds: a second run over [4, 8] counts its ops twice,
    and then every one of the three reads None, not a smaller number."""
    devices, host = _trace()
    devices[PLANE]["XLA Modules"].append(("jit_shadow(9)", 4, 8))
    (fit,) = sr.reduce_fits(devices, host, [(0, 10)])
    assert sum(fit["programs"].values()) == pytest.approx(fit["busy_s"] + 4)
    assert not sr.closed(fit)
    assert sr.solve_seconds(fit, sr.RE_SOLVE) is None
    assert sr.solve_seconds(fit, sr.FE_SOLVE) is None
    assert sr.other_seconds(fit) is None


def test_a_gap_is_named_by_what_the_device_waited_for():
    devices, host = _trace()
    first, second = sr.reduce_fits(devices, host, [(0, 10), (10, 20)])
    idle = first["idle"]
    # [0, 1] ends with the FE solve, called over [0.6, 0.7]: the host was
    # not there yet for 0.6 (its middle is in build/coordinates), was in
    # the call for 0.1, and for 0.3 the solve waited for its operands
    assert idle["photon/build/coordinates"] == pytest.approx(0.6)
    assert idle["call of jit_fe_solve"] == pytest.approx(0.1)
    assert idle["operands of jit_fe_solve"] == pytest.approx(0.3)
    # [2, 2.5] is a hole in the FE solve's own run
    assert idle["inside jit_fe_solve"] == pytest.approx(0.5)
    # [3, 4] ends with the first bucket solve, called over [3.9, 4.2]:
    # before that the innermost of perUser/solve > re/solve_call holds it
    assert idle["photon/re/solve_call"] == pytest.approx(0.9)
    assert idle["call of jit_re_bucket_solve"] == pytest.approx(0.1)
    # [8, 8.5] ends with an eager op and no solve follows: the host's span
    assert idle["photon/0/perUser/solve"] == pytest.approx(0.5)
    # [9, 10]: nothing follows and its middle is past every span
    assert idle[sr.NO_SPAN] == pytest.approx(1.0)
    assert sum(idle.values()) == pytest.approx(first["idle_s"])
    assert sr.idle_seconds(first, sr.CALL, DISPATCH) == pytest.approx(1.1)
    assert sr.idle_seconds(first, sr.OPERANDS, STAGING) == pytest.approx(0.9)
    # fit 1, [10, 12]: the first bucket solve was called by 10.2 and the
    # device waits 1.8 more for its operands, whatever the host is in by
    # then (the call of the SECOND bucket, [10.3, 14.9])
    assert second["idle"] == pytest.approx(
        {"photon/0/perUser/solve": 0.1, "call of jit_re_bucket_solve": 0.1,
         "operands of jit_re_bucket_solve": 1.8, sr.NO_SPAN: 1.0})


def test_calls_pair_with_runs_in_order_or_not_at_all():
    devices, host = _trace()
    runs = [("jit_re_bucket_solve", 12, 15), ("jit_re_bucket_solve", 15, 19)]
    spans = [ev for ev in host if ev[1] >= 10]
    assert sr.match_calls(runs, spans) == {0: (10.1, 10.2), 1: (10.3, 14.9)}
    # one call span short: nothing is paired and the host's span names it
    assert sr.match_calls(runs, spans[:-1]) == {}
    assert sr.idle_causes([(10, 12)], runs, spans[:-1]) == pytest.approx(
        {"photon/0/perUser/solve": 2.0})
    # a run cannot start before its call opens
    assert sr.match_calls([("jit_fe_solve", 0.5, 3)], host) == {}
    # of three nested spans the shortest wins
    spans = [("photon/a", 0, 10), ("photon/b", 2, 8), ("photon/c", 4, 6)]
    assert sr.innermost(spans, 5) == "photon/c"
    assert sr.innermost(spans, 7) == "photon/b"
    assert sr.innermost(spans, 9) == "photon/a"
    assert sr.innermost(spans, 11) is None
    cuts, names = sr.span_pieces(spans)
    assert cuts == [0, 2, 4, 6, 8, 10]
    assert names == ["photon/a", "photon/b", "photon/c", "photon/b",
                     "photon/a"]


def test_an_older_program_reads_as_nothing():
    """A trace of a commit without the names and spans: every new reading
    is None, none raises (the driver runs these files over the parent)."""
    devices, host = _trace()
    devices[PLANE]["XLA Modules"] = [
        (name.replace("re_bucket_solve", "solve_one")
         .replace("fe_solve", "_lambda_"), s, e)
        for name, s, e in devices[PLANE]["XLA Modules"]]
    host = [ev for ev in host if not ev[0].startswith("photon/")]
    (fit,) = sr.reduce_fits(devices, host, [(0, 10)])
    assert sr.RE_SOLVE not in fit["programs"]
    assert sr.solve_seconds(fit, sr.RE_SOLVE) is None
    assert sr.other_seconds(fit) is None
    assert sr.idle_seconds(fit, sr.CALL, DISPATCH) is None
    assert fit["idle"] == pytest.approx(
        {sr.NO_SPAN: 3.5, "inside jit__lambda_": 0.5})
    assert sr.reduce_fits({}, host, [(0, 10)]) == []


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_per_layer_entry_resolves_to_its_reader(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (entry,) = [m for m in spec["per_layer"] if m["name"] == name]
    meta = load_module("layer_metrics", name).META
    assert meta == {k: entry[k] for k in ("name", "unit", "layer", "moves")}
    # later cells are appended to the list, as benchmark/README.md prescribes
    assert entry["workloads"][0] == "glmix-ml20m.fit"
    assert entry["better"] == "lower"
    # appended, not put among the accepted four; later PRs' follow
    assert [m["name"] for m in spec["per_layer"][4:10]] == NEW_METRICS


@pytest.mark.parametrize("name", _metric_files())
def test_every_reader_returns_none_where_there_is_nothing_to_read(name):
    record = {"cell": {}, "config": {}, "traffic": {}, "built": {},
              "samples": {"fits": []}, "summary": {},
              "compile": {"setup_seconds": None, "setup_count": 0,
                          "window_count": 0},
              "trace": None, "peak": None}
    assert load_module("layer_metrics", name).read(record) is None
