"""The benchmark's reading of the per-entity solve's own lock-step counts
(ISSUE 37): `benchmark/lockstep_reduce.py` on hand-made (name, start, end,
stats) events, and its three per-layer metrics against BENCHMARK.json. No
JAX: the one function that reads a trace is not called here."""
import json
import os

import pytest

from benchmark import lockstep_reduce as lr
from benchmark.run import load_module

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NEW_METRICS = ["re_trips.fit", "re_lane_occupancy.fit",
               "re_ended_trial_share.fit"]
GAME_CELLS = ["glmix-ml20m.fit", "glmix-ml20m-user-item.fit",
              "game-ml20m-mf.fit"]


def _run(trips, lane_iterations, lanes, lockstep, running, **extra):
    return dict(coordinate="perUser", visit=0, run=0, entities=lanes,
                samples=8, lanes=lanes, trips=trips,
                lane_iterations=lane_iterations, lockstep_trials=lockstep,
                running_trials=running, data_passes=trips + 2, **extra)


def _events():
    """Two fits, [0, 10] and [10, 20], two runs each, and one run outside
    both (a fit after tracing stopped would have none; a stray event must
    not count either)."""
    return [
        ("bench/fit", 0.0, 10.0, {}),
        (lr.EVENT, 2.0, 2.0, _run(10, 30, 4, 40, 20)),
        (lr.EVENT, 3.0, 3.0, _run(20, 40, 4, 60, 30)),
        ("bench/fit", 10.0, 20.0, {}),
        (lr.EVENT, 12.0, 12.0, _run(10, 20, 4, 40, 40)),
        (lr.EVENT, 13.0, 13.0, _run(10, 20, 4, 40, 40)),
        (lr.EVENT, 25.0, 25.0, _run(99, 99, 1, 99, 1)),
    ]


def test_events_are_placed_in_the_fit_that_holds_them():
    fits = lr.per_fit(_events())
    assert [len(runs) for runs in fits] == [2, 2]
    assert [lr.trips(runs) for runs in fits] == [30, 20]
    # 70 lane iterations in 4 x 10 + 4 x 20 lane-trips; 40 in 80
    assert lr.lane_occupancy(fits[0]) == pytest.approx(100 * 70 / 120)
    assert lr.lane_occupancy(fits[1]) == pytest.approx(50.0)
    assert lr.ended_trial_share(fits[0]) == pytest.approx(50.0)
    assert lr.ended_trial_share(fits[1]) == pytest.approx(0.0)


def test_the_median_over_fits_and_an_event_outside_every_fit(monkeypatch):
    events = _events()
    monkeypatch.setattr(lr, "read_events", lambda path: events)
    lr._fits.cache_clear()
    record = {"trace": {"path": "a.xplane.pb"}, "cell": {"name": "c"}}
    assert lr.median_per_fit(record, lr.trips) == 25
    assert lr.median_per_fit(record, lr.ended_trial_share) == \
        pytest.approx(25.0)
    # the stray event alone, in no fit: nothing to read
    events[:] = [ev for ev in events if ev[0] != "bench/fit"]
    lr._fits.cache_clear()
    assert lr.per_fit(events) == []
    assert lr.median_per_fit(record, lr.trips) is None
    lr._fits.cache_clear()


def test_a_commit_without_the_annotation_reads_as_nothing(monkeypatch):
    """The parent's trace: the fits are there, the events are not, so every
    reader gives None and none raises (the driver runs these files over
    the parent)."""
    events = [ev for ev in _events() if ev[0] == "bench/fit"]
    monkeypatch.setattr(lr, "read_events", lambda path: events)
    lr._fits.cache_clear()
    record = {"trace": {"path": "b.xplane.pb"}, "cell": {"name": "c"}}
    assert lr.per_fit(events) == [[], []]
    for name in NEW_METRICS:
        assert load_module("layer_metrics", name).read(record) is None
    lr._fits.cache_clear()


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_per_layer_entry_resolves_to_its_reader(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    (entry,) = [m for m in spec["per_layer"] if m["name"] == name]
    meta = load_module("layer_metrics", name).META
    assert meta == {k: entry[k] for k in ("name", "unit", "layer", "moves")}
    assert entry["source"] == "program_counter"
    assert entry["workloads"][:3] == GAME_CELLS
    # appended after every metric the benchmark had
    assert [m["name"] for m in spec["per_layer"]].index(name) >= 22
