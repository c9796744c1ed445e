"""The trace reduction: on hand-made events, and on a small trace recorded
on the chip (`data/trace_cut.json`, cut by tools/trace_dump.py)."""

import json
import os

import pytest

import xtrace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "trace_cut.json")


def test_leaves_drop_containers():
    evs = [("while", 0.0, 10.0), ("fusion.1", 1.0, 2.0),
           ("custom-call.2", 4.0, 3.0), ("inner", 4.5, 1.0),
           ("after", 12.0, 1.0)]
    assert [e[0] for e in xtrace.leaves(evs)] == ["fusion.1", "inner",
                                                   "after"]


def test_union_and_gaps():
    evs = [("a", 1.0, 2.0), ("b", 2.5, 1.0), ("c", 6.0, 1.0)]
    busy, gaps = xtrace.union_and_gaps(evs, 0.0, 8.0)
    assert busy == pytest.approx(3.5)
    assert gaps == [(0.0, 1.0), (3.5, 2.5), (7.0, 1.0)]


def test_reduce_counts_mosaic_apart_and_names_the_gap():
    trace = {"device": {"/device:TPU:0": [
                 ("while", 1.0, 9.0), ("fusion.1", 1.0, 2.0),
                 ("custom-call.7", 3.0, 4.0), ("sort.3", 8.0, 2.0)]},
             "host": [("bench:train", 0.0, 20.0), ("bench:stamp", 0.0, 0.9),
                      (xtrace.SLICE_START, 0.5, 0.0),
                      (xtrace.SLICE_STOP, 9.5, 0.0)],
             "mosaic": ["custom-call.7"]}
    r = xtrace.reduce(trace)
    assert r["window_s"] == pytest.approx(9.0)      # 0.5 .. 9.5
    assert r["busy_s"] == pytest.approx(2.0 + 4.0 + 1.5)
    assert r["mosaic_s"] == pytest.approx(4.0)
    assert r["other_s"] == pytest.approx(4.0)
    assert r["longest_gap_s"] == pytest.approx(1.0)     # 7.0 .. 8.0
    assert r["device_ops"][0] == ["custom-call.7", 4.0]
    assert r["idle_gaps"][0][0] == "bench:train"
    assert r["idle_gaps"][1] == ["bench:stamp", pytest.approx(0.5)]


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        xtrace.reduce({"device": {}, "host": [], "mosaic": []})


@pytest.mark.skipif(not os.path.exists(DATA),
                    reason="no recorded trace in this checkout")
def test_recorded_trace():
    trace = json.load(open(DATA))
    trace["device"] = {p: [tuple(e) for e in evs]
                       for p, evs in trace["device"].items()}
    trace["host"] = [tuple(e) for e in trace["host"]]
    r = xtrace.reduce(trace)
    expect = json.load(open(DATA.replace(".json", ".expect.json")))
    assert 0 < r["busy_s"] <= r["window_s"]
    for key in ("busy_s", "window_s", "mosaic_s", "other_s",
                "longest_gap_s"):
        assert r[key] == pytest.approx(expect[key], rel=1e-9), key
    assert r["mosaic_s"] > 0 and r["other_s"] > 0
    assert [n for n, _ in r["device_ops"]] == \
        [n for n, _ in expect["device_ops"]]
