"""The reduction from trace to numbers, on the small recorded trace kept
beside this file, against answers worked by hand."""

import json
import os

import pytest

from benchmarks.harness import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def reduced():
    doc = json.load(open(os.path.join(HERE, "recorded_trace.json")))
    trace = {"devices": {d: [tuple(e) for e in evs]
                         for d, evs in doc["devices"].items()},
             "spans": [tuple(s) for s in doc["spans"]]}
    return tr.reduce_trace(trace)


# device 0 busy: [100,250] [300,400] [500,600] [950,1000] = 400 ns
# device 1 busy: [100,300] [400,500] [600,700]            = 400 ns
# exposed collective: dev 0 all-reduce [340,400] less compute [300,350] -> 50;
#                     dev 1 all-reduce [400,500], no compute under it -> 100
@pytest.mark.parametrize("key, want", [
    ("window_s", 1000e-9),
    ("busy_s", 400e-9),
    ("devices", 2),
    ("exposed_collective_s", 75e-9),
])
def test_window_busy_and_exposed_collectives(reduced, key, want):
    assert reduced[key] == pytest.approx(want)


def test_idle_share(reduced):
    assert 1 - reduced["busy_s"] / reduced["window_s"] == pytest.approx(0.6)


@pytest.mark.parametrize("pattern, want_ns", [
    (r"sparse_tables/apply", 100.0),                  # 100 on each device
    (r"sparse_tables/dedup_gather", (150 + 200) / 2),  # overlapping ops count once
    (r"sparse_tables/|sort|gather|scatter", (250 + 300) / 2),
    (r"no_such_scope", 0.0),
])
def test_scope_sums(reduced, pattern, want_ns):
    assert tr.scope_seconds(reduced, pattern) == pytest.approx(want_ns * 1e-9)


def test_top_ops_are_ranked_and_clipped_to_the_window(reduced):
    ops = dict(reduced["device_ops"])
    # fusion.9 runs 950..1050: only its 50 ns inside the window count
    assert ops["fusion.9__jit_step_/outside"] == pytest.approx(25e-9)
    times = [t for _, t in reduced["device_ops"]]
    assert times == sorted(times, reverse=True) and len(times) <= 10


def test_idle_gaps_are_named_by_the_host_span(reduced):
    gaps = dict(reduced["idle_gaps"])
    # device 0 idle: [0,100] [250,300] [400,500] [600,950]
    assert gaps["final_sync"] == pytest.approx(250e-9)   # 700..950
    assert gaps["next_batch"] == pytest.approx((90 + 15) * 1e-9)
    # train_step: 90..100, 250..300, 400..480, 495..500, 600..700
    assert gaps["train_step"] == pytest.approx(245e-9)
    assert sum(gaps.values()) == pytest.approx(600e-9)


@pytest.mark.parametrize("a, b, want", [
    ([(0, 10)], [(2, 4), (6, 8)], [(0, 2), (4, 6), (8, 10)]),
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 5), (7, 9)], [(4, 8)], [(0, 4), (8, 9)]),
    ([(0, 5)], [(0, 5)], []),
])
def test_interval_subtraction(a, b, want):
    assert tr.subtract(a, b) == want


def test_union_merges_touching_and_nested():
    assert tr.union([(5, 6), (0, 2), (1, 3), (3, 4), (10, 10)]) == [(0, 4), (5, 6)]


def test_a_trace_without_the_window_span_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_trace({"devices": {}, "spans": []})
