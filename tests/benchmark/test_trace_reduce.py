"""The reduction from trace to numbers, on the small recorded trace kept
beside this file, against answers worked by hand."""

import json
import os

import pytest

from benchmarks.harness import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


def recorded(keep=lambda ev: True, more_spans=()):
    """The recorded trace, its device events filtered, host spans added."""
    doc = json.load(open(os.path.join(HERE, "recorded_trace.json")))
    return {"devices": {d: [tuple(e) for e in evs if keep(tuple(e))]
                        for d, evs in doc["devices"].items()},
            "spans": sorted([tuple(s) for s in doc["spans"]] + list(more_spans),
                            key=lambda sp: sp[1])}


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_trace(recorded())


# device 0 busy: [100,250] [300,400] [500,600] [950,1000] = 400 ns
# device 1 busy: [100,300] [400,500] [600,700]            = 400 ns
# collectives: dev 0 all-reduce [340,400] = 60, dev 1 all-reduce [400,500] = 100
# exposed collective: dev 0 all-reduce [340,400] less compute [300,350] -> 50;
#                     dev 1 all-reduce [400,500], no compute under it -> 100
@pytest.mark.parametrize("key, want", [
    ("window_s", 1000e-9),
    ("busy_s", 400e-9),
    ("devices", 2),
    ("collective_s", 80e-9),
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


# the program's spans on the driving thread, beside the benchmark's own:
# ingest/get_wait 0..80 inside next_batch 0..90; trainer/step 90..470 (shorter
# than train_step 90..480, so the innermost) with trainer/health_fetch 250..300
PROGRAM_SPANS = [("ingest/get_wait", 0, 80), ("trainer/step", 90, 470),
                 ("trainer/health_fetch", 250, 300)]


@pytest.mark.parametrize("label, want_ns", [
    ("ingest/get_wait", 80),         # gap 0..100: 0..80
    ("next_batch", 10 + 15),         # 80..90, 480..495
    ("trainer/step", 10 + 70),       # 90..100, 400..470
    ("trainer/health_fetch", 50),    # gap 250..300, whole
    ("train_step", 10 + 5 + 100),    # 470..480, 495..500, 600..700
    ("final_sync", 250),
])
def test_idle_gaps_are_named_by_the_programs_spans_too(label, want_ns):
    gaps = dict(tr.reduce_trace(recorded(more_spans=PROGRAM_SPANS))["idle_gaps"])
    assert gaps[label] == pytest.approx(want_ns * 1e-9)
    assert sum(gaps.values()) == pytest.approx(600e-9)


def test_load_xplane_keeps_the_driving_threads_spans_alone(monkeypatch):
    """Two Python threads in one host plane: the one that placed
    ``bench/window`` names the gaps; the ingest worker's spans, the scorer's
    and the profiler's own events are left out."""
    from benchmarks.harness import xplane

    driver = [("bench/window", {}, 0.0, 1000.0), ("trainer/step", {}, 90.0, 380.0),
              ("ingest/get_wait", {}, 0.0, 80.0), ("serve/batch", {}, 5.0, 1.0),
              ("PjitFunction(step)", {}, 95.0, 3.0)]
    worker = [("ingest/produce", {}, 10.0, 300.0), ("ingest/put_wait", {}, 310.0, 50.0)]
    monkeypatch.setattr(xplane, "read_planes", lambda *a, **kw: {
        "/host:CPU": {"python3": driver, "python3#1": worker},
        "/device:TPU:0": {"XLA Ops": [("fusion.1", {"tf_op": "jit(step)/dot:"}, 100.0, 50.0)]}})
    trace = tr.load_xplane("unused.xplane.pb")
    assert trace["spans"] == [("bench/window", 0.0, 1000.0), ("ingest/get_wait", 0.0, 80.0),
                              ("trainer/step", 90.0, 470.0)]
    assert trace["devices"] == {"/device:TPU:0": [("fusion.1", "jit(step)/dot", 100.0, 50.0)]}


def exposed_reader():
    from benchmarks.harness.manifest import Manifest

    repo = os.path.dirname(os.path.dirname(HERE))
    return Manifest(repo).metric_reader("train_exposed_collective_ms_per_step")


@pytest.mark.parametrize("trace, steps, want_ms", [
    (recorded(), 3, 1e3 * 75e-9 / 3),                   # exposed 75 ns over 3 steps
    (recorded(), 0, None),                              # no step, no reading
    (recorded(keep=lambda ev: not tr.is_collective(ev)), 3, None),   # one chip's step
    (None, 3, None),                                    # an untraced run
], ids=["collectives", "no_steps", "no_collectives", "no_trace"])
def test_exposed_collective_reader(trace, steps, want_ms):
    reduced = tr.reduce_trace(trace) if trace is not None else None
    got = exposed_reader().read({"reduced": reduced, "steps": steps})
    assert got == (pytest.approx(want_ms) if want_ms is not None else None)


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
