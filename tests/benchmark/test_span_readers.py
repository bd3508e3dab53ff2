"""The three ``program_span`` readers on hand-written rings: known durations
give known values; too few steps, a ring that lost its oldest records and one
step blocked inside ``trainer/exec`` are each handled as the files say."""

import pytest

from benchmarks.harness import manifest as mf
from benchmarks.harness import spans

from helpers import REPO

MAN = mf.Manifest(REPO)
READERS = ("train_host_busy_ms_per_step", "train_telemetry_ms_per_step",
           "ingest_produce_ms_per_batch")
MS = 1_000_000


def step_tree(i, t0_ms, exec_ms=0.1, fetch_ms=0.0, lost=()):
    """One ``trainer/step`` tree as ``obs.trace.finished()`` gives it, in
    order of END: input 1 ms, exec, record 0.5 ms of its own around a
    health fetch, 0.25 ms of the step's own; ``lost`` names records the
    ring has dropped."""
    def rec(name, sid, start, end, parent=None):
        r = {"kind": "span", "name": name, "span": f"{sid}{i:04d}",
             "trace": f"t{i}", "start_ns": int(start * MS),
             "end_ns": int(end * MS), "dur_s": (end - start) / 1e3,
             "ts": start / 1e3, "tid": 1, "pid": 1}
        if parent:
            r["parent"] = f"{parent}{i:04d}"
        return r

    t = t0_ms
    out = [rec("trainer/input", "in", t, t + 1.0, "st")]
    t += 1.0
    out.append(rec("trainer/exec", "ex", t, t + exec_ms, "st"))
    t += exec_ms
    r0 = t
    if fetch_ms:
        out.append(rec("health/observe", "ho", t + 0.1, t + 0.2, "re"))
        out.append(rec("trainer/health_fetch", "hf", t + 0.25,
                       t + 0.25 + fetch_ms, "re"))
    t += 0.5 + fetch_ms
    out.append(rec("trainer/record", "re", r0, t, "st"))
    out.append(rec("trainer/step", "st", t0_ms, t + 0.25))
    return [r for r in out if r["name"] not in lost]


def produce(i, t0_ms, ms):
    return {"kind": "span", "name": "ingest/produce", "span": f"pr{i:04d}",
            "trace": f"p{i}", "start_ns": int(t0_ms * MS),
            "end_ns": int((t0_ms + ms) * MS), "tid": 2, "pid": 1}


def ring_of(n_steps, first=0, produce_ms=2.0, **kw):
    ring = []
    for i in range(first, first + n_steps):
        ring += step_tree(i, 100.0 * i, **kw)
        ring.append(produce(i, 100.0 * i, produce_ms + (i % 2)))  # 2, 3, 2, 3 ms
    return ring


# a sound step: 1 + 0.1 + 0.5 + 0.25 ms of the host's own
SOUND = {"train_host_busy_ms_per_step": 1.85,
         "train_telemetry_ms_per_step": 0.5,
         "ingest_produce_ms_per_batch": 2.5}


def read(name, ring, steps, monkeypatch):
    monkeypatch.setattr(spans, "ring", lambda: ring)
    return MAN.metric_reader(name).read({"steps": steps})


@pytest.mark.parametrize("name", READERS)
def test_known_durations_give_the_known_value(name, monkeypatch):
    assert read(name, ring_of(12), 12, monkeypatch) == pytest.approx(SOUND[name])


@pytest.mark.parametrize("name", READERS)
def test_the_health_fetch_is_the_devices_time_and_not_the_hosts(name, monkeypatch):
    ring = ring_of(12, fetch_ms=40.0)
    assert read(name, ring, 12, monkeypatch) == pytest.approx(SOUND[name])


@pytest.mark.parametrize("name", READERS)
def test_nine_samples_are_too_few(name, monkeypatch):
    assert read(name, ring_of(9), 9, monkeypatch) is None
    assert read(name, [], 100, monkeypatch) is None


@pytest.mark.parametrize("name", READERS)
def test_only_the_windows_last_steps_are_read(name, monkeypatch):
    # an earlier run of the process left 5 slow steps in the ring
    earlier = ring_of(5, exec_ms=50.0, produce_ms=80.0)
    assert read(name, earlier + ring_of(12, first=5), 12, monkeypatch) == \
        pytest.approx(SOUND[name])


@pytest.mark.parametrize("name", READERS[:2])
def test_a_ring_that_lost_its_oldest_records_reads_the_whole_steps_left(
        name, monkeypatch):
    # the ring dropped from its old end: step 0 whole, step 1 less its
    # first-ended child; the window had 14 steps, 12 are whole
    ring = (step_tree(0, 0.0, exec_ms=70.0, lost=("trainer/input", "trainer/exec",
                                                  "trainer/record"))
            + step_tree(1, 100.0, exec_ms=70.0, lost=("trainer/input",)))
    for i in range(2, 14):
        ring += step_tree(i, 100.0 * i)
    assert read(name, ring, 14, monkeypatch) == pytest.approx(SOUND[name])


def test_a_step_blocked_inside_exec_does_not_move_the_median(monkeypatch):
    ring = []
    for i in range(12):
        ring += step_tree(i, 200.0 * i, exec_ms=90.0 if i == 5 else 0.1)
    got = read("train_host_busy_ms_per_step", ring, 12, monkeypatch)
    assert got == pytest.approx(SOUND["train_host_busy_ms_per_step"])
    # the telemetry's mean does not see exec at all
    assert read("train_telemetry_ms_per_step", ring, 12, monkeypatch) == \
        pytest.approx(0.5)


def test_the_ring_read_is_the_programs_and_a_program_without_one_reads_nothing(
        monkeypatch):
    import sys

    from lightctr_tpu import obs
    from lightctr_tpu.obs import trace

    trace.configure()
    with obs.override(True), trace.override_rate(1.0):
        with trace.span("trainer/step"):
            pass
    assert [r["name"] for r in spans.ring()] == ["trainer/step"]
    trace.configure()
    monkeypatch.setitem(sys.modules, "lightctr_tpu.obs.trace", None)
    assert spans.ring() == []


def test_the_manifest_holds_with_the_three_entries():
    assert mf.validate(MAN) == []
    entries = {m["name"]: m for m in MAN.doc["per_layer"]}
    for name in READERS:
        assert entries[name]["source"] == "program_span"
        assert entries[name]["workloads"] == [w["name"] for w in MAN.doc["workloads"]]
    assert [n for n in entries if n in READERS] == list(READERS)
