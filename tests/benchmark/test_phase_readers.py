"""The eight readers of the step's phases on the small hand-made trace kept
beside this file (``phase_trace.json``), against answers worked by hand."""

import json
import os

import pytest

from benchmarks.harness import manifest as mf
from benchmarks.harness import phases, spans
from benchmarks.harness import trace_reduce as tr

from helpers import REPO
from test_span_readers import ring_of

HERE = os.path.dirname(os.path.abspath(__file__))
MAN = mf.Manifest(REPO)
STEPS = 2
DEVICE_READERS = tuple(f"train_phase_{p}_ms_per_step" for p in (
    "dedup", "gather", "expand", "model", "update", "apply")) + (
    "train_unphased_device_share",)
READERS = DEVICE_READERS + ("train_input_ms_per_step",)


def reduced(rescope=lambda scope: scope):
    doc = json.load(open(os.path.join(HERE, "phase_trace.json")))
    return tr.reduce_trace({
        "devices": {d: [(n, rescope(sc), s, dur) for n, sc, s, dur in evs]
                    for d, evs in doc["devices"].items()},
        "spans": [tuple(s) for s in doc["spans"]]})


# nanoseconds in the window, device 0 | device 1:
# dedup   sort.1 100                                            | 140
# gather  cond.7 [200,320] has no name stack and encloses the   | fusion.8 60 + the join's
#         gather alone: the whole 120 is the gather's           | all-reduce.5 40 = 100
# expand  take 70 + its transpose's scatter-add 90 + the        | 60 + 100 = 160
#         all-reduce that transpose feeds 60 = 220              |
# model   the seq/kda/scan op, under none of the five 150       | forward 100 + backward 100
# update  40 + 40 (inside while.20)                             | 60
# apply   scatter-add 100 (inside while.20) + fusion.31 80      | cond.40 [760,900] encloses the
#         (its scope past the label's 120 characters) + the     | apply alone: 140
#         accumulator's gather 50 = 230                         |
# under no phase: while.20 [740,1000] encloses apply AND update,| copy.50, no name stack and
#         so what they leave of it, 10 + 10 + 100, and the      | nothing inside: 30
#         primitive called gather of another program 50 = 170   |
# busy    100 + 120 + 720 + 80 + 50 = 1070                      | [100,930] = 830
NS = {"dedup": (100, 140), "gather": (120, 100), "expand": (220, 160),
      "model": (150, 200), "update": (80, 60), "apply": (230, 140),
      "unphased": (170, 30), "busy": (1070, 830)}
WANT = {f"train_phase_{p}_ms_per_step": sum(NS[p]) / 2 / 1e6 / STEPS
        for p in phases.PHASES}
WANT["train_unphased_device_share"] = 100.0 * sum(NS["unphased"]) / sum(NS["busy"])
WANT["train_input_ms_per_step"] = 1.0      # test_span_readers.step_tree's input


def unscoped(scope):
    """The name stack of the same operation in a program that opens no
    scope: ``jit(step)/<primitive>``; a wrapper keeps none."""
    return "jit(step)/" + scope.rsplit("/", 1)[-1] if phases.phase_of(scope) else scope


@pytest.mark.parametrize("name", READERS)
def test_each_reader_gives_its_hand_worked_value(name, monkeypatch):
    # the span reader wants ten whole steps in the ring; the trace holds two
    monkeypatch.setattr(spans, "ring", lambda: ring_of(12))
    steps = 12 if name == "train_input_ms_per_step" else STEPS
    got = MAN.metric_reader(name).read({"reduced": reduced(), "steps": steps})
    assert got == pytest.approx(WANT[name])


def test_phases_are_disjoint_and_with_the_unphased_time_sum_to_the_busy_time():
    r = reduced()
    for dev, events in r["per_device"].items():
        by_phase = phases.device_phases(events)
        whole = tr.union(i for iv in by_phase.values() for i in iv)
        # no interval of one phase overlaps another's: the union is their sum
        assert tr.total(whole) == sum(tr.total(iv) for iv in by_phase.values())
    ns = phases.split_ns(r)
    assert {k: 2 * v for k, v in ns.items()} == {k: sum(v) for k, v in NS.items()}
    assert sum(ns[p] for p in phases.PHASES) + ns["unphased"] == ns["busy"] \
        == pytest.approx(r["busy_s"] * 1e9)


def test_a_label_cut_at_120_characters_does_not_change_a_phase():
    (label, scope, _, _), = [e for e in reduced()["per_device"]["/device:TPU:0"]
                             if e[0].startswith("fusion.31")]
    assert len(label) == 120 and "sparse_tables" not in label
    assert phases.phase_of(scope) == "apply"


@pytest.mark.parametrize("scope, want", [
    ("jit(step)/sparse_tables/dedup_gather/dedup_ids/sort", "dedup"),
    ("jit(step)/sparse_tables/dedup_gather/gather_rows/shard_map/psum", "gather"),
    ("jit(step)/jvp(model/expand)/jit(_take)/gather", "expand"),
    ("jit(step)/transpose(jvp(model/expand))/jit(_take)/scatter-add", "expand"),
    ("jit(local_step)/transpose(jvp(model/expand))/jit(_take)/scatter-add", "expand"),
    ("jit(step)/step/update/add", "update"),
    ("jit(step)/sparse_tables/apply/jit(_take)/gather", "apply"),
    # the model's pass opens no scope: what the step runs under none of the five
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/seq/kda/seq/kda/scan/while", "model"),
    ("jit(step)/jvp(jvp())/checkpoint/seq/moe/seq/moe/experts/dot_general", "model"),
    ("jit(step)/transpose(jvp())/dot_general", "model"),
    ("jit(step)/jvp(jit(model_zoo))/expand/gather", "model"),
    ("jit(step)/jvp(remodel/expand)/gather", "model"),
    # another program's, a wrapper's, a name that is no stack
    ("jit(evaluate)/sparse_tables/apply/gather", None),
    ("jit(_take)/gather", None),
    ("while.20", None),
    ("", None),
])
def test_the_phase_of_a_name_stack(scope, want):
    assert phases.phase_of(scope) == want


@pytest.mark.parametrize("name", DEVICE_READERS)
def test_a_program_without_the_scopes_reads_nothing(name):
    bare = reduced(unscoped)
    read = MAN.metric_reader(name).read
    assert read({"reduced": bare, "steps": STEPS}) is None
    assert read({"reduced": None, "steps": STEPS}) is None       # an untraced run
    # a CPU run: the trace holds no device plane
    assert read({"reduced": dict(bare, per_device={}), "steps": STEPS}) is None


@pytest.mark.parametrize("name", DEVICE_READERS)
def test_the_parent_commits_program_reads_nothing(name):
    """``sparse_tables/apply`` alone of the scopes: without one of the
    scopes that split it, "under none of the five" is no model's time."""
    only_apply = reduced(lambda scope: scope if "sparse_tables/apply" in scope
                         else unscoped(scope))
    assert MAN.metric_reader(name).read(
        {"reduced": only_apply, "steps": STEPS}) is None


def test_a_phase_the_program_lacks_is_left_out_and_the_others_are_read():
    """A step whose model calls no ``expand_rows``: its takes are the
    model's."""
    no_expand = reduced(lambda scope: scope.replace("model/expand", ""))
    ctx = {"reduced": no_expand, "steps": STEPS}
    assert MAN.metric_reader("train_phase_expand_ms_per_step").read(ctx) is None
    assert MAN.metric_reader("train_phase_model_ms_per_step").read(ctx) == \
        pytest.approx(sum(NS["model"] + NS["expand"]) / 2 / 1e6 / STEPS)
    assert MAN.metric_reader("train_phase_apply_ms_per_step").read(ctx) == \
        pytest.approx(WANT["train_phase_apply_ms_per_step"])
    assert MAN.metric_reader("train_unphased_device_share").read(ctx) == \
        pytest.approx(WANT["train_unphased_device_share"])


def test_no_step_no_reading():
    for name in DEVICE_READERS[:-1]:
        assert MAN.metric_reader(name).read({"reduced": reduced(), "steps": 0}) is None


def test_the_manifest_holds_with_the_eight_entries():
    assert mf.validate(MAN) == []
    entries = [m for m in MAN.doc["per_layer"] if m["name"] in READERS]
    assert sorted(m["name"] for m in entries) == sorted(READERS)
    for m in entries:
        assert m["workloads"] == [w["name"] for w in MAN.doc["workloads"]]
        assert m["moves"] == "train_examples_per_s_per_chip"
        assert m["source"] == ("program_span" if m["name"] == "train_input_ms_per_step"
                               else "device_trace")
