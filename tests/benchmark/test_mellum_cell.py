"""The ``mellum-train-packed16k`` cell (ISSUE 40) and ``wd-train-tail``: their
manifest entries and data files, the configuration against the model catalog,
the work counts, the three new readers on a trace whose labels come from the
chip, and whole runs of the runner at toy sizes on the CPU with the timed path
broken underneath (``correct`` must come out false)."""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import helpers
from helpers import REPO

from benchmarks.harness import manifest as mf
from benchmarks.harness import seqgen

CELL = "mellum-train-packed16k"
CONFIG = "mellum2-12b-a2.5b-ep4"
MAN = mf.Manifest(REPO)
CFG = MAN.config(CONFIG)
TRAFFIC = MAN.traffic("train-packed16k-repo")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
READERS = ("train_gqa_device_share", "gqa_window_roofline", "gqa_full_roofline")


@pytest.fixture(scope="module")
def model():
    return MAN.model("mellum")


def test_the_new_entries_are_at_the_ends_of_their_lists():
    assert mf.validate(MAN) == []
    # each after the last entry its list had before (PR 39's), in one run
    names = [w["name"] for w in MAN.doc["workloads"]]
    at = names.index("fm-train-zipf") + 1
    assert names[at:at + 2] == [CELL, "wd-train-tail"]
    configs = [c["name"] for c in MAN.doc["configs"]]
    assert configs[configs.index("kimi-linear-48b-a3b-ep16") + 1] == CONFIG
    metrics = [m["name"] for m in MAN.doc["per_layer"]]
    at = metrics.index("train_input_ms_per_step") + 1
    assert metrics[at:at + 3] == list(READERS)
    cell = MAN.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "train-packed16k-repo", 1)
    tail = MAN.workload("wd-train-tail")
    assert (tail["config"], tail["traffic"], tail["chips"]) == ("criteo-widedeep", "train-tail", 1)
    per_layer = {m["name"] for m in MAN.metrics_for(CELL, "per_layer")}
    assert set(READERS) | {"train_moe_device_share", "moe_experts_roofline",
                           "moe_expert_load_max_over_mean", "train_step_mfu_share"} <= per_layer
    assert not per_layer & {"train_kda_device_share", "kda_scan_roofline"}
    assert not {m["name"] for m in MAN.metrics_for("wd-train-tail", "per_layer")} & set(READERS)


# Three older tests assert the benchmark as it stood before this PR (five cells;
# PR 38's readers last; traffic kind ``train``), and a PR that adds cells may not
# edit them.  These hold the same things for the seven cells.
PHASE_READERS = tuple(f"train_phase_{p}_ms_per_step" for p in (
    "dedup", "gather", "expand", "model", "update", "apply")) + (
    "train_unphased_device_share", "train_input_ms_per_step")


def test_the_benchmark_holds_seven_cells_one_on_four_chips():
    assert [w["name"] for w in MAN.doc["workloads"]] == [
        "wd-train-zipf", "wd-x4-train-zipf", "fm-train-tail", "kimilinear-train-packed8k",
        "fm-train-zipf", CELL, "wd-train-tail"]
    assert sum(w["chips"] == 4 for w in MAN.doc["workloads"]) == 1


def test_pr38s_phase_readers_stay_where_they_were_appended():
    metrics = [m["name"] for m in MAN.doc["per_layer"]]
    assert metrics[17:25] == list(PHASE_READERS)
    assert metrics[25:] == list(READERS)
    for m in MAN.doc["per_layer"][17:25]:
        assert m["workloads"] == [w["name"] for w in MAN.doc["workloads"]]


@pytest.mark.parametrize("cell", MAN.doc["workloads"], ids=lambda w: w["name"])
def test_every_cells_files_load(cell):
    traffic = MAN.traffic(cell["traffic"])
    assert traffic["kind"] in ("train", "train_seq")
    assert os.path.isfile(os.path.join(REPO, "benchmarks", "harness",
                                       traffic["kind"] + "_cell.py"))
    limits = MAN.cell_file(cell["name"])["limits"]
    assert limits and all(v >= 0 for v in limits.values())
    if traffic["kind"] == "train":
        assert traffic["rows"]["distinct_batches"] >= 64
    else:
        assert helpers.whole_passes(MAN.runner("train_seq"),
                                    traffic["rows"]["distinct_batches"])


def test_the_configuration_is_the_catalogs_at_published_widths():
    entry = MAN.config_entry(CONFIG)
    assert sorted(CFG["reduced"]) == sorted(entry["reduced"]) == sorted(
        ["num_hidden_layers", "num_experts", "vocab_size", "replay_batches"])
    assert (CFG["num_hidden_layers"], CFG["num_experts"], CFG["vocab_size"]) == (4, 16, 24576)
    assert CFG["experts_routed_over"] == 64 and CFG["first_expert_held"] == 0
    assert CFG["hidden"] == CFG["hidden_size"] == 2304
    assert CFG["dim"] == CFG["head_dim"] == 128
    assert CFG["vocab"] == CFG["vocab_size"] == 98304 // 4
    assert CFG["batch"] == 16384 and CFG["sequences"] == 1
    assert CFG["layer_types"][:4] == ["sliding_attention"] * 3 + ["full_attention"]
    if not os.path.isfile(CATALOG):
        pytest.skip("the model catalog is not on this machine")
    row = next(json.loads(ln) for ln in open(CATALOG)
               if '"Mellum2-12B-A2.5B-Instruct"' in ln)
    assert entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key


def test_parameter_counts_are_the_issues(model):
    b = CFG["bytes"]
    assert b["layer_outside_experts_parameters"] == 21_385_728
    assert b["expert_parameters"] == 6_193_152
    assert b["layer_parameters"] == 120_476_160
    assert b["parameters"] == 595_153_152
    assert model.state_bytes(CFG, True) == b["training_state"] == 8 * 595_153_152
    z = model.sizes(CFG)
    assert (z["heads"], z["kv_heads"], z["dim"], z["window"]) == (32, 4, 128, 1024)
    assert (z["n_experts"], z["held"], z["top_k"], z["expert_width"]) == (64, 16, 8, 896)


def test_the_traffic_files_counts_are_the_generators(model):
    counted = seqgen.counted(TRAFFIC["rows"], tokens=16384, vocab=CFG["vocab"], sequences=1)
    want = TRAFFIC["counted"]
    for key in ("distinct_rows_per_step", "documents_per_step", "attended_pairs_per_step"):
        assert counted[key] == pytest.approx(want[key], rel=1e-9)
    assert model.windowed_pairs(CFG, TRAFFIC["rows"]) == pytest.approx(
        want["windowed_pairs_per_step"], rel=1e-9)
    assert 2.5 < counted["documents_per_step"] < 3.5
    assert 60e6 < counted["attended_pairs_per_step"] < 80e6


@pytest.mark.parametrize("length", [1, 7, 8, 9, 30])
def test_the_windowed_count_is_the_pairs_inside_the_mask(model, length):
    i, j = np.arange(length)[:, None], np.arange(length)[None]
    assert model.window_pairs_of(length, 8) == int(np.sum((j <= i) & (i - j < 8)))


def test_the_windowed_count_is_the_programs(model):
    """The benchmark's count over a toy traffic against the program's own
    count off the segment ids (``nn/gqa.attended_pairs``)."""
    import jax.numpy as jnp

    from lightctr_tpu.nn import gqa

    cfg = dict(CFG, batch=512, sliding_window=32)
    spec = dict(TRAFFIC["rows"], median_tokens=40, min_tokens=4, max_tokens=512,
                distinct_batches=4)
    seqs = seqgen.packed_sequences(
        np.random.default_rng(seqgen.datagen.seed_words(spec["data_seed"])), 4, spec,
        tokens=512, vocab=CFG["vocab"])
    program = sum(int(gqa.attended_pairs(jnp.asarray(s), 32)) for s in seqs["segments"])
    assert model.windowed_pairs(cfg, spec) == program / 4


def test_cost_parts_and_the_window_bound(model):
    c = model.train_step_cost(CFG, 4654.0, 4 * 16384 * 8 * 16 / 64, 69.73e6)
    assert 24e12 < c["flops"] < 25.5e12                     # ISSUE 40: ~24.6 TF
    assert c["moe_experts_flops"] / c["flops"] == pytest.approx(0.197, abs=0.005)
    exact = model.gqa_core_cost(CFG, TRAFFIC["counted"]["windowed_pairs_per_step"])
    bound = model.gqa_core_cost(CFG, 16384 * 1024)
    assert 1.0 < bound["flops"] / exact["flops"] < 1.11       # the upper bound's slack
    assert c["gqa_window_flops_per_pair"] * TRAFFIC["counted"]["windowed_pairs_per_step"] \
        == pytest.approx(3 * exact["flops"])
    assert c["gqa_window_bytes"] == pytest.approx(3 * exact["bytes"])
    # FLOP-bound cores: the roofline reads FLOPs
    assert c["gqa_full_flops"] / 197e12 > c["gqa_full_bytes"] / 819e9
    assert exact["flops"] / 197e12 > exact["bytes"] / 819e9


def test_the_weights_come_from_the_config_and_not_from_the_seed(model):
    """The routing the weights make sets the step's time, so the cell draws
    them from ``weight_seed``; ``--seed`` (the key) picks the order alone."""
    import jax
    import numpy as np

    small = dict(CFG, hidden=16, dim=8, vocab=64, moe_intermediate_size=8, num_experts=2,
                 num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=1)
    leaves = lambda cfg, k: jax.tree_util.tree_leaves(model.init_params(cfg, jax.random.PRNGKey(k)))
    assert all(np.array_equal(a, b) for a, b in zip(leaves(small, 1), leaves(small, 2)))
    other = dict(small, weight_seed=small["weight_seed"] + 1)
    assert not np.array_equal(leaves(small, 1)[0], leaves(other, 1)[0])


# -- the readers, on the chip's labels ------------------------------------------


TRACE = json.load(open(os.path.join(os.path.dirname(__file__), "mellum_trace.json")))


def reduced_of(events):
    """A reduced trace of one device holding ``events`` (label, scope,
    start ns, duration ns), as ``trace_reduce.reduce_trace`` keeps them."""
    from benchmarks.harness import trace_reduce

    evs = [(trace_reduce.op_label(n, s), s, t, d) for n, s, t, d in events]
    busy = trace_reduce.total(trace_reduce.union((t, t + d) for _, _, t, d in evs))
    return {"per_device": {"/device:TPU:0": evs}, "busy_s": busy / 1e9}


def test_each_new_scope_falls_inside_the_first_120_characters_of_its_label():
    from benchmarks.harness import trace_reduce

    seen = {p: 0 for p in ("seq/gqa/window", "seq/gqa/full", "seq/gqa", "seq/moe")}
    for name, scope in TRACE["ops"]:
        label = trace_reduce.op_label(name, scope)
        for pattern in seen:
            if pattern in scope:
                assert re.search(pattern, label), (pattern, label)
                seen[pattern] += 1
    assert all(seen.values()), seen


def test_the_three_readers_on_a_recorded_trace(model):
    """Each recorded op laid out for 1 ms, one after another, on two steps:
    the share and the two rooflines are what the op counts give."""
    events, t = [], 0.0
    for name, scope in TRACE["ops"] * 2:
        events.append((name, scope, t, 1e6))
        t += 1e6
    red = reduced_of(events)
    steps = 2
    cost = model.train_step_cost(CFG, 4654.0, 16384.0, TRAFFIC["counted"]["attended_pairs_per_step"])
    ctx = {"reduced": red, "steps": steps, "cost": cost, "cfg": CFG, "traffic": TRAFFIC,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    n = {p: sum(p in s for _, s in TRACE["ops"]) for p in ("seq/gqa/window", "seq/gqa/full", "seq/gqa")}
    read = {m: MAN.metric_reader(m).read(ctx) for m in READERS}
    assert read["train_gqa_device_share"] == pytest.approx(
        100.0 * n["seq/gqa"] / len(TRACE["ops"]))
    window = model.gqa_core_cost(CFG, TRAFFIC["counted"]["windowed_pairs_per_step"])
    assert read["gqa_window_roofline"] == pytest.approx(
        100.0 * 3 * window["flops"] / 197e12 / (n["seq/gqa/window"] * 1e-3))
    assert read["gqa_full_roofline"] == pytest.approx(
        100.0 * cost["gqa_full_flops"] / 197e12 / (n["seq/gqa/full"] * 1e-3))
    # a program without the scopes, or a cell of another model, reads nothing
    plain = dict(ctx, reduced=reduced_of([("fusion.1", "jit(step)/add", 0.0, 1e6)]))
    assert all(MAN.metric_reader(m).read(plain) is None for m in READERS)
    kimi = dict(ctx, cost={"flops": 1.0}, traffic={"kind": "train_seq"})
    assert MAN.metric_reader("gqa_window_roofline").read(kimi) is None
    assert MAN.metric_reader("gqa_full_roofline").read(kimi) is None


# -- whole runs at toy sizes ------------------------------------------------------


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The toy root, with documents short enough that its 64-token sequences
    hold several."""
    root = helpers.tiny_root(str(tmp_path_factory.mktemp("bench") / "root"))
    path = os.path.join(root, "benchmarks", "traffic", "train-packed16k-repo.json")
    traffic = json.load(open(path))
    traffic["rows"].update(median_tokens=12, min_tokens=4, max_tokens=64)
    json.dump(traffic, open(path, "w"))
    return root


@pytest.mark.parametrize("fault", ["no_segment_reset", "absent_experts_renormalised",
                                   "half_targets"])
def test_a_broken_mellum_tower_comes_out_as_not_correct(root, fault):
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "benchmark", "drive_seq.py"),
         "--root", root, "--workload", CELL, "--fault", fault],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    c = line["checks"]["grad_norm_gap"]
    assert c["value"] is None or c["value"] > c["limit"]


def test_the_parent_fails_at_once(root):
    """The parent of this PR has no ``models/mellum.py``: the runner says so
    and exits before any row is made."""
    code = ("import sys; sys.path.insert(0, %r); sys.modules['lightctr_tpu.models.mellum'] = None\n"
            "sys.path.insert(0, %r)\nimport helpers\nfrom benchmarks import run\n"
            "run.main(['--workload', %r, '--seed', '1', '--seconds', '1'], "
            "require=helpers.cpu_device, root=%r)"
            % (REPO, os.path.join(REPO, "tests", "benchmark"), CELL, root))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert "cannot run 'mellum'" in p.stderr and "nothing was run" in p.stderr


def test_the_cell_files_name_their_readings():
    """Each limit sits above the program's largest reading, and the control
    and every planted fault read above at least one limit."""
    for name in (CELL, "wd-train-tail"):
        cell = MAN.cell_file(name)
        assert set(cell["limits"]) == {"loss_gap", "grad_norm_gap", "change_norm_gap"}
        assert "PR 40" in cell["set_from"]["origin"]
        failed = {}
        for key, limit in cell["limits"].items():
            reading = cell["set_from"][key]
            assert reading["program_max"] < limit, (name, key)
            for what, value in reading.items():
                if what.startswith(("control", "fault")) and isinstance(value, float):
                    failed[what] = failed.get(what, False) or value > limit
        assert failed and all(failed.values()), (name, failed)
