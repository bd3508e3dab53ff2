"""The ``train_seq`` kind (ISSUE 36): its manifest entries and data files, its
generator, and whole runs of its runner at toy sizes on the CPU with the
timed path broken underneath (``correct`` must come out false).  Sound runs of
the cell in both modes are ``test_runs.py``'s, which takes every cell of
``BENCHMARK.json``."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import helpers
from helpers import BENCH, REPO

from benchmarks.harness import manifest as mf
from benchmarks.harness import seqgen

CELL = "kimilinear-train-packed8k"
MAN = mf.Manifest(REPO)
CFG = MAN.config("kimi-linear-48b-a3b-ep16")
TRAFFIC = MAN.traffic("train-packed8k")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_the_manifest_validates_with_the_new_entries():
    assert mf.validate(MAN) == []
    cell = MAN.workload(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi-linear-48b-a3b-ep16", "train-packed8k", 1)
    assert MAN.workload("fm-train-zipf")["config"] == "criteo-fm-k64"
    cells = MAN.doc["workloads"]
    assert CELL in [w["name"] for w in cells]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    per_layer = {m["name"] for m in MAN.metrics_for(CELL, "per_layer")}
    assert {"train_kda_device_share", "kda_scan_roofline", "train_moe_device_share",
            "moe_experts_roofline", "moe_expert_load_max_over_mean",
            "train_step_mfu_share", "sparse_apply_roofline"} <= per_layer
    assert TRAFFIC["kind"] == "train_seq"
    assert os.path.isfile(os.path.join(BENCH, "harness", "train_seq_cell.py"))


def test_the_size_keys_repeat_the_catalogs_and_no_width_is_cut():
    assert CFG["hidden"] == CFG["hidden_size"] == 2304
    assert CFG["dim"] == CFG["linear_attn_config"]["head_dim"] == 128
    assert CFG["vocab"] == CFG["vocab_size"] == 163840 // 8
    assert CFG["batch"] == 8192 and CFG["sequences"] == 1
    assert CFG["experts_routed_over"] == 256 and CFG["num_experts"] == 16
    assert sorted(CFG["reduced"]) == sorted(MAN.config_entry(
        "kimi-linear-48b-a3b-ep16")["reduced"])
    if not os.path.isfile(CATALOG):
        pytest.skip("the model catalog is not on this machine")
    row = next(json.loads(ln) for ln in open(CATALOG)
               if '"Kimi-Linear-48B-A3B-Instruct"' in ln)
    assert MAN.config_entry("kimi-linear-48b-a3b-ep16")["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in CFG["reduced"]:
            assert CFG[key] == value, key


def test_parameter_counts_are_the_issues():
    model = MAN.model("kimi_linear")
    assert CFG["bytes"]["dense_parameters"] == 781_740_928
    assert CFG["bytes"]["routed_expert_parameters"] == 16 * 4 * 3 * 2304 * 1024
    assert model.state_bytes(CFG, True) == 8 * (781_740_928 + 20480 * 2304)
    z = model.sizes(CFG)
    assert z["kinds"] == ("kda", "kda", "kda", "mla", "kda") and z["first_dense"] == 1


def test_packed_sequences_have_no_padding_and_renumber_documents():
    spec = TRAFFIC["rows"]
    seqs = seqgen.packed_sequences(np.random.default_rng(1), 6, spec,
                                   tokens=512, vocab=4096)
    assert seqs["tokens"].shape == (6, 512) and seqs["tokens"].max() < 4096
    seg = seqs["segments"]
    assert (seg[:, 0] == 0).all() and (np.diff(seg, axis=1) >= 0).all()
    assert (np.diff(seg, axis=1) <= 1).all()
    again = seqgen.packed_sequences(np.random.default_rng(1), 6, spec,
                                    tokens=512, vocab=4096)
    assert (again["tokens"] == seqs["tokens"]).all()


def test_the_traffic_files_counts_are_the_generators():
    counted = seqgen.counted(TRAFFIC["rows"], tokens=8192, vocab=CFG["vocab"],
                             sequences=1)
    want = TRAFFIC["counted"]
    for key in ("distinct_rows_per_step", "documents_per_step",
                "attended_pairs_per_step"):
        assert counted[key] == pytest.approx(want[key], rel=1e-6)
    assert 2500 < counted["distinct_rows_per_step"] < 3200
    assert 4 < counted["documents_per_step"] < 8


def test_cost_parts_add_up_and_the_step_is_flop_bound():
    model = MAN.model("kimi_linear")
    c = model.train_step_cost(CFG, 2800.0, 4 * 4096.0, 11.4e6)
    assert 15e12 < c["flops"] < 19e12                  # ISSUE 36: 2.05 GFLOP a token
    parts = c["kda_scan_flops"] + c["moe_experts_flops"] + c["mla_attention_flops"]
    assert 0 < parts < 0.2 * c["flops"]
    assert c["flops"] / 197e12 > c["hbm_bytes"] / 819e9
    assert c["apply_bytes"] == 4 * c["gather_bytes"] == 4 * 2800 * 4 * 2304
    # six bfloat16 passes a float32 product: the whole step's share of the
    # peak cannot pass a sixth
    assert 100 * (c["flops"] / 197e12) / (c["flops"] * 6 / 197e12) < 17


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The toy root, with documents short enough that its 64-token sequences
    hold several."""
    root = helpers.tiny_root(str(tmp_path_factory.mktemp("bench") / "root"))
    path = os.path.join(root, "benchmarks", "traffic", "train-packed8k.json")
    traffic = json.load(open(path))
    traffic["rows"].update(median_tokens=12, min_tokens=4, max_tokens=64)
    json.dump(traffic, open(path, "w"))
    return root


@pytest.mark.parametrize("fault, failing", [
    ("no_segment_reset", "grad_norm_gap"),
    ("absent_experts_renormalised", "grad_norm_gap"),
    ("half_targets", "grad_norm_gap"),
    ("bf16", "grad_norm_gap"),
])
def test_a_broken_sequence_tower_comes_out_as_not_correct(root, fault, failing):
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "benchmark", "drive_seq.py"),
         "--root", root, "--workload", CELL, "--fault", fault],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    c = line["checks"][failing]
    assert c["value"] is None or c["value"] > c["limit"]


def test_a_program_without_the_model_fails_at_once(root):
    """The parent of the PR that brought the model: the runner says so and
    exits before any row is made."""
    code = ("import sys; sys.path.insert(0, %r); sys.modules['lightctr_tpu.models.kimi_linear'] = None\n"
            "sys.path.insert(0, %r)\nimport helpers\nfrom benchmarks import run\n"
            "run.main(['--workload', %r, '--seed', '1', '--seconds', '1'], "
            "require=helpers.cpu_device, root=%r)"
            % (REPO, os.path.join(REPO, "tests", "benchmark"), CELL, root))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert "cannot run 'kimi_linear'" in p.stderr and "nothing was run" in p.stderr


def test_the_weights_come_from_the_config_and_not_from_the_seed():
    """The routing the weights make sets the step's time, so the cell draws
    them from ``weight_seed``; ``--seed`` (the key) picks the order alone."""
    import jax

    model = MAN.model("kimi_linear")
    small = dict(CFG, hidden=16, dim=8, vocab=64, moe_intermediate_size=8, num_experts=2,
                 intermediate_size=8, kv_lora_rank=8, qk_nope_head_dim=8,
                 qk_rope_head_dim=8, v_head_dim=8, kda_gate_rank=8)
    leaves = lambda cfg, k: jax.tree_util.tree_leaves(  # noqa: E731
        model.init_params(cfg, jax.random.PRNGKey(k)))
    assert all(np.array_equal(a, b) for a, b in zip(leaves(small, 1), leaves(small, 2)))
    other = dict(small, weight_seed=small["weight_seed"] + 1)
    assert not np.array_equal(leaves(small, 1)[0], leaves(other, 1)[0])
