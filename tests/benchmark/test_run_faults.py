"""Whole runs at toy sizes on the CPU with the timed path broken underneath
(``correct`` must come out false), a cell added by files alone, and the
refusal to run off a TPU or outside a checkout of the program."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import helpers
from helpers import BENCH, REPO, drive

ENV = dict(os.environ, JAX_PLATFORMS="cpu")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return helpers.tiny_root(str(tmp_path_factory.mktemp("bench") / "root"))


@pytest.mark.parametrize("cell, fault, failing", [
    ("wd-train-zipf", "frozen_state", "change_norm_gap"),
    ("fm-train-tail", "frozen_state", "grad_norm_gap"),
    ("wd-train-zipf", "half_batch", "grad_norm_gap"),
    ("wd-x4-train-zipf", "half_batch", "loss_gap"),
    ("wd-x4-train-zipf", "no_exchange", "grad_norm_gap"),
    ("fm-train-tail", "half_batch", "grad_norm_gap"),
])
def test_a_broken_timed_path_comes_out_as_not_correct(root, cell, fault, failing):
    line, _ = drive(root, cell, fault=fault)
    assert line["correct"] is False
    c = line["checks"][failing]
    assert c["value"] is None or c["value"] > c["limit"]


def test_a_cell_config_traffic_and_metric_are_added_by_files_alone(tmp_path):
    root = helpers.tiny_root(str(tmp_path / "root"))
    bench = os.path.join(root, "benchmarks")
    before = {p: os.path.getmtime(os.path.join(dp, p))
              for dp, _, fs in os.walk(bench) for p in fs}
    cfg = helpers.tiny_config("criteo-fm-k64", factors=4)
    json.dump(cfg, open(os.path.join(bench, "configs", "throwaway-fm.json"), "w"))
    traffic = json.load(open(os.path.join(bench, "traffic", "train-zipf.json")))
    traffic["rows"]["exponent"] = 0.4
    json.dump(traffic, open(os.path.join(bench, "traffic", "train-mild.json"), "w"))
    json.dump({"limits": helpers.TRAIN_LIMITS},
              open(os.path.join(bench, "cells", "throwaway.json"), "w"))
    with open(os.path.join(bench, "metrics", "steps_in_window.py"), "w") as f:
        f.write('"""Steps the window completed."""\n\n'
                "def read(ctx):\n    return ctx['steps']\n")
    doc = json.load(open(os.path.join(root, "BENCHMARK.json")))
    doc["configs"].append({"name": "throwaway-fm", "source": "none",
                           "file": "benchmarks/configs/throwaway-fm.json",
                           "reduced": [], "why": "test"})
    doc["workloads"].append({"name": "throwaway", "config": "throwaway-fm",
                             "traffic": "train-mild", "chips": 1, "why": "test"})
    doc["end_to_end"][0]["workloads"].append("throwaway")
    doc["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                             "better": "higher", "source": "program_counter",
                             "layer": "jitted step",
                             "moves": "train_examples_per_s_per_chip",
                             "workloads": ["throwaway"]})
    json.dump(doc, open(os.path.join(root, "BENCHMARK.json"), "w"))
    line, _ = drive(root, "throwaway", trace=1)
    assert line["correct"] is True
    assert line["metrics"]["steps_in_window"]["value"] == line["steps"] > 0
    after = {p: os.path.getmtime(os.path.join(dp, p))
             for dp, _, fs in os.walk(bench) for p in fs if p in before}
    assert after == before                   # no file that was there changed


def test_run_exits_non_zero_off_a_tpu_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "wd-train-zipf",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=ENV, cwd=REPO)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_run_exits_non_zero_where_only_the_benchmarks_files_are(tmp_path):
    alone = tmp_path / "alone"
    shutil.copytree(BENCH, alone / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), alone)
    env = {k: v for k, v in ENV.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "wd-train-zipf",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=str(alone))
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_an_unknown_workload_is_an_error():
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", "no-such-cell",
         "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, timeout=300, env=ENV, cwd=REPO)
    assert p.returncode != 0 and p.stdout.strip() == ""
