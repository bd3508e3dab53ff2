"""A ``train_seq`` window is whole passes over the replayed set: set-up's
steps come out of the stream's first pass and the window opens at the next
pass boundary, so every run trains each distinct sequence equally often,
in an order ``--seed`` picks.  Whole runs of the runner at toy sizes on the
CPU (``drive_seq.py``), and the plan alone."""

import collections
import json
import os
import subprocess
import sys

import pytest

import helpers
from helpers import REPO

from benchmarks.harness import manifest as mf

CELL = "mellum-train-packed16k"
RUNNER = mf.Manifest(REPO).runner("train_seq")
SEEDS = (3000000019, 2**32 + 5)
PASS = 8                                  # helpers.tiny_root's distinct_batches


@pytest.mark.parametrize("pass_steps, consumed, seconds, step_s, want", [
    (16, 5, 10.0, 1.6, (11, 1)),          # the sequence cells on the chip
    (16, 5, 10.0, 0.3, (11, 3)),          # 4.8 s a pass: three cover 10 s
    (16, 5, 9.6, 0.3, (11, 2)),           # two passes are exactly 9.6 s
    (16, 16, 10.0, 1.6, (0, 1)),          # set-up ended on a boundary
    (4, 5, 10.0, 5.0, (3, 1)),            # set-up spans two passes
])
def test_the_window_plan(pass_steps, consumed, seconds, step_s, want):
    assert RUNNER.window_plan(pass_steps, consumed, seconds, step_s) == want


@pytest.fixture(scope="module")
def lines(tmp_path_factory):
    """One sound run a seed of the toy root, documents short enough that its
    64-token sequences hold several."""
    root = helpers.tiny_root(str(tmp_path_factory.mktemp("bench") / "root"))
    path = os.path.join(root, "benchmarks", "traffic", "train-packed16k-repo.json")
    traffic = json.load(open(path))
    traffic["rows"].update(median_tokens=12, min_tokens=4, max_tokens=64)
    json.dump(traffic, open(path, "w"))
    out = {}
    for seed in SEEDS:
        p = subprocess.run(
            [sys.executable, os.path.join(REPO, "tests", "benchmark", "drive_seq.py"),
             "--root", root, "--workload", CELL, "--seed", str(seed)],
            capture_output=True, text=True, timeout=900,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert p.returncode == 0, p.stderr[-3000:]
        out[seed] = json.loads(p.stdout.strip().splitlines()[-1])
    return out


@pytest.mark.parametrize("seed", SEEDS)
def test_each_sequence_is_trained_equally_often(lines, seed):
    line = lines[seed]
    assert line["correct"] is True
    trained = collections.Counter(line["window_sequences"])
    assert len(trained) == PASS
    assert set(trained.values()) == {line["passes"]}
    assert line["steps"] == line["attempted"] == line["passes"] * PASS
    # the first pass of the window is one whole pass: it opened on a boundary
    assert len(set(line["window_sequences"][:PASS])) == PASS


def test_two_seeds_train_the_same_multiset_in_different_orders(lines):
    a, b = (lines[s]["window_sequences"] for s in SEEDS)
    assert sorted(a) == sorted(b) and a != b
