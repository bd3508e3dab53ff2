"""Whole runs of the harness at toy sizes on the CPU, each in a process of
its own (``drive.py``): the contract's last line in both modes, ``correct``
true on sound runs."""

import json
import os

import pytest

import helpers
from helpers import REPO, drive

CELLS = [w["name"] for w in json.load(
    open(os.path.join(REPO, "BENCHMARK.json")))["workloads"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return helpers.tiny_root(str(tmp_path_factory.mktemp("bench") / "root"))


def metrics_of(root, cell, group):
    from benchmarks.harness.manifest import Manifest

    return {m["name"]: m for m in Manifest(root).metrics_for(cell, group)}


@pytest.mark.parametrize("cell", CELLS)
def test_end_to_end_line(root, cell):
    line, err = drive(root, cell, trace=0)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"            # the numbers compared come last
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    want = {n: m["unit"] for n, m in metrics_of(root, cell, "end_to_end").items()}
    assert {k: v["unit"] for k, v in line["metrics"].items()} == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["compiles_in_window"] == 0
    # each number compared beside its limit: the last lines of stderr
    tail = [ln for ln in err.strip().splitlines() if ln.startswith("[check]")]
    assert len(tail) == len(line["checks"]) and err.strip().splitlines()[-1] == tail[-1]


@pytest.mark.parametrize("cell", CELLS)
def test_per_layer_line(root, cell):
    line, _ = drive(root, cell, trace=1, seed=2**31 + 77)
    assert line["correct"] is True
    known = metrics_of(root, cell, "per_layer")
    assert set(line["metrics"]) <= set(known)
    # what needs no device trace is read on the CPU too
    host_side = {n for n, m in known.items()
                 if m["source"] in ("host_clock", "program_counter")
                 and not n.startswith("hbm_")}      # the CPU reports no memory
    assert host_side <= set(line["metrics"])
    for name, m in line["metrics"].items():
        assert m["unit"] == known[name]["unit"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}


