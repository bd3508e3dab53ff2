"""Shared by the harness's CPU tests: a throw-away benchmark root in a
temporary directory, with tiny configurations beside links to the real
harness, model and metric files."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")

TINY_WD = {"model": "widedeep", "fields": 39, "n_cat": 26, "vocab": 4096,
           "dim": 8, "hidden": 16, "batch": 64, "learning_rate": 0.05,
           "adagrad_eps": 1e-7, "lambda_l2": 0.0, "matmul_precision": "highest"}
TINY_FM = {"model": "fm", "fields": 39, "n_cat": 26, "vocab": 4096,
           "factors": 8, "batch": 64, "learning_rate": 0.05,
           "adagrad_eps": 1e-7, "lambda_l2": 0.001, "matmul_precision": "highest"}
TRAIN_LIMITS = {"loss_gap": 1e-4, "grad_norm_gap": 1e-3, "change_norm_gap": 1e-3}


def tiny_root(tmp: str) -> str:
    """A benchmark root at ``tmp``: real code, tiny data files, the real
    cells at toy sizes, and beside them ``wd-x4-train-zipf``: the same
    Wide&Deep on a data=2 x embed=2 mesh of the virtual CPU devices, which
    keeps the harness's sharded path driven while no such cell is shipped."""
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    real["configs"].append({
        "name": "criteo-widedeep-x4", "source": "test",
        "file": "benchmarks/configs/criteo-widedeep-x4.json", "reduced": [],
        "why": "test"})
    real["workloads"].append({
        "name": "wd-x4-train-zipf", "config": "criteo-widedeep-x4",
        "traffic": "train-zipf", "chips": 4, "why": "test"})
    for m in real["end_to_end"] + real["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("wd-x4-train-zipf")
    bench = os.path.join(tmp, "benchmarks")
    os.makedirs(bench)
    for d in ("harness", "models", "metrics"):
        shutil.copytree(os.path.join(BENCH, d), os.path.join(bench, d),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(BENCH, "run.py"), bench)
    shutil.copy(os.path.join(BENCH, "peaks.json"), bench)
    for d in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(bench, d))
    x4 = dict(TINY_WD, mesh={"data": 2, "embed": 2})
    for name, cfg in (("criteo-widedeep", TINY_WD), ("criteo-fm-k64", TINY_FM),
                      ("criteo-widedeep-x4", x4)):
        json.dump(cfg, open(os.path.join(bench, "configs", name + ".json"), "w"))
    for t in ("train-zipf", "train-tail"):
        d = json.load(open(os.path.join(BENCH, "traffic", t + ".json")))
        d["rows"]["distinct_batches"] = 8
        d["replay"]["shuffle_batches"] = 2
        json.dump(d, open(os.path.join(bench, "traffic", t + ".json"), "w"))
    for w in real["workloads"]:
        json.dump({"limits": TRAIN_LIMITS},
                  open(os.path.join(bench, "cells", w["name"] + ".json"), "w"))
    json.dump(real, open(os.path.join(tmp, "BENCHMARK.json"), "w"))
    return tmp


def cpu_device(chips: int) -> dict:
    """In place of the harness's look for a chip (tests only)."""
    return {"platform": "cpu", "kind": "TPU v5 lite", "count": chips}


DRIVE = os.path.join(REPO, "tests", "benchmark", "drive.py")


def drive(root, workload, trace=0, fault="", seed=3000000019):
    """One run in a process of its own -> (the last line, its stderr)."""
    import subprocess
    import sys

    p = subprocess.run(
        [sys.executable, DRIVE, "--root", root, "--workload", workload,
         "--trace", str(trace), "--fault", fault, "--seed", str(seed)],
        capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr
