"""Shared by the harness's CPU tests: a throw-away benchmark root in a
temporary directory: the shipped manifest and configuration files at toy
sizes beside copies of the real harness, model and metric files."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmarks")

#: the toy sizes; every other key (``model``, ``mesh``, learning rate,
#: precision, ...) is the shipped configuration file's own
TINY_SIZES = {"vocab": 4096, "batch": 64, "dim": 8, "hidden": 16, "factors": 8}
TRAIN_LIMITS = {"loss_gap": 1e-4, "grad_norm_gap": 1e-3, "change_norm_gap": 1e-3}


def tiny_config(name: str, **sizes) -> dict:
    """The shipped ``configs/<name>.json`` with its sizes cut to a toy's."""
    cfg = json.load(open(os.path.join(BENCH, "configs", name + ".json")))
    cfg.update({k: v for k, v in dict(TINY_SIZES, **sizes).items() if k in cfg})
    return cfg


def tiny_root(tmp: str) -> str:
    """A benchmark root at ``tmp``: the real manifest and code, the shipped
    configuration, traffic and cell files at toy sizes.  ``wd-x4-train-zipf``
    takes its ``data=2 x embed=2`` mesh from its shipped file and runs on
    four of the virtual CPU devices."""
    bench = os.path.join(tmp, "benchmarks")
    os.makedirs(bench)
    for d in ("harness", "models", "metrics"):
        shutil.copytree(os.path.join(BENCH, d), os.path.join(bench, d),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(BENCH, "run.py"), bench)
    shutil.copy(os.path.join(BENCH, "peaks.json"), bench)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp)
    for d in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(bench, d))
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    for c in real["configs"]:
        json.dump(tiny_config(c["name"]), open(os.path.join(tmp, c["file"]), "w"))
    for t in {w["traffic"] for w in real["workloads"]}:
        d = json.load(open(os.path.join(BENCH, "traffic", t + ".json")))
        d["rows"]["distinct_batches"] = 8
        d["replay"]["shuffle_batches"] = 2
        json.dump(d, open(os.path.join(bench, "traffic", t + ".json"), "w"))
    for w in real["workloads"]:
        json.dump({"limits": TRAIN_LIMITS},
                  open(os.path.join(bench, "cells", w["name"] + ".json"), "w"))
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
