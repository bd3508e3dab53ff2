"""Finding the benchmark's data files by the names ``BENCHMARK.json`` gives.

A cell is ``workloads[i]``: it names a configuration (``configs[j].file``)
and a traffic mix (``<bench>/traffic/<traffic>.json``).  A per-layer metric
``m`` is read by ``<bench>/metrics/<m>.py``.  A configuration names its
model, whose plain reference and program adapter are
``<bench>/models/<model>.py``; a traffic file names its kind, whose runner
is ``<bench>/harness/<kind>_cell.py``.  Nothing here lists names: a later
PR adds files and manifest entries and edits no file that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Dict, List

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH_DIR)


class ManifestError(ValueError):
    pass


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


class Manifest:
    """``BENCHMARK.json`` of the checkout at ``root`` with its data files."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.doc = load_json(os.path.join(root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(
            root, os.path.dirname(self.doc["command"][1]))

    # -- entries ------------------------------------------------------------

    def workload(self, name: str) -> Dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(
            f"no workload {name!r} in BENCHMARK.json (have "
            f"{[w['name'] for w in self.doc['workloads']]})")

    def config_entry(self, name: str) -> Dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return c
        raise ManifestError(f"no config {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict:
        return load_json(os.path.join(self.root, self.config_entry(name)["file"]))

    def traffic(self, name: str) -> Dict:
        return load_json(os.path.join(self.bench_dir, "traffic", name + ".json"))

    def cell_file(self, name: str) -> Dict:
        """What belongs to one cell alone: the limits of its comparison."""
        return load_json(os.path.join(self.bench_dir, "cells", name + ".json"))

    def metrics_for(self, workload: str, group: str) -> List[Dict]:
        """Entries of ``end_to_end`` or ``per_layer`` that this cell reports."""
        e2e = {m["name"]: m for m in self.doc["end_to_end"]}
        reports = {n for n, m in e2e.items()
                   if "workloads" not in m or workload in m["workloads"]}
        if group == "end_to_end":
            return [e2e[n] for n in e2e if n in reports]
        return [m for m in self.doc["per_layer"]
                if (workload in m["workloads"] if "workloads" in m
                    else m["moves"] in reports)]

    # -- code found by name --------------------------------------------------

    def _module(self, kind: str, name: str, path: str):
        if not os.path.isfile(path):
            raise ManifestError(f"{kind} {name!r}: no file {path}")
        spec = importlib.util.spec_from_file_location(
            f"_bench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def metric_reader(self, name: str):
        return self._module("metric", name, os.path.join(
            self.bench_dir, "metrics", name + ".py"))

    def model(self, name: str):
        return self._module("model", name, os.path.join(
            self.bench_dir, "models", name + ".py"))

    def runner(self, kind: str):
        return self._module("runner", kind, os.path.join(
            self.bench_dir, "harness", kind + "_cell.py"))


def validate(m: Manifest) -> List[str]:
    """Every cross-reference and character rule a CPU test can hold the
    manifest and its files to; returns the faults found."""
    bad: List[str] = []
    doc = m.doc
    e2e = {x["name"]: x for x in doc["end_to_end"]}
    cells = {w["name"]: w for w in doc["workloads"]}
    configs = {c["name"]: c for c in doc["configs"]}
    if "setup_s" not in e2e:
        bad.append("end_to_end lacks setup_s")

    def check_name(what: str, s: str):
        if not NAME_RE.match(s):
            bad.append(f"{what} {s!r} is not a valid name")

    for c in doc["configs"]:
        check_name("config", c["name"])
        if not os.path.isfile(os.path.join(m.root, c["file"])):
            bad.append(f"config {c['name']}: no file {c['file']}")
        for k in c["reduced"]:
            check_name("reduced key", k)
    for w in doc["workloads"]:
        check_name("workload", w["name"])
        check_name("traffic", w["traffic"])
        if w["config"] not in configs:
            bad.append(f"workload {w['name']}: unknown config {w['config']}")
        if w["chips"] not in (1, 4):
            bad.append(f"workload {w['name']}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200:
            bad.append(f"workload {w['name']}: why has {len(w['why'])} chars")
        if not os.path.isfile(os.path.join(
                m.bench_dir, "traffic", w["traffic"] + ".json")):
            bad.append(f"workload {w['name']}: no traffic file")
    used = {w["config"] for w in doc["workloads"]}
    for c in configs:
        if c not in used:
            bad.append(f"config {c} is used by no cell")
    for group in ("end_to_end", "per_layer"):
        for x in doc[group]:
            check_name("metric", x["name"])
            if not UNIT_RE.match(x["unit"]):
                bad.append(f"metric {x['name']}: unit {x['unit']!r}")
            if x["better"] not in ("lower", "higher"):
                bad.append(f"metric {x['name']}: better {x['better']!r}")
            if x["source"] not in SOURCES:
                bad.append(f"metric {x['name']}: source {x['source']!r}")
            for w in x.get("workloads", ()):
                if w not in cells:
                    bad.append(f"metric {x['name']}: unknown cell {w}")
    for x in doc["end_to_end"]:
        if not 0 < x["bound"] <= 0.1:
            bad.append(f"metric {x['name']}: bound {x['bound']}")
        if x["source"] not in ("host_clock", "device_trace"):
            bad.append(f"end-to-end {x['name']}: source {x['source']}")
    for x in doc["per_layer"]:
        if x["moves"] not in e2e:
            bad.append(f"metric {x['name']}: moves unknown {x['moves']}")
            continue
        for w in cells:
            per = {p["name"] for p in m.metrics_for(w, "per_layer")}
            ends = {p["name"] for p in m.metrics_for(w, "end_to_end")}
            if x["name"] in per and x["moves"] not in ends:
                bad.append(f"metric {x['name']}: cell {w} does not report "
                           f"{x['moves']}")
        path = os.path.join(m.bench_dir, "metrics", x["name"] + ".py")
        if not os.path.isfile(path):
            bad.append(f"metric {x['name']}: no reader {path}")
    for w in cells:
        ends = {p["name"] for p in m.metrics_for(w, "end_to_end")}
        if "setup_s" not in ends or len(ends) < 2:
            bad.append(f"cell {w}: end-to-end metrics {sorted(ends)}")
        if not m.metrics_for(w, "per_layer"):
            bad.append(f"cell {w}: no per-layer metric")
    return bad
