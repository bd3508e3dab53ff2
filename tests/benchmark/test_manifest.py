"""BENCHMARK.json and every file it names load and cross-reference."""

import glob
import json
import os

import pytest

from benchmarks.harness import manifest as mf

import helpers
from helpers import BENCH, REPO

MAN = mf.Manifest(REPO)
DOC = MAN.doc


def test_manifest_has_exactly_the_contracts_keys():
    assert sorted(DOC) == sorted(["command", "paths", "run_seconds", "configs",
                                  "workloads", "end_to_end", "per_layer"])
    assert DOC["command"][:2] == ["python3", "benchmarks/run.py"]
    assert 1 <= DOC["run_seconds"] <= 51


def test_every_cross_reference_holds():
    assert mf.validate(MAN) == []


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    cells = DOC["workloads"]
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)


@pytest.mark.parametrize("entry", DOC["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_sizes(entry):
    cfg = MAN.config(entry["name"])
    for key in ("model", "source", "deployment", "assumed", "reduced", "bytes",
                "vocab", "batch", "fields", "learning_rate", "matmul_precision"):
        assert key in cfg, key
    assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
    model = MAN.model(cfg["model"])
    # the bytes the file reckons are the bytes the shapes give
    assert cfg["bytes"]["training_state"] == pytest.approx(
        model.state_bytes(cfg, training=True), rel=1e-3)
    # no reduced key names a width
    for key in entry["reduced"]:
        assert not key.endswith(("_dim", "_rank")) and key not in (
            "dim", "hidden", "factors")


@pytest.mark.parametrize("cell", DOC["workloads"], ids=lambda w: w["name"])
def test_cell_files_load(cell):
    traffic = MAN.traffic(cell["traffic"])
    assert traffic["kind"] in ("train", "train_seq")
    assert os.path.isfile(os.path.join(BENCH, "harness", traffic["kind"] + "_cell.py"))
    limits = MAN.cell_file(cell["name"])["limits"]
    assert limits and all(v >= 0 for v in limits.values())
    if traffic["kind"] == "train":
        assert traffic["rows"]["distinct_batches"] >= 64
    else:
        assert helpers.whole_passes(MAN.runner("train_seq"),
                                    traffic["rows"]["distinct_batches"])


@pytest.mark.parametrize("entry", DOC["per_layer"], ids=lambda m: m["name"])
def test_metric_file_declares_what_the_manifest_says(entry):
    reader = MAN.metric_reader(entry["name"])
    # which cells report it is the manifest's alone to say: a later PR adds a
    # cell to the entry's list and cannot edit the reader's file
    assert reader.META == {k: v for k, v in entry.items() if k != "workloads"}
    assert callable(reader.read)
    assert reader.__doc__ and len(reader.__doc__) > 40


@pytest.mark.parametrize("folder, ext, named", [
    ("metrics", ".py", {m["name"] for m in DOC["per_layer"]}),
    ("cells", ".json", {w["name"] for w in DOC["workloads"]}),
    ("traffic", ".json", {w["traffic"] for w in DOC["workloads"]}),
    ("configs", ".json", {os.path.basename(c["file"])[:-5] for c in DOC["configs"]}),
], ids=["metrics", "cells", "traffic", "configs"])
def test_no_data_file_without_a_manifest_entry(folder, ext, named):
    files = {os.path.basename(p)[:-len(ext)]
             for p in glob.glob(os.path.join(BENCH, folder, "*" + ext))}
    assert files == named


NAMES = ([("config", c["name"]) for c in DOC["configs"]]
         + [("cell", w["name"]) for w in DOC["workloads"]]
         + [("traffic", w["traffic"]) for w in DOC["workloads"]]
         + [("metric", m["name"]) for m in DOC["end_to_end"] + DOC["per_layer"]])


@pytest.mark.parametrize("kind, name", NAMES, ids=lambda x: x if isinstance(x, str) else None)
def test_names_use_only_the_allowed_characters(kind, name):
    assert mf.NAME_RE.match(name)


@pytest.mark.parametrize("m", DOC["end_to_end"] + DOC["per_layer"], ids=lambda m: m["name"])
def test_units_and_sources(m):
    assert mf.UNIT_RE.match(m["unit"]) and m["source"] in mf.SOURCES
    extra = set(m) - {"name", "unit", "better", "source", "bound", "layer",
                      "moves", "workloads"}
    assert not extra


def test_every_moves_is_reported_by_each_of_the_metrics_cells():
    for w in DOC["workloads"]:
        ends = {m["name"] for m in MAN.metrics_for(w["name"], "end_to_end")}
        for m in MAN.metrics_for(w["name"], "per_layer"):
            assert m["moves"] in ends, (w["name"], m["name"])


def test_a_roofline_or_mfu_share_is_named_so_and_is_a_percentage():
    for m in DOC["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"].split("_"):
            assert m["unit"] == "%" and m["better"] == "higher"


def test_peaks_table_names_its_source_and_rejects_an_unknown_kind():
    from benchmarks.harness.peaks import peaks_for

    table = json.load(open(os.path.join(BENCH, "peaks.json")))
    assert "819 GB/s" in table["_source"]
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks_for("_source")
