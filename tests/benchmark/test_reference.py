"""The comparison that decides ``correct``: the control (the reference in
bfloat16) and every planted fault read over the limits at a size a test
run can hold; a sound reference reads zero."""

import json
import os

import numpy as np
import pytest

from benchmarks.harness import datagen, reference
from benchmarks.harness.manifest import Manifest

from helpers import REPO, tiny_config

MAN = Manifest(REPO)
CASES = {"widedeep": tiny_config("criteo-widedeep", vocab=1 << 14, batch=512,
                                 dim=16, hidden=32),
         "fm": tiny_config("criteo-fm-k64", vocab=1 << 14, batch=512, factors=16)}


def evidence(model_name, seed):
    import jax

    cfg = CASES[model_name]
    model = MAN.model(model_name)
    rng = np.random.default_rng(seed)
    batches = []
    for _ in range(3):
        r = datagen.criteo_rows(rng, cfg["batch"], MAN.traffic("train-zipf")["rows"],
                                fields=39, n_cat=26, vocab=cfg["vocab"])
        r["mask"] = np.ones_like(r["vals"])
        batches.append(r)
    union = reference.touched_union(model, batches)
    full = jax.tree_util.tree_map(np.asarray, model.init_params(cfg, jax.random.PRNGKey(seed)))
    rows0 = {k: (v[union[model.TABLES[k]]] if k in model.TABLES else v)
             for k, v in full.items()}
    return model, cfg, rows0, reference.compact_batches(model, batches, union)


@pytest.fixture(scope="module", params=sorted(CASES))
def runs(request):
    model, cfg, rows0, compact = evidence(request.param, seed=3)
    out = {v: reference.reference_steps(model, cfg, rows0, compact, v)
           for v in reference.VARIANTS}
    return request.param, out


LIMITS = json.load(open(os.path.join(REPO, "benchmarks", "cells", "wd-train-zipf.json")))["limits"]


def test_the_reference_agrees_with_itself(runs):
    _, out = runs
    numbers = reference.compare(out["f32"], out["f32"])
    assert all(v == 0.0 for v in numbers.values())


@pytest.mark.parametrize("variant", ["bf16", "half_batch", "no_exchange"])
def test_control_and_faults_come_out_as_not_correct(runs, variant):
    name, out = runs
    numbers = reference.compare(out[variant], out["f32"])
    v = reference.verdict(numbers, LIMITS)
    assert v["correct"] is False, (name, variant, numbers)


@pytest.mark.parametrize("variant, floor", [("half_batch", 0.01), ("no_exchange", 0.1)])
def test_faults_read_far_above_the_limits(runs, variant, floor):
    _, out = runs
    numbers = reference.compare(out[variant], out["f32"])
    assert numbers["grad_norm_gap"] > floor > 10 * LIMITS["grad_norm_gap"]


def test_a_state_left_unchanged_reads_one(runs):
    _, out = runs
    frozen = dict(out["f32"], change_norm={k: 0.0 for k in out["f32"]["change_norm"]})
    assert reference.compare(frozen, out["f32"])["change_norm_gap"] == pytest.approx(1.0)


def test_gap_is_of_norms_against_the_larger_of_leaf_and_median():
    want = {"a": 1.0, "b": 100.0, "c": 1e-9}
    got = {"a": 1.1, "b": 100.0, "c": 2e-9}
    # median leaf norm is 1.0: leaf c's doubled norm is a gap of 1e-9, not of 1
    assert reference.worst_leaf_gap(got, want, list(want)) == pytest.approx(0.1)


def test_dead_gradient_leaves_are_left_out_of_the_change_by_rule():
    want = {"loss": [1.0], "grad_norm": {"a": 1.0, "b": 1.0, "dead": 1e-9},
            "change_norm": {"a": 1.0, "b": 1.0, "dead": 5.0}}
    got = {"loss": [1.0], "grad_norm": dict(want["grad_norm"]),
           "change_norm": {"a": 1.0, "b": 1.0, "dead": 0.0}}
    assert reference.compare(got, want)["change_norm_gap"] == 0.0


@pytest.mark.parametrize("value, ok", [(0.0, True), (1e-5, True), (1.0, False),
                                       (float("nan"), False), (float("inf"), False),
                                       (None, False)])
def test_verdict_fails_what_is_over_the_limit_or_not_a_number(value, ok):
    v = reference.verdict({"loss_gap": value}, {"loss_gap": 1e-4})
    assert v["correct"] is ok
    json.dumps(v)                            # the checks are plain JSON
