"""The generator reproduces from a seed, differs across seeds, follows the
law a traffic file states, and offers every seed the same work."""

import numpy as np
import pytest

from benchmarks.harness import datagen
from benchmarks.harness.manifest import Manifest

from helpers import REPO

MAN = Manifest(REPO)
KW = dict(fields=39, n_cat=26, vocab=1 << 20)
BIG = 2**31 + 12345
ZIPF = MAN.traffic("train-zipf")["rows"]
TAIL = MAN.traffic("train-tail")["rows"]
OLD_PROXY = dict(ZIPF, cardinalities="table")       # u^4 * vocab, in effect


def rows(seed, spec=ZIPF, n=256):
    rng = np.random.default_rng(datagen.seed_words(seed))
    return datagen.criteo_rows(rng, n, spec, **KW)


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_rows_reproduce_from_a_seed(seed):
    a, b = rows(seed), rows(seed)
    assert all(np.array_equal(a[k], b[k]) for k in a)


def test_rows_differ_across_seeds():
    assert not np.array_equal(rows(1)["fids"], rows(2)["fids"])
    assert not np.array_equal(rows(BIG)["fids"], rows(BIG + 1)["fids"])


@pytest.mark.parametrize("spec", [ZIPF, TAIL, OLD_PROXY], ids=["zipf", "tail", "table-skewed"])
def test_criteo_layout(spec):
    r = rows(3, spec)
    assert r["fids"].shape == (256, 39) and r["fids"].dtype == np.int32
    fixed = np.array_equal(r["fids"][:, 26:], np.tile(np.arange(26, 39), (256, 1)))
    assert fixed == (spec["numeric_ids"] == "fixed")
    assert np.all(r["vals"][:, :26] == 1.0) and set(np.unique(r["labels"])) <= {0.0, 1.0}
    assert r["fids"].min() >= 0 and r["fids"].max() < KW["vocab"]


def test_zipf_traffic_states_its_sources_and_the_criteo_kaggle_cardinalities():
    t = MAN.traffic("train-zipf")
    cards = t["rows"]["cardinalities"]
    assert len(cards) == 26 and sum(cards) == 33_762_577 and max(cards) == 10_131_227
    assert 0 < t["rows"]["exponent"] < 1
    assert {"cardinalities", "exponent", "numeric"} <= set(t["source"])


@pytest.mark.parametrize("s", [0.0, 0.75, 1.0, 1.2])
def test_ranks_follow_the_stated_power_law(s):
    n, draws = 1000, 400_000
    ranks = datagen.power_law_ranks(np.random.default_rng(1).random(draws), n, s)
    assert ranks.min() == 0 and ranks.max() == n - 1
    k = np.arange(n, dtype=np.float64)
    if s == 1.0:
        want = (np.log(k + 2) - np.log(k + 1)) / np.log(n + 1.0)
    else:
        e = 1.0 - s
        want = ((k + 2) ** e - (k + 1) ** e) / ((n + 1.0) ** e - 1.0)
    got = np.bincount(ranks, minlength=n) / draws
    assert np.abs(got[:20] - want[:20]).max() < 0.004
    assert abs(got[:100].sum() - want[:100].sum()) < 0.005


def test_a_field_touches_no_more_rows_than_it_has_values():
    r = rows(5, n=4096)
    per_field = [np.unique(r["fids"][:, j]).size for j in range(26)]
    assert all(d <= c for d, c in zip(per_field, ZIPF["cardinalities"]))
    assert per_field[8] == 3 and per_field[19] == 4       # C9, C20


def test_hashing_spreads_a_fields_values_over_the_table():
    ids = datagen.hash_rows(np.arange(100_000), 2, 1 << 25)
    assert ids.min() >= 0 and ids.max() < 1 << 25
    assert np.unique(ids).size > 99_800                  # few collisions
    assert not np.array_equal(ids, datagen.hash_rows(np.arange(100_000), 3, 1 << 25))
    assert abs(np.mean(ids) / (1 << 25) - 0.5) < 0.01


def test_zipf_ids_repeat_and_tail_ids_do_not():
    wd, fm = MAN.config("criteo-widedeep"), MAN.config("criteo-fm-k64")
    k = 4096 * 39
    dz = datagen.distinct_ids_per_batch(ZIPF, fields=39, n_cat=26, vocab=wd["vocab"], batch=4096)
    dt = datagen.distinct_ids_per_batch(TAIL, fields=39, n_cat=26, vocab=fm["vocab"], batch=4096)
    counted = MAN.traffic("train-zipf")["counted"]["distinct_rows_per_batch"]
    assert counted[0] - 200 < dz < counted[1] + 200      # ~45.4k of 159,744
    assert 0.27 * k < dz < 0.30 * k
    assert dt > 0.99 * k                                 # nearly every id is distinct


def test_libffm_text_round_trips(tmp_path):
    path = datagen.write_libffm(str(tmp_path / "r.ffm"), 50, 9, ZIPF, **KW)
    lines = open(path).read().splitlines()
    want = rows(9, n=50)
    assert len(lines) == 50
    first = lines[0].split()
    assert int(first[0]) == int(want["labels"][0]) and len(first) == 40
    assert [int(x.split(":")[1]) for x in first[1:]] == want["fids"][0].tolist()
    f, fid, val = first[27].split(":")
    assert (int(f), int(fid)) == (26, 26) and float(val) == pytest.approx(want["vals"][0, 26])


def test_shards_are_named_from_everything_that_determines_the_rows():
    from benchmarks.harness import dataset

    cfg = MAN.config("criteo-widedeep")
    a = dataset.rows_key(cfg, MAN.traffic("train-zipf"))
    b = dataset.rows_key(cfg, MAN.traffic("train-tail"))
    c = dataset.rows_key(dict(cfg, vocab=cfg["vocab"] // 2), MAN.traffic("train-zipf"))
    assert a != b and a != c and a == dataset.rows_key(cfg, MAN.traffic("train-zipf"))
    assert a["rows"] == 64 * cfg["batch"]
