"""The FLOP and byte functions against shapes worked by hand."""

import pytest

from benchmarks.harness.manifest import Manifest

from helpers import REPO

MAN = Manifest(REPO)
WD = MAN.model("widedeep")
FM = MAN.model("fm")
WD_CFG = {"batch": 4, "fields": 3, "dim": 2, "hidden": 5, "vocab": 100}
FM_CFG = {"batch": 4, "fields": 3, "factors": 2, "vocab": 100}


# tower forward = 2*4*(3*2)*5 + 2*4*5 = 280; x3 = 840; wide 2*4*3 = 24
# a touched row is 4*(2+1) = 12 B: 7 distinct -> gather 84, apply 4*84 = 336
# batch in = 4*3*4*5 + 4*4 = 256
@pytest.mark.parametrize("key, want", [
    ("flops", 864.0), ("gather_bytes", 84.0), ("apply_bytes", 336.0),
    ("hbm_bytes", 84.0 + 336.0 + 256.0),
])
def test_widedeep_train_step_cost(key, want):
    assert WD.train_step_cost(WD_CFG, distinct=7)[key] == want


# fwd = 4*3*(4*2+2) + 3*4*2 = 144; x3 = 432; l2 term 2*4*3*3 = 72
# row 4*(2+1) = 12 B: 7 distinct -> 84 / 336; batch in 4*3*4*3 + 16 = 160
@pytest.mark.parametrize("key, want", [
    ("flops", 504.0), ("gather_bytes", 84.0), ("apply_bytes", 336.0),
    ("hbm_bytes", 84.0 + 336.0 + 160.0),
])
def test_fm_train_step_cost(key, want):
    assert FM.train_step_cost(FM_CFG, distinct=7)[key] == want


@pytest.mark.parametrize("name, state_gb", [
    ("criteo-widedeep", 8.86), ("criteo-fm-k64", 7.63),
])
def test_deployment_sizes_fill_the_chip_as_the_files_say(name, state_gb):
    cfg = MAN.config(name)
    got = MAN.model(cfg["model"]).state_bytes(cfg, training=True) / 1e9
    assert got == pytest.approx(state_gb, abs=0.02)


def test_costs_count_touched_rows_not_the_table():
    cfg = MAN.config("criteo-widedeep")
    c = WD.train_step_cost(cfg, distinct=45400)
    assert c["hbm_bytes"] < 1e8 < cfg["bytes"]["embed"]
