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
    ("criteo-widedeep", 8.86), ("criteo-fm-k64", 7.63), ("criteo-widedeep-x4", 17.72),
])
def test_deployment_sizes_fill_the_chip_as_the_files_say(name, state_gb):
    cfg = MAN.config(name)
    got = MAN.model(cfg["model"]).state_bytes(cfg, training=True) / 1e9
    assert got == pytest.approx(state_gb, abs=0.02)


def test_costs_count_touched_rows_not_the_table():
    cfg = MAN.config("criteo-widedeep")
    c = WD.train_step_cost(cfg, distinct=45400)
    assert c["hbm_bytes"] < 1e8 < cfg["bytes"]["embed"]


# -- a chip's share of the step's bytes: neither share of a peak passes 100% ---


def share_ctx(cfg, chips, apply_s_a_step, step_s):
    """What the two readers get, from a step whose apply took
    ``apply_s_a_step`` on every chip and whose window ran ``step_s`` a step."""
    from benchmarks.harness.peaks import peaks_for
    from benchmarks.harness.trace_reduce import op_label

    scope = "jit(step)/sparse_tables/apply/scatter-add"
    ev = (op_label("fusion.1", scope), scope, 0.0, apply_s_a_step * 1e9)
    return {"cfg": cfg, "chips": chips, "steps": 1, "window_s": step_s,
            "peaks": peaks_for("TPU v5 lite"),
            "cost": WD.train_step_cost(MAN.config("criteo-widedeep"), distinct=45400),
            "reduced": {"per_device": {f"/device:TPU:{i}": [ev] for i in range(chips)}}}


# the shipped data=2 x embed=2 file: dividing the update's bytes by its four
# chips, not its two embed shards, would read half of the apply's share
@pytest.mark.parametrize("cfg, chips, writers", [
    ({}, 1, 1), ({}, 4, 4), (MAN.config("criteo-widedeep-x4"), 4, 2),
    ({"mesh": {"data": 4}}, 4, 1),
], ids=["one_chip", "no_mesh_x4", "data2_embed2", "data4"])
def test_shares_of_a_peak_reach_100_at_the_least_time_and_never_pass_it(cfg, chips, writers):
    from benchmarks.harness.common import row_writers

    assert row_writers(cfg, chips) == writers
    cost = WD.train_step_cost(MAN.config("criteo-widedeep"), distinct=45400)
    bw = 819e9
    # the least a chip can take: its rows' update, written by every replica
    apply_least = cost["apply_bytes"] / writers / bw
    step_least = ((cost["hbm_bytes"] - cost["apply_bytes"]) / chips / bw + apply_least)
    ctx = share_ctx(cfg, chips, apply_least, step_least)
    assert MAN.metric_reader("sparse_apply_roofline").read(ctx) == pytest.approx(100.0)
    assert MAN.metric_reader("train_step_mfu_share").read(ctx) == pytest.approx(100.0)
    # any real step is slower, and reads under 100
    slow = share_ctx(cfg, chips, 3 * apply_least, 3 * step_least)
    assert MAN.metric_reader("sparse_apply_roofline").read(slow) == pytest.approx(100.0 / 3)
    assert MAN.metric_reader("train_step_mfu_share").read(slow) == pytest.approx(100.0 / 3)
