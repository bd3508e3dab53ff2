"""CTRTrainer with PS-style param shardings (embedding tables row-sharded
over the embed axis) matches replicated training."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from lightctr_tpu import TrainConfig
from lightctr_tpu.core.mesh import MeshSpec, make_mesh
from lightctr_tpu.models import widedeep
from lightctr_tpu.models.ctr_trainer import CTRTrainer
# bound here: ``_rotated_dedup`` is patched over ``sparse_kernels.dedup_ids``
from lightctr_tpu.ops.sparse_kernels import dedup_ids as _dedup_ids


def test_embed_sharded_widedeep_matches_replicated(rng):
    n, f, field_cnt, nnz, dim = 64, 128, 4, 6, 8
    fids = rng.integers(1, f, size=(n, nnz)).astype(np.int32)
    fields = rng.integers(0, field_cnt, size=(n, nnz)).astype(np.int32)
    mask = np.ones((n, nnz), np.float32)
    labels = (rng.random(n) > 0.5).astype(np.float32)
    rep, rep_mask = widedeep.field_representatives(fids, fields, mask, field_cnt)
    batch = {
        "fids": fids, "fields": fields, "vals": np.ones((n, nnz), np.float32),
        "mask": mask, "labels": labels, "rep_fids": rep, "rep_mask": rep_mask,
    }
    params = widedeep.init(jax.random.PRNGKey(0), f, field_cnt, dim)
    cfg = TrainConfig(learning_rate=0.1)

    mesh = make_mesh(MeshSpec(data=4, embed=2))
    shardings = {
        "w": NamedSharding(mesh, P("embed")),
        "embed": NamedSharding(mesh, P("embed", None)),
        "fc1": {"w": NamedSharding(mesh, P()), "b": NamedSharding(mesh, P())},
        "fc2": {"w": NamedSharding(mesh, P()), "b": NamedSharding(mesh, P())},
    }
    tr_sharded = CTRTrainer(
        params, widedeep.logits, cfg, mesh=mesh, param_shardings=shardings
    )
    tr_plain = CTRTrainer(params, widedeep.logits, cfg)
    l_sharded = tr_sharded.fit_fullbatch_scan(batch, 10)
    l_plain = tr_plain.fit_fullbatch_scan(batch, 10)
    np.testing.assert_allclose(l_sharded, l_plain, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(tr_sharded.params["embed"]), np.asarray(tr_plain.params["embed"]),
        rtol=1e-4, atol=1e-5,
    )
    ev_s = tr_sharded.evaluate(batch)
    ev_p = tr_plain.evaluate(batch)
    assert abs(ev_s["auc"] - ev_p["auc"]) < 1e-4


# -- the O(touched) step with the live plan made per ``embed`` shard --------
#
# SparseTableCTRTrainer under ``param_shardings``: each shard of a table's
# rows gathers and applies its own run of the dedup slots on its own rung
# (ops.sparse_kernels.shard_plan).  The one-device step is the oracle.

_VOCAB, _B, _F, _DIM = 40_000, 256, 39, 4          # K = 9,984 >= the ladder's floor


def _row_sharded(mesh):
    """Wide&Deep's ``param_shardings``: tables by rows over ``embed``."""
    rep = NamedSharding(mesh, P())
    return {"w": NamedSharding(mesh, P("embed")),
            "embed": NamedSharding(mesh, P("embed", None)),
            "fc1": {"w": rep, "b": rep}, "fc2": {"w": rep, "b": rep}}


def _stream_cases(vocab=_VOCAB):
    """{name: (ids_of(step, n_shards) -> the batch's distinct ids, hook)}:
    every case builds the distinct set per shard of ``vocab // n`` rows."""
    from lightctr_tpu.ops import sparse_kernels as sk

    ladder = sk.apply_ladder(_B * _F)

    def spread(counts_of):
        def ids(step, n):
            rng = np.random.default_rng(100 + step)
            v = vocab // n
            out = []
            for e, c in enumerate(counts_of(n, step)):
                lo = max(1, e * v)                   # id 0 only on purpose
                out.append(rng.choice(np.arange(lo, (e + 1) * v), size=c,
                                      replace=False))
            return np.concatenate(out)
        return ids

    def id0(step, n):
        return np.concatenate([[0], spread(lambda n, s: [700] * n)(step, n)])

    return {
        # (a) a real id 0: live in slot 0, and only there
        "id0_live": id0,
        # (b) one shard owns no row of the batch
        "empty_shard": spread(
            lambda n, s: [ladder[1] - 10] + [0] + [300] * (n - 2)),
        # (c) the whole batch in one shard (the last, so its run starts late)
        "one_shard": spread(lambda n, s: [0] * (n - 1) + [ladder[1] + 40]),
        # (d) shard counts on a rung's edge, one past it, and a step later
        # swapped: neighbouring shards take different rungs
        "rung_edges": spread(lambda n, s: (
            [ladder[0], ladder[0] + 1] + [5] * (n - 2))[::1 if s % 2 else -1]),
        # (e) ids the plan sees out of order (the dedup below is rotated)
        "unsorted": spread(lambda n, s: [600] * n),
    }


def _rotated_dedup(ids, size=None):
    """``dedup_ids`` with the live prefix rotated by half its length: a
    valid dedup-convention pair for ids >= 1, but not ascending — what an
    exchange that hands per-owner segments gives."""
    u, inv, count = _dedup_ids(ids, size)
    slot = jnp.arange(u.shape[0])
    half = count // 2
    rot = jnp.where(slot < count, jnp.take(u, (slot + half) % count), 0)
    return rot, (inv - half) % count, count


def _wd_batch(distinct, step):
    from lightctr_tpu.models import widedeep as wd

    rng = np.random.default_rng(step)
    fids = rng.choice(distinct, size=_B * _F)
    fids[:distinct.size] = distinct                   # every id at least once
    fids = rng.permutation(fids).reshape(_B, _F).astype(np.int32)
    fields = np.tile(np.arange(_F, dtype=np.int32), (_B, 1))
    mask = np.ones((_B, _F), np.float32)
    rep, rep_mask = wd.field_representatives(fids, fields, mask, _F)
    return {"fids": fids, "fields": fields, "vals": np.ones((_B, _F), np.float32),
            "mask": mask, "labels": (rng.random(_B) > 0.5).astype(np.float32),
            "rep_fids": rep, "rep_mask": rep_mask}


@pytest.mark.parametrize("stream", sorted(_stream_cases()))
@pytest.mark.parametrize("axes", [dict(data=2, embed=2), dict(embed=4)],
                         ids=["data2xembed2", "embed4"])
def test_sharded_sparse_step_equals_one_device(axes, stream, monkeypatch):
    """Params, accumulators and loss of the row-sharded O(touched) step
    against the one-device step over three batches, and each shard on the
    rung its own rows need (the per-shard counters name it, from the
    branch the step reports in its health vector)."""
    from lightctr_tpu import obs
    from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer
    from lightctr_tpu.obs import health
    from lightctr_tpu.ops import sparse_kernels as sk

    if stream == "unsorted":
        monkeypatch.setattr(sk, "dedup_ids", _rotated_dedup)
    ids_of = _stream_cases()[stream]
    n = axes["embed"]
    params = widedeep.init(jax.random.PRNGKey(2), _VOCAB, _F, _DIM)
    cfg = TrainConfig(learning_rate=0.1)
    tables = {"w": ["fids"], "embed": ["rep_fids"]}
    mesh = make_mesh(MeshSpec(**axes))
    sharded = SparseTableCTRTrainer(
        params, widedeep.logits, cfg, sparse_tables=tables, mesh=mesh,
        param_shardings=_row_sharded(mesh))
    plain = SparseTableCTRTrainer(params, widedeep.logits, cfg,
                                  sparse_tables=tables)
    assert sharded._row_shards() == {"w": "embed", "embed": "embed"}
    assert plain._row_shards() == {}
    sharded.telemetry = obs.MetricsRegistry()
    sharded.health = health.HealthMonitor(registry=obs.MetricsRegistry())
    health.ensure_trainer_detectors(sharded.health, tables=True)
    want_slots = np.zeros(n, np.int64)
    try:
        with obs.override(True):
            for step in range(3):
                distinct = ids_of(step, n)
                batch = _wd_batch(distinct, step)
                ls, lp = sharded.train_step(batch), plain.train_step(batch)
                np.testing.assert_allclose(float(ls), float(lp), rtol=2e-6)
                per = np.bincount(distinct // (_VOCAB // n), minlength=n)
                # (ids the plan sees out of order take the undeclared
                # branch, all K slots, and the counter says so since it
                # reads the branch the device's switch took)
                want_slots += [_B * _F if stream == "unsorted"
                               else sk.ladder_slots(_B * _F, int(c))
                               for c in per]
            sharded.flush_health()
    finally:
        sharded.health.close()
    for k in tables:
        np.testing.assert_allclose(np.asarray(sharded.params[k]),
                                   np.asarray(plain.params[k]),
                                   rtol=0, atol=5e-7)
        np.testing.assert_allclose(
            np.asarray(sharded.opt_state["accum"][k]),
            np.asarray(plain.opt_state["accum"][k]), rtol=2e-6, atol=1e-9)
        assert sharded.params[k].sharding.spec[0] == "embed"
    counters = sharded.telemetry.snapshot()["counters"]
    got = [counters[obs.labeled("trainer_apply_slots_total", table="embed",
                                shard=e)] for e in range(n)]
    assert got == want_slots.tolist()
    if stream in ("one_shard", "rung_edges", "empty_shard"):
        assert len(set(got)) > 1              # shards on different rungs


@pytest.mark.parametrize("axes", [None, dict(data=2), dict(data=2, embed=2)],
                         ids=["no_mesh", "embed_axis_of_1", "embed2"])
def test_step_without_row_shards_compiles_as_before(axes):
    """What ISSUE 30 may not cost the one-chip cells, guarded without a
    chip: with no mesh, or with rows "sharded" over an axis of one device,
    the step's optimized HLO is the parent's in what matters — 6 sorts, 38
    gathers, 38 scatters, 6 conditionals (counted at PR 29's tree on this
    configuration) — and holds nothing a shard_map leaves behind: no
    name scope of one, no collective, no dynamic slice, no partition id.
    The counter sees what it is there to see: over two row shards they
    appear."""
    from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer

    params = widedeep.init(jax.random.PRNGKey(0), _VOCAB, _F, _DIM)
    kw = {}
    if axes:
        mesh = make_mesh(MeshSpec(**axes))
        kw = dict(mesh=mesh, param_shardings=_row_sharded(mesh))
    tr = SparseTableCTRTrainer(
        params, widedeep.logits, TrainConfig(learning_rate=0.1),
        sparse_tables={"w": ["fids"], "embed": ["rep_fids"]}, **kw)

    def spec(dtype, *shape):
        sharding = NamedSharding(kw["mesh"], P("data")) if axes else None
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    batch = {"fids": spec(jnp.int32, _B, _F), "fields": spec(jnp.int32, _B, _F),
             "rep_fids": spec(jnp.int32, _B, _F),
             "vals": spec(jnp.float32, _B, _F), "mask": spec(jnp.float32, _B, _F),
             "rep_mask": spec(jnp.float32, _B, _F),
             "labels": spec(jnp.float32, _B)}
    # the state as the step holds it: embed[40000, 4] is lane-packed where
    # a shard's rows divide by r = 32 (docs/KERNELS.md), which changes no
    # count below — the same gathers and scatters, over whole lane rows
    text = jax.jit(tr._build_step(), donate_argnums=(0, 1)).lower(
        tr._params, tr._opt_state, batch).compile().as_text()
    count = {op: text.count(f" {op}(") for op in (
        "sort", "gather", "scatter", "conditional", "all-reduce",
        "dynamic-slice", "partition-id")}
    if axes and axes.get("embed", 1) > 1:
        assert tr._row_shards() == {"w": "embed", "embed": "embed"}
        assert "shard_map" in text
        assert count["dynamic-slice"] and count["partition-id"]
        return
    assert tr._row_shards() == {}
    assert "shard_map" not in text
    if not axes:                      # (GSPMD slices a data-sharded batch)
        assert count == {"sort": 6, "gather": 38, "scatter": 38,
                         "conditional": 6, "all-reduce": 0,
                         "dynamic-slice": 0, "partition-id": 0}


# -- the lane-packed store (docs/KERNELS.md) -------------------------------

_PV, _PD = 40_960, 32                       # r = 4; K = 9,984 as above


def _shared_rows_ids(step, n):
    """Distinct ids whose lane rows hold 1 to 4 of them, a live id 0, and
    shard counts on different rungs from one step to the next."""
    rng = np.random.default_rng(200 + step)
    v = _PV // n
    out = [np.arange(3)]                                 # id 0, 1, 2 live
    for e, c in enumerate(([1200, 500] * n)[step % 2:][:n]):
        rows = rng.choice(np.arange(max(1, e * v // 4), (e + 1) * v // 4),
                          size=c, replace=False)
        out.append(np.concatenate([
            row * 4 + rng.choice(4, size=m, replace=False)
            for row, m in zip(rows, rng.integers(1, 5, size=c))]))
    return np.concatenate(out)


def _packed_pair(monkeypatch, axes):
    """A trainer that keeps ``embed[_PV, 32]`` lane-packed (over ``axes``),
    and a one-device trainer built under a rule no table meets: the
    unpacked arithmetic."""
    from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer
    from lightctr_tpu.ops import sparse_kernels as sk

    params = widedeep.init(jax.random.PRNGKey(4), _PV, _F, _PD)
    kw = {}
    if axes:
        mesh = make_mesh(MeshSpec(**axes))
        kw = dict(mesh=mesh, param_shardings=_row_sharded(mesh))

    def build(**kw):
        return SparseTableCTRTrainer(
            params, widedeep.logits, TrainConfig(learning_rate=0.1),
            sparse_tables={"w": ["fids"], "embed": ["rep_fids"]}, **kw)

    # the relayouts go a few lane rows at a time, with a tail, as a table
    # of 2^17 lane rows and more does (docs/KERNELS.md)
    monkeypatch.setattr(sk, "_RELAYOUT_LANE_ROWS", 1000)
    packed = build(**kw)
    # (on one device whatever the mesh: XLA's CPU backend segfaults in the
    # row-sharded step over [V, 16] and wider tables, at the parent too)
    with monkeypatch.context() as m:
        m.setattr(sk, "MIN_LANE_PACK", sk.LANES + 1)
        plain = build()
    assert packed._lane_pack == {"embed": 4} and plain._lane_pack == {}
    return packed, plain, params


@pytest.mark.parametrize("axes", [None, dict(data=2, embed=2)],
                         ids=["one_device", "data2xembed2"])
def test_packed_trainer_follows_the_unpacked_arithmetic(axes, monkeypatch):
    """Three steps of the trainer that holds ``embed`` as ``[V // 4,
    128]`` against the same trainer holding it ``[V, 32]``: loss, table
    and accumulator, on one device and over ``data=2 x embed=2`` with the
    shards on different rungs; ``params`` / ``opt_state`` show logical
    shapes under the table's sharding, and the caller's tree is left
    alone."""
    from lightctr_tpu.ops import sparse_kernels as sk

    packed, plain, params = _packed_pair(monkeypatch, axes)
    n = axes["embed"] if axes else 1
    assert packed._params["embed"].shape == (_PV // 4, 128)
    assert packed._opt_state["accum"]["embed"].shape == (_PV // 4, 128)
    assert packed._params["w"].shape == (_PV,)
    np.testing.assert_array_equal(np.asarray(packed.params["embed"]),
                                  np.asarray(params["embed"]))
    rungs = set()
    for step in range(3):
        distinct = _shared_rows_ids(step, n)
        batch = _wd_batch(distinct, step)
        lp, lu = packed.train_step(batch), plain.train_step(batch)
        np.testing.assert_allclose(float(lp), float(lu), rtol=2e-6)
        rungs |= {sk.ladder_slots(_B * _F, int(c)) for c in
                  np.bincount(distinct // (_PV // n), minlength=n)}
    assert len(rungs) > 1
    assert params["embed"].shape == (_PV, _PD)           # never donated
    for view in (packed.params, packed.opt_state["accum"]):
        assert view["embed"].shape == (_PV, _PD)
        if axes:
            assert view["embed"].sharding.spec[0] == "embed"
            assert packed._params["embed"].sharding.spec[0] == "embed"
    for k in ("w", "embed"):
        np.testing.assert_allclose(np.asarray(packed.params[k]),
                                   np.asarray(plain.params[k]),
                                   rtol=0, atol=5e-7)
        np.testing.assert_allclose(
            np.asarray(packed.opt_state["accum"][k]),
            np.asarray(plain.opt_state["accum"][k]), rtol=2e-6, atol=1e-9)
    moved = np.asarray(packed.params["embed"]) != np.asarray(params["embed"])
    assert moved.any() and not moved.all()
    # evaluation reads the logical view, once a call
    batch = _wd_batch(_shared_rows_ids(7, n), 7)
    assert packed.evaluate(batch, batch_size=64) == pytest.approx(
        plain.evaluate(batch, batch_size=64), rel=1e-6)


def test_packed_trainer_checkpoint_restores_into_the_same_trajectory(
        tmp_path, monkeypatch):
    """A checkpoint of a packed trainer holds logical tables (the format
    does not change) and restores, through ``params`` / ``opt_state``,
    into a trainer that goes on as the first one does."""
    from lightctr_tpu import ckpt

    first, _, params = _packed_pair(monkeypatch, None)
    batches = [_wd_batch(_shared_rows_ids(s, 1), s) for s in range(4)]
    for b in batches[:2]:
        first.train_step(b)
    ckpt.save(str(tmp_path), 2, {"params": first.params,
                                 "opt_state": first.opt_state})
    second, _, _ = _packed_pair(monkeypatch, None)
    state = ckpt.restore(str(tmp_path), like={
        "params": second.params, "opt_state": second.opt_state})
    assert np.shape(state["params"]["embed"]) == (_PV, _PD)
    assert np.shape(state["opt_state"]["accum"]["embed"]) == (_PV, _PD)
    second.params, second.opt_state = state["params"], state["opt_state"]
    assert second._params["embed"].shape == (_PV // 4, 128)
    for b in batches[2:]:
        assert float(first.train_step(b)) == float(second.train_step(b))
    for k in ("w", "embed"):
        np.testing.assert_array_equal(np.asarray(first.params[k]),
                                      np.asarray(second.params[k]))
        np.testing.assert_array_equal(
            np.asarray(first.opt_state["accum"][k]),
            np.asarray(second.opt_state["accum"][k]))
    first.reset(params)                       # packs anew, fresh accumulators
    assert first._params["embed"].shape == (_PV // 4, 128)
    np.testing.assert_array_equal(np.asarray(first.params["embed"]),
                                  np.asarray(params["embed"]))
    assert not np.asarray(first._opt_state["accum"]["embed"]).any()
