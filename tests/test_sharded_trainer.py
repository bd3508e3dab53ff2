"""CTRTrainer with PS-style param shardings (embedding tables row-sharded
over the embed axis) matches replicated training."""

import re

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


@pytest.mark.parametrize(
    "axes", [None, dict(data=1), dict(data=2), dict(data=2, embed=2)],
    ids=["no_mesh", "axes_of_1", "data2", "embed2"])
def test_step_without_row_shards_compiles_as_before(axes):
    """What ISSUEs 30 and 39 may not cost the one-chip cells, guarded
    without a chip: with no mesh, or with rows "sharded" and the batch
    split over axes of one device, the step's optimized HLO is the
    parent's in what matters — 6 sorts, 38 gathers, 38 scatters, 6
    conditionals (counted at PR 29's tree on this configuration) — and
    holds nothing a shard_map leaves behind: no name scope of one, no
    collective, no dynamic slice, no partition id.  The counter sees what
    it is there to see: over two ``data`` replicas the step joins the row
    gradients inside a shard_map (and still plans no row shard), over two
    row shards the shards' plans appear."""
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
    # the state as the step holds it: embed[40000, 4] and its accumulator
    # are one fused store where a shard's rows divide by r = 32
    # (docs/KERNELS.md): a scatter fewer a rung, below
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
    if axes and axes["data"] > 1:
        assert "shard_map/model/expand" in text and count["all-reduce"]
        assert "gather_rows/shard_map" not in text
        return
    assert "shard_map" not in text
    if not axes:                      # (GSPMD slices a data-sharded batch)
        # a forward gather and the accumulator's, one switch of 9 branches
        # each, for w and for embed (whose two read lane rows of the one
        # store); w's switch scatters table and accumulator, embed's the
        # store once
        assert count == {"sort": 6, "gather": 38, "scatter": 29,
                         "conditional": 6, "all-reduce": 0,
                         "dynamic-slice": 0, "partition-id": 0}


# -- the row gradients joined over ``data`` by the step itself ---------------
#
# Over more than one ``data`` replica the loss and its gradient run a replica
# inside a shard_map and ``sparse_kernels.join_live`` sums the partial row
# gradients, flat and at the live prefix's rung.  The one-device step is the
# oracle, for every loss the step builds and for models that share no code.

_X4 = dict(data=2, embed=2)


def _by_rows(mesh, tables, params):
    rep = NamedSharding(mesh, P())
    return {k: NamedSharding(mesh, P("embed")) if k in tables else rep
            for k in params}


def _fm_pair(**kw):
    from lightctr_tpu.models import fm
    from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer

    params = fm.init(jax.random.PRNGKey(3), _VOCAB, _DIM)
    tables = {"w": ["fids"], "v": ["fids"]}
    mesh = make_mesh(MeshSpec(**_X4))

    def build(**mesh_kw):
        return SparseTableCTRTrainer(
            params, fm.logits, TrainConfig(learning_rate=0.1, lambda_l2=0.001),
            fused_fn=fm.logits_with_l2, sparse_tables=tables, **kw, **mesh_kw)

    batches = [_wd_batch(_stream_cases()["id0_live"](s, 2), s) for s in range(3)]
    return (build(mesh=mesh, param_shardings=_by_rows(mesh, tables, params)),
            build(), batches)


def _widedeep_pair(**kw):
    from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer

    params = widedeep.init(jax.random.PRNGKey(2), _VOCAB, _F, _DIM)
    tables = {"w": ["fids"], "embed": ["rep_fids"]}
    mesh = make_mesh(MeshSpec(**_X4))

    def build(**mesh_kw):
        return SparseTableCTRTrainer(
            params, widedeep.logits, TrainConfig(learning_rate=0.1),
            sparse_tables=tables, **kw, **mesh_kw)

    batches = [_wd_batch(_stream_cases()["rung_edges"](s, 2), s)
               for s in range(3)]
    return (build(mesh=mesh, param_shardings=_row_sharded(mesh)), build(),
            batches)


def _kimi_pair():
    """The sequence tower under the softmax loss: packed rows whose marked
    positions the two ``data`` replicas hold unequally (91 and 60), so a
    mean of the replicas' means would not be the batch's."""
    from lightctr_tpu.data import ingest
    from lightctr_tpu.models import kimi_linear
    from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer

    params, logits = kimi_linear.build(jax.random.PRNGKey(0))
    tables = {"embed": ["tokens"]}
    mesh = make_mesh(MeshSpec(**_X4))

    def build(**mesh_kw):
        return SparseTableCTRTrainer(
            params, logits,
            TrainConfig(learning_rate=0.05, lambda_l2=0.0, loss="softmax_xent"),
            sparse_tables=tables, **mesh_kw)

    docs = [list(range(1, 20)), list(range(5, 40)), list(range(30, 50)),
            list(range(3, 60)), list(range(7, 31)), list(range(2, 9))]
    batch = ingest.sequence_batch(ingest.pack_documents(docs, 32))
    marked = batch["target_mask"].reshape(2, -1).sum(axis=1)
    assert marked[0] != marked[1]
    return (build(mesh=mesh, param_shardings=_by_rows(mesh, tables, params)),
            build(), [batch] * 3)


# (the pair, the most a weight may differ: this file's 5e-7 — FM's hot ids 0
# and 1 sum a few hundred addends a step in another order and Adagrad's
# rsqrt carries that into 2 of ``w``'s weights and 13 of ``v``'s, by 7.9e-6
# at most, under GSPMD's all-reduce at PR 38 to the same digits; the tower's
# norm weights of 1.0 take Adagrad steps of 0.05 and end ten ulps apart)
_JOINED = {
    "widedeep-plain": (_widedeep_pair, 5e-7),
    "fm-plain": (_fm_pair, 1e-5),
    "widedeep-armed": (lambda: _widedeep_pair(quality_bins=16), 5e-7),
    "fm-armed": (lambda: _fm_pair(quality_bins=16), 1e-5),
    "kimi_linear-softmax_xent": (_kimi_pair, 5e-6),
}


def _run(tr, batch):
    """One step of the trainer's own jitted step, with its health vector."""
    tr._params, tr._opt_state, loss, health = tr._step(
        tr._params, tr._opt_state, tr._put(batch))
    return float(loss), np.asarray(health)


def _padded_collectives(text):
    """The all-reduces, reduce-scatters and all-gathers of an optimized
    HLO whose operand or result is an array of two dimensions or more
    with fewer than 128 in the minor one: what XLA:TPU pads to 128
    lanes.  (An array under one 8 x 128 tile's 1,024 elements — GSPMD's
    sum of the armed step's ``[bins, 4]`` sketch — is one tile either
    way.)"""
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) "
                     r"(all-reduce|reduce-scatter|all-gather)(?:-start)?\(",
                     line)
        if not m:
            continue
        for dims in re.findall(r"\[([0-9,]+)\]", m.group(1)):
            dims = [int(d) for d in dims.split(",")]
            if len(dims) > 1 and dims[-1] < 128 and np.prod(dims) >= 1024:
                found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("case", sorted(_JOINED))
def test_mesh_step_joins_the_row_gradients_itself(case):
    """Three steps over ``data=2 x embed=2`` against the one-device step,
    for the plain step, the step armed with the quality sketch and the
    softmax loss: loss, tables, accumulators, dense leaves, the sketch and
    the loss's own counts; the bytes a member hands each join, counted on
    the host from the stream's distinct count; and the compiled step holds
    no collective over a ``[.., d < 128]`` array — the row gradients cross
    flat, and so do the dense ones."""
    from lightctr_tpu import obs
    from lightctr_tpu.models.sparse_trainer import _unpack_counts
    from lightctr_tpu.obs import health
    from lightctr_tpu.ops import sparse_kernels as sk
    from tools import metrics_report

    pair, atol = _JOINED[case]
    sharded, plain, batches = pair()
    spec = sharded._spec
    text = jax.jit(sharded._build_step(), donate_argnums=(0, 1)).lower(
        sharded._params, sharded._opt_state, sharded._put(batches[0])
    ).compile().as_text()
    assert "shard_map/model/expand" in text
    assert not _padded_collectives(text)
    assert _padded_collectives(
        "%ar = (f32[9984,4]{1,0}, f32[7]{0}) all-reduce-start(%x, %y)")

    sharded.telemetry = obs.MetricsRegistry()
    sharded.health = health.HealthMonitor(registry=obs.MetricsRegistry())
    health.ensure_trainer_detectors(sharded.health, tables=True)
    want_bytes = dict.fromkeys(spec, 0)
    try:
        with obs.override(True):
            for batch in batches:
                (ls, hs), (lp, hp) = _run(sharded, batch), _run(plain, batch)
                np.testing.assert_allclose(ls, lp, rtol=2e-6)
                np.testing.assert_allclose(hs[:2], hp[:2], rtol=2e-5)
                if sharded._quality_bins:
                    tail = 4 * sharded._quality_bins
                    np.testing.assert_allclose(hs[-tail:], hp[-tail:],
                                               rtol=1e-5, atol=1e-6)
                model = sharded._step_counts.model
                if model:
                    got, want = (
                        _unpack_counts(h[2:2 + t._step_counts.width])[-len(model):]
                        for t, h in ((sharded, hs), (plain, hp)))
                    for name, g, w in zip(model, got, want):
                        # (a replica's fullest expert, summed over the
                        # replicas, bounds the batch's from above)
                        assert g >= w if "tokens_max" in name else g == w, name
                # what the host makes of the vector once it is drained
                sharded._observe_scalars(sharded.health, hs)
                for k, fields in spec.items():
                    ids = np.concatenate([batch[f].reshape(-1) for f in fields])
                    want_bytes[k] += 4 * sk.ladder_slots(
                        ids.size, np.unique(ids).size) * int(
                            np.prod(sharded._table_shapes[k][1:]))
    finally:
        sharded.health.close()
    # ``trainer_exchange_bytes_total{table, policy}``, as the report shows it
    report = metrics_report.summarize_exchange(sharded.telemetry.snapshot())
    for k in spec:
        assert report["tables"][k]["bytes"] == {
            "rows_join": want_bytes[k], "grad_join": want_bytes[k]}
        np.testing.assert_allclose(np.asarray(sharded.params[k]),
                                   np.asarray(plain.params[k]),
                                   rtol=0, atol=atol)
        np.testing.assert_allclose(
            np.asarray(sharded.opt_state["accum"][k]),
            np.asarray(plain.opt_state["accum"][k]), rtol=2e-6, atol=1e-9)
    dense = [jax.tree_util.tree_leaves({k: v for k, v in t.params.items()
                                        if k not in spec})
             for t in (sharded, plain)]
    for a, b in zip(*dense):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=atol)


# -- the fused store (docs/KERNELS.md) --------------------------------------

_PV, _PD = 40_960, 32                       # r = 4; K = 9,984 as above


def _shared_rows_ids(step, n):
    """Distinct ids in runs of 1 to 4 neighbours (the fused store's lane
    rows hold two ids each: some whole, some half touched), a live id 0,
    and shard counts on different rungs from one step to the next."""
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
    """A trainer that keeps ``embed[_PV, 32]`` and its accumulator in one
    fused store (over ``axes``), and a one-device trainer built under a
    rule no table meets: the unpacked arithmetic."""
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
    """Three steps of the trainer that holds ``embed`` and its
    accumulator as one ``[V // 2, 128]`` store against the same trainer
    holding the two ``[V, 32]``: loss, table and accumulator, on one
    device and over ``data=2 x embed=2`` with the shards on different
    rungs; ``params`` / ``opt_state`` show logical shapes under
    the table's sharding, their setters write one half of the store and
    leave the other, and the caller's tree is left alone."""
    from lightctr_tpu.ops import sparse_kernels as sk

    packed, plain, params = _packed_pair(monkeypatch, axes)
    n = axes["embed"] if axes else 1
    assert packed._params["embed"].shape == (_PV // 2, 128)
    assert set(packed._opt_state["accum"]) == {"w"}      # embed's is fused
    assert packed._params["w"].shape == (_PV,)
    assert not np.asarray(packed.opt_state["accum"]["embed"]).any()
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
    # (two whole steps compiled apart contract their fmas apart: the last
    # ulp; tests/test_sparse_kernels.py holds the apply alone to the bit)
    for k in ("w", "embed"):
        np.testing.assert_allclose(np.asarray(packed.params[k]),
                                   np.asarray(plain.params[k]),
                                   rtol=0, atol=5e-7)
        np.testing.assert_allclose(
            np.asarray(packed.opt_state["accum"][k]),
            np.asarray(plain.opt_state["accum"][k]), rtol=2e-6, atol=1e-9)
    moved = np.asarray(packed.params["embed"]) != np.asarray(params["embed"])
    assert moved.any() and not moved.all()
    # a setter writes its half of the store, in either order, and the
    # other half stays; the leaves no store holds are the caller's
    table, state = packed.params, packed.opt_state
    packed.params = {**table, "embed": 2 * table["embed"]}
    np.testing.assert_array_equal(
        np.asarray(packed.opt_state["accum"]["embed"]),
        np.asarray(state["accum"]["embed"]))
    packed.opt_state = {**state, "accum": {
        **state["accum"], "embed": 3 * state["accum"]["embed"]}}
    np.testing.assert_array_equal(np.asarray(packed.params["embed"]),
                                  2 * np.asarray(table["embed"]))
    np.testing.assert_array_equal(
        np.asarray(packed.opt_state["accum"]["embed"]),
        3 * np.asarray(state["accum"]["embed"]))
    assert set(packed._opt_state["accum"]) == {"w"}
    if axes:
        assert packed._params["embed"].sharding.spec[0] == "embed"
    packed.opt_state, packed.params = state, table
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
    assert second._params["embed"].shape == (_PV // 2, 128)
    assert set(second._opt_state["accum"]) == {"w"}
    for b in batches[2:]:
        assert float(first.train_step(b)) == float(second.train_step(b))
    for k in ("w", "embed"):
        np.testing.assert_array_equal(np.asarray(first.params[k]),
                                      np.asarray(second.params[k]))
        np.testing.assert_array_equal(
            np.asarray(first.opt_state["accum"][k]),
            np.asarray(second.opt_state["accum"][k]))
    first.reset(params)                       # fuses anew, fresh accumulators
    assert first._params["embed"].shape == (_PV // 2, 128)
    np.testing.assert_array_equal(np.asarray(first.params["embed"]),
                                  np.asarray(params["embed"]))
    assert not np.asarray(first.opt_state["accum"]["embed"]).any()
    assert not np.asarray(first.opt_state["accum"]["w"]).any()
