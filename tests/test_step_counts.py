"""The one-program step counts its own ids: ``table_touch`` and the
apply's three counters come out of the drained health vector, and are the
integers the host used to make with a fetch of the id columns and an
``np.unique`` a table a step (PR 35).  That host path is the oracle here."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightctr_tpu import TrainConfig, obs
from lightctr_tpu.core.mesh import MeshSpec, make_mesh
from lightctr_tpu.models import fm, widedeep
from lightctr_tpu.models import sparse_trainer as st
from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer
from lightctr_tpu.obs import health
from lightctr_tpu.ops import sparse_kernels as sk

from test_sharded_trainer import (_B, _F, _PD, _PV, _row_sharded,
                                  _shared_rows_ids, _stream_cases, _wd_batch)

_K = _B * _F                                 # 9,984 slots: the ladder has rungs


class _TouchProbe(health.Detector):
    """Records every ``table_touch`` the monitor is fed."""

    name = "touch_probe"
    signals = ("table_touch",)

    def __init__(self):
        self.seen = []

    def check(self, signals):
        self.seen.append(signals["table_touch"])
        return health.OK, {}


def _watched(tr):
    """``tr`` on registries of its own, the default detectors and a probe."""
    tr.telemetry = obs.MetricsRegistry()
    tr.health = health.HealthMonitor(registry=obs.MetricsRegistry())
    health.ensure_trainer_detectors(tr.health, tables=True)
    return tr.health.add_detector(_TouchProbe())


def _host_counts(tr, batch, counters):
    """What the parent's ``_health_signals`` and ``_count_apply_slots``
    made of a host batch: ``table_touch``, and the increments of the three
    ``trainer_apply_*`` counters added to ``counters``."""
    touch = {}
    row_shards = tr._row_shards()
    for k, fields in tr._spec.items():
        ids = np.concatenate([np.asarray(batch[f]).reshape(-1)
                              for f in fields])
        distinct = np.unique(ids)
        vocab = tr._table_shapes[k][0]
        touch[k] = {"unique": int(distinct.size), "ids": int(ids.size),
                    "vocab": vocab}
        per = [({"table": k}, distinct)]
        if k in row_shards:
            n = tr.mesh.shape[row_shards[k]]
            cuts = np.searchsorted(distinct, np.arange(1, n) * (vocab // n))
            per = [({"table": k, "shard": i}, own)
                   for i, own in enumerate(np.split(distinct, cuts))]
        r = tr._lane_pack.get(k)
        for labels, own in per:
            counters[obs.labeled("trainer_apply_live_rows_total",
                                 **labels)] += own.size
            counters[obs.labeled("trainer_apply_slots_total", **labels)] += \
                sk.ladder_slots(ids.size, own.size)
            if r:
                counters[obs.labeled("trainer_apply_lane_rows_total",
                                     **labels)] += int(
                    np.count_nonzero(np.diff(own // r)) + (own.size > 0))
    return touch


def _count_cases():
    """{name: (mesh axes or None, ids_of(step, n_shards))}: the streams of
    tests/test_sharded_trainer.py over the lane-packed ``embed[_PV, 32]``
    (r = 4) and ``w[_PV]``, on one device and over ``data=2 x embed=2``."""
    ladder = sk.apply_ladder(_K)
    sharded = _stream_cases(_PV)
    x4 = dict(data=2, embed=2)

    def edges(step, n):                  # on a rung's edge, past it, the next
        count = (ladder[0], ladder[0] + 1, ladder[1])[step]
        return np.random.default_rng(step).choice(
            np.arange(1, _PV), size=count, replace=False)

    cases = {
        "one_device-id0_live": (None, sharded["id0_live"]),
        "one_device-all_equal": (None, lambda step, n: np.array([7 * step])),
        "one_device-all_distinct": (None, lambda step, n: np.random.
                                    default_rng(step).permutation(_PV)[:_K]),
        "one_device-rung_edges": (None, edges),
        "one_device-shared_lane_rows": (None, _shared_rows_ids),
        "data2xembed2-all_equal": (x4, lambda step, n: np.array(
            [(0, _PV // 2, _PV - 1)[step]])),
        "data2xembed2-shared_lane_rows": (x4, _shared_rows_ids),
    }
    for name in ("id0_live", "empty_shard", "one_shard", "rung_edges"):
        cases[f"data2xembed2-{name}"] = (x4, sharded[name])
    return cases


@pytest.mark.parametrize("case", sorted(_count_cases()))
def test_device_counts_equal_the_hosts_np_unique(case):
    """Three steps a stream: every ``table_touch`` the monitor is fed and
    the three ``trainer_apply_*`` counters (per shard on the mesh; lane
    rows for the packed table) equal the host's count of the same batches,
    ``trainer_health_signals_total`` says where they came from, and a
    step is observed once the queue is drained, not before."""
    axes, ids_of = _count_cases()[case]
    kw, n = {}, 1
    if axes:
        mesh = make_mesh(MeshSpec(**axes))
        kw, n = dict(mesh=mesh, param_shardings=_row_sharded(mesh)), 2
    tr = SparseTableCTRTrainer(
        widedeep.init(jax.random.PRNGKey(4), _PV, _F, _PD), widedeep.logits,
        TrainConfig(learning_rate=0.1),
        sparse_tables={"w": ["fids"], "embed": ["rep_fids"]}, **kw)
    assert tr._lane_pack == {"embed": 4}
    assert set(tr._row_shards()) == ({"w", "embed"} if axes else set())
    probe = _watched(tr)
    want_touch, want = [], collections.Counter()
    try:
        with obs.override(True):
            for step in range(3):
                batch = _wd_batch(ids_of(step, n), step)
                tr.train_step(batch)
                want_touch.append(_host_counts(tr, batch, want))
            assert len(probe.seen) <= 3
            tr.flush_health()
    finally:
        tr.health.close()
    assert probe.seen == want_touch
    want[obs.labeled("trainer_health_signals_total", source="device")] = 3
    counters = tr.telemetry.snapshot()["counters"]
    got = {k: v for k, v in counters.items()
           if k.startswith(("trainer_apply_", "trainer_health_signals_"))}
    assert got == dict(want)
    if axes:
        assert obs.labeled("trainer_apply_lane_rows_total", table="embed",
                           shard=1) in got
        assert obs.labeled("trainer_apply_lane_rows_total", table="w",
                           shard=0) not in got


@pytest.mark.parametrize("count", [0, 1, 65_535, 65_536, 159_744, 2**24 + 1,
                                   2**31 - 1])
def test_a_count_rides_the_vector_exactly(count):
    """Two f32 halves hold any int32 whole; one f32 does not hold 2^24 + 1
    (a stream of that many ids reports its length in the same slots)."""
    packed = jax.jit(st._pack_counts)(jnp.asarray([count, 7], jnp.int32))
    assert packed.dtype == jnp.float32 and packed.shape == (4,)
    assert st._unpack_counts(np.asarray(packed)) == [count, 7]
    if count == 2**24 + 1:
        assert int(np.float32(count)) != count


def test_a_stream_over_2p24_ids_keeps_its_slots():
    """By shape only: the step over a batch of 2^19 x 39 = 20,447,232 ids
    a stream traces, and its health vector is the head, the counts'
    slots and nothing else."""
    b = 1 << 19
    tr = SparseTableCTRTrainer(
        widedeep.init(jax.random.PRNGKey(0), 4096, _F, 4), widedeep.logits,
        TrainConfig(learning_rate=0.1),
        sparse_tables={"w": ["fids"], "embed": ["rep_fids"]})
    spec = lambda dt, *s: jax.ShapeDtypeStruct(s, dt)
    batch = {"fids": spec(jnp.int32, b, _F), "fields": spec(jnp.int32, b, _F),
             "vals": spec(jnp.float32, b, _F), "mask": spec(jnp.float32, b, _F),
             "rep_fids": spec(jnp.int32, b, _F),
             "rep_mask": spec(jnp.float32, b, _F),
             "labels": spec(jnp.float32, b)}
    assert b * _F > 2**24
    health_vec = jax.eval_shape(tr._build_step(), tr._params, tr._opt_state,
                                batch)[3]
    # two streams of (count, length); w: (live, branch); embed, lane-packed
    # at r = 32: (live, branch, lane rows); two f32 an integer
    assert tr._step_counts.width == 2 * (2 * 2 + 2 + 3)
    assert health_vec.shape == (2 + tr._step_counts.width,)


class _Spy(dict):
    """A batch that remembers which columns were asked for."""

    def __init__(self, *a):
        super().__init__(*a)
        self.read = set()

    def __getitem__(self, key):
        self.read.add(key)
        return super().__getitem__(key)


def test_the_one_program_record_step_reads_no_id_column(monkeypatch):
    """``_record_step`` of the one-program step touches no column of the
    batch but ``labels`` (for its shape), and no ``np.unique`` runs
    anywhere in the step: the skew detector is fed all the same."""
    b, f = 64, 8
    rng = np.random.default_rng(7)
    tr = SparseTableCTRTrainer(
        fm.init(jax.random.PRNGKey(0), 4096, 32), fm.logits,
        TrainConfig(learning_rate=0.05),
        sparse_tables={"w": ["fids"], "v": ["fids"]},
        fused_fn=fm.logits_with_l2)
    probe = _watched(tr)
    spies = []
    record = tr._record_step

    def spied(dt, batch, health=None):
        spies.append(_Spy(batch))
        return record(dt, spies[-1], health=health)

    def no_unique(*a, **kw):
        raise AssertionError("np.unique on the step's path")

    monkeypatch.setattr(tr, "_record_step", spied)
    uniques = []
    try:
        with obs.override(True):
            for _ in range(3):
                fids = rng.integers(0, 4096, size=(b, f)).astype(np.int32)
                uniques.append(int(np.unique(fids).size))
                with monkeypatch.context() as m:
                    m.setattr(np, "unique", no_unique)
                    tr.train_step({
                        "fids": fids,
                        "fields": np.tile(np.arange(f, dtype=np.int32), (b, 1)),
                        "vals": np.ones((b, f), np.float32),
                        "mask": np.ones((b, f), np.float32),
                        "labels": (rng.random(b) > 0.5).astype(np.float32)})
            with monkeypatch.context() as m:
                m.setattr(np, "unique", no_unique)
                tr.flush_health()
    finally:
        tr.health.close()
    assert len(spies) == 3 and all(s.read <= {"labels"} for s in spies)
    assert [t["w"]["unique"] for t in probe.seen] == uniques
    fresh = _Spy(spies[-1])
    assert tr._health_signals(fresh) == {} and not fresh.read


def test_a_hybrid_step_still_counts_on_the_host():
    """The hybrid exchange step dedups each replica's local rows, so the
    global distinct count is not on the device: ``table_touch`` comes from
    the host's ``np.unique`` as before, the same step, counted
    ``source="host"``, and no apply counter is made."""
    b, f, vocab = 64, 8, 1024
    rng = np.random.default_rng(9)
    tr = SparseTableCTRTrainer(
        fm.init(jax.random.PRNGKey(0), vocab, 4), fm.logits,
        TrainConfig(learning_rate=0.1, lambda_l2=0.001),
        sparse_tables={"w": ["fids"], "v": ["fids"]},
        fused_fn=fm.logits_with_l2, mesh=make_mesh(MeshSpec(data=8)))
    assert tr._hybrid_dp and tr._step_counts is None
    probe = _watched(tr)
    want = []
    try:
        with obs.override(True):
            for step in range(2):
                fids = rng.integers(1, vocab, size=(b, f)).astype(np.int32)
                tr.train_step({
                    "fids": fids,
                    "fields": np.tile(np.arange(f, dtype=np.int32), (b, 1)),
                    "vals": np.ones((b, f), np.float32),
                    "mask": np.ones((b, f), np.float32),
                    "labels": (rng.random(b) > 0.5).astype(np.float32)})
                touch = {"unique": int(np.unique(fids).size), "ids": b * f,
                         "vocab": vocab}
                want.append({"w": touch, "v": touch})
                assert probe.seen == want          # no lag: the host's count
            tr.flush_health()
    finally:
        tr.health.close()
    counters = tr.telemetry.snapshot()["counters"]
    assert counters[obs.labeled("trainer_health_signals_total",
                                source="host")] == 2
    assert not [k for k in counters if k.startswith("trainer_apply_")
                or 'source="device"' in k]
