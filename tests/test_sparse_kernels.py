"""The sparse hot path (``ops.sparse_kernels``): each phase against an
oracle that is not the code under test — the dedup against ``jnp.unique``
over duplicate-heavy, degenerate and empty id streams, the merge against
a numpy loop, the sized apply against ``sparse_adagrad_update`` within
FMA-contraction ulp, the EF pack against the written-out codec chain —
and, for the kernels with two implementations, the Pallas form in
interpret mode bit-identical to the codec, and the registry's dispatch."""

from functools import lru_cache, partial

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightctr_tpu.ops import quantize
from lightctr_tpu.ops import sparse_kernels as sk


def _assert_dedup_equal(ref, got):
    for a, b, what in zip(ref, got, ("uids", "inv", "count")):
        assert a.dtype == b.dtype and a.shape == b.shape, what
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=what)


def _unique_oracle(ids, size):
    """The literal call ``dedup_ids`` was until PR 29, kept here as the
    oracle the three-sort body is held to, output for output."""
    ids = jnp.asarray(ids).reshape(-1)
    u, inv = jnp.unique(ids, return_inverse=True, size=size, fill_value=0)
    inv = inv.reshape(-1).astype(jnp.int32)
    count = (jnp.max(inv) + 1).astype(jnp.int32) if ids.shape[0] else jnp.int32(0)
    return u, inv, count


# -- (a) dedup: exact jnp.unique contract --------------------------------


def test_dedup_empty_stream():
    """K=0 sorts nothing: the early return keeps the contract shapes
    (size-padded uids, empty inverse, zero count)."""
    u, inv, c = sk.dedup_ids(jnp.zeros((0,), jnp.int32), size=4)
    assert u.shape == (4,) and inv.shape == (0,) and int(c) == 0
    assert not np.asarray(u).any()


def _power_law_ids(k, vocab, seed):
    """A head-heavy stream: most slots repeat a few hundred ids, the rest
    spread over the vocabulary, id 0 among them."""
    r = np.random.default_rng(seed)
    ids = (vocab * r.random(k) ** 6).astype(np.int64).clip(0, vocab - 1)
    ids[::97] = 0
    return ids.astype(np.int32)


_INT32_MAX = np.iinfo(np.int32).max


def _property_streams():
    """The property sweep: a random stream, duplicate-heavy ones (few
    distinct values, id 0 present and absent), all-identical, all-padding
    (all-zero) and all-distinct descending."""
    r = np.random.default_rng(0)
    return {
        "random_777": np.random.default_rng(0).integers(
            0, 500, size=777).astype(np.int32),
        "heavy_id0": r.choice([0, 1, 7], size=300).astype(np.int32),
        "heavy_no_id0": r.choice([3, 9], size=256).astype(np.int32),
        "identical_64": np.full(64, 5, np.int32),
        "all_zero_32": np.zeros(32, np.int32),
        "reversed_distinct_96": np.arange(1, 97, dtype=np.int32)[::-1].copy(),
    }


def _seeded_stream(seed):
    r = np.random.default_rng(seed)
    return r.integers(0, 8, size=int(r.integers(9, 200))).astype(np.int32)


#: name -> (ids, size, x64): the streams the dedup is held to
#: ``jnp.unique`` on, bit for bit
_ORACLE_CASES = {
    **{name: (ids, None, False)
       for name, ids in _property_streams().items()},
    **{f"few_values_seed{seed}": (_seeded_stream(seed), None, False)
       for seed in range(4)},
    "all_distinct": (np.random.default_rng(1).permutation(
        np.arange(1, 1025)).astype(np.int32), None, False),
    "all_equal": (np.full(300, 7, np.int32), None, False),
    "id0_present": (np.random.default_rng(2).choice(
        [0, 1, 7, 90], size=500).astype(np.int32), None, False),
    "id0_absent": (np.random.default_rng(3).choice(
        [3, 9, 11], size=500).astype(np.int32), None, False),
    "all_zero": (np.zeros(64, np.int32), None, False),
    "power_law_8192": (_power_law_ids(8192, 1 << 25, 4), None, False),
    "power_law_16384": (_power_law_ids(16384, 1 << 20, 5), None, False),
    "size_below_count": (np.random.default_rng(6).permutation(
        np.arange(1, 51)).astype(np.int32), 10, False),
    "size_above_k": (np.random.default_rng(7).integers(
        0, 40, size=64).astype(np.int32), 200, False),
    "k1": (np.array([42], np.int32), None, False),
    "k1_size3": (np.array([0], np.int32), 3, False),
    "k0": (np.zeros((0,), np.int32), 4, False),
    "int32_extremes": (np.array(
        [_INT32_MAX, 0, -5, _INT32_MAX, -(2 ** 31), 3, -5], np.int32),
        None, False),
    "int64_above_2_31": (np.random.default_rng(8).choice(
        np.array([0, 5, 2 ** 31, 2 ** 31 + 1, 2 ** 40 + 3, 2 ** 62],
                 np.int64), size=400), None, True),
}


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("case", sorted(_ORACLE_CASES))
def test_dedup_is_jnp_unique_bit_for_bit(case, jitted):
    """The three-sort body against the literal ``jnp.unique`` call (K = 0
    takes the early return): values, dtypes and shapes of uids, the
    full-rank inverse — ranks beyond the cut where ``size`` truncates —
    and the count, which is the true total whatever ``size`` is."""
    ids, size, x64 = _ORACLE_CASES[case]
    size = ids.shape[0] if size is None else size
    with jax.enable_x64(x64):
        dedup = partial(sk.dedup_ids, size=size)
        got = (jax.jit(dedup) if jitted else dedup)(jnp.asarray(ids))
        assert got[0].dtype == ids.dtype     # an int64 stream keeps its width
        _assert_dedup_equal(_unique_oracle(ids, size), got)
        assert int(got[2]) == np.unique(ids).size


def test_dedup_compiles_to_three_sorts_and_nothing_k_sized_else():
    """What PR 29 bought, guarded without a chip: at K = 8,192 (the ladder
    live) the jitted dedup's optimized HLO holds three sorts and no
    gather and no scatter — ``jnp.unique`` compiles here to one sort, two
    gathers and two scatters, and on a v5e each of those K-sized passes
    costs 5-9 sorts."""
    def hlo(fn):
        return jax.jit(fn).lower(
            jax.ShapeDtypeStruct((8192,), jnp.int32)).compile().as_text()

    def count(text):
        return {op: text.count(f" {op}(")
                for op in ("sort", "gather", "scatter")}

    new = hlo(lambda ids: sk.dedup_ids(ids, 8192))
    assert count(new) == {"sort": 3, "gather": 0, "scatter": 0}
    # the counter sees what it is there to see: the old body's passes
    old = hlo(lambda ids: _unique_oracle(ids, 8192))
    assert count(old)["gather"] > 0 and count(old)["scatter"] > 0


# -- (b) merge + merge-apply ----------------------------------------------


def test_merge_rows_bit_exact(rng):
    """``merge_rows`` against a numpy loop adding each row to its segment
    in slot order (the order ``segment_sum`` applies); out-of-range
    segments — truncated ranks — are dropped."""
    m, s, d = 333, 40, 6
    inv = rng.integers(0, s + 5, size=m).astype(np.int32)  # incl. dropped
    rows = rng.normal(size=(m, d)).astype(np.float32)
    want = np.zeros((s, d), np.float32)
    for seg, row in zip(inv, rows):
        if seg < s:
            want[seg] += row
    got = sk.merge_rows(jnp.asarray(rows), jnp.asarray(inv), s)
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), want)


def _convention_uids(rng, s, vocab, with_zero=False):
    lo = 0 if with_zero else 1
    u = np.unique(rng.integers(lo, vocab, size=s))
    uids = np.zeros(s, np.int64)
    uids[: u.size] = u
    return uids, u.size


def _small_merged_payload(rng):
    """A merged payload (``inv``) with ``denom=4.0``, id 0 allowed."""
    m, s, vocab, d = 160, 40, 64, 5
    uids, nu = _convention_uids(rng, s, vocab, with_zero=True)
    inv = rng.integers(0, nu, size=m).astype(np.int32)
    rows = rng.normal(size=(m, d)).astype(np.float32)
    table = rng.normal(size=(vocab, d)).astype(np.float32)
    accum = np.abs(rng.normal(size=(vocab, d))).astype(np.float32)
    return (table, accum, uids, rows, inv), dict(lr=0.1, denom=4.0)


def _small_apply_only_1d(rng):
    """inv=None (the rs path: rows arrive merged) on a 1-D table (the FM
    w leaf) — padded id-0 slots are exact no-ops."""
    s, vocab = 24, 48
    uids, nu = _convention_uids(rng, s, vocab)
    rows = rng.normal(size=(s,)).astype(np.float32)
    rows[nu:] = 0.0
    table = rng.normal(size=(vocab,)).astype(np.float32)
    accum = np.abs(rng.normal(size=(vocab,))).astype(np.float32)
    return (table, accum, uids, rows, None), dict(lr=0.05, denom=1.0)


def _small_pads_and_real_id0(rng):
    """An odd slot count with dedup pads behind the live ids (row 0 is
    written once) and a REAL id 0 at slot 0."""
    s, vocab, d = 11, 32, 3
    uids = np.zeros(s, np.int64)
    u = np.unique(rng.integers(1, vocab, size=s - 2))
    uids[1:1 + u.size] = u  # slot 0 stays id 0 — REAL here
    rows = rng.normal(size=(s, d)).astype(np.float32)
    rows[1 + u.size:] = 0.0  # pads carry zero rows
    table = rng.normal(size=(vocab, d)).astype(np.float32)
    accum = np.abs(rng.normal(size=(vocab, d))).astype(np.float32)
    return (table, accum, uids, rows, None), dict(lr=0.1, denom=2.0)


# -- (b') the sized apply: live prefix, ladder, observed order -------------

#: the smallest K with a real ladder, and its rungs
_LK = sk.LADDER_MIN_SLOTS
_RUNGS = sk.apply_ladder(_LK)


def _live_case(rng, count, *, vocab=20000, shape=(4,), with_zero=False,
               with_inv=False, interleave=False, per_shard=None):
    """Dedup-convention inputs at K = ``_LK`` with ``count`` live slots:
    ``(table, accum, uids, rows, inv)`` as numpy arrays (``inv`` None
    unless ``with_inv``).  ``per_shard``: how many of the ids fall in
    each of ``len(per_shard)`` equal row ranges of the table."""
    k = _LK
    if per_shard is None:
        per_shard = [count]
    v = vocab // len(per_shard)
    assert sum(per_shard) == count
    ids = np.concatenate([
        np.sort(rng.choice(np.arange(max(1, e * v), (e + 1) * v), size=c,
                           replace=False))
        for e, c in enumerate(per_shard)])
    if with_zero:
        ids[0] = 0
    uids = np.zeros(k, np.int32)
    g = np.zeros((k,) + shape, np.float32)
    if interleave:
        # what the reduce-scatter exchange hands over: per-owner sorted
        # segments, id-0 pads (and whatever the codec decoded there)
        # between them
        slots = np.sort(rng.choice(np.arange(1, k), size=count - 1,
                                   replace=False))
        slots = np.concatenate([[0], slots])
        half = count // 2
        ids = np.concatenate([np.sort(ids[:half]), np.sort(ids[half:])])
        g[:] = rng.normal(size=g.shape)          # noise in the pad slots
    else:
        slots = np.arange(count)
    uids[slots] = ids
    g[slots] = rng.normal(size=(count,) + shape)
    # |w| < 2 keeps one ulp of a weight under the parity tests' atol
    table = rng.uniform(-1, 1, size=(vocab,) + shape).astype(np.float32)
    accum = np.abs(rng.normal(size=(vocab,) + shape)).astype(np.float32)
    if not with_inv:
        return table, accum, uids, g, None
    # every live slot's row split over three payload rows; pad segments
    # are never referenced
    inv = np.repeat(slots, 3).astype(np.int32)
    rows = np.repeat(g[slots], 3, axis=0) * \
        rng.normal(size=(3 * count,) + (1,) * len(shape)).astype(np.float32)
    perm = rng.permutation(3 * count)
    return table, accum, uids, rows.astype(np.float32)[perm], inv[perm]


def _sized_apply_cases():
    cases = [("count1", dict(count=1))]
    for s in _RUNGS:
        cases.append((f"edge{s}", dict(count=s)))
        if s < _LK:
            cases.append((f"edge{s}+1", dict(count=s + 1)))
    cases += [
        ("real_id0", dict(count=_RUNGS[2] - 7, with_zero=True)),
        ("table_1d", dict(count=_RUNGS[1] + 5, shape=())),
        ("table_1d_id0", dict(count=_RUNGS[0], shape=(), with_zero=True)),
        ("with_inv", dict(count=_RUNGS[0] + 9, with_inv=True)),
        ("with_inv_1d", dict(count=_RUNGS[3], shape=(), with_inv=True)),
        ("interleaved_pads", dict(count=_RUNGS[1], interleave=True)),
        ("interleaved_pads_1d", dict(count=777, shape=(), interleave=True)),
        ("embed_mesh", dict(count=_RUNGS[2] + 1, mesh=True)),
    ]
    # the apply made per row shard (``shard_axis``, inside a shard_map):
    # a rung's edge in one shard and one past it in the next, a real id
    # 0, a shard that owns nothing, everything in the last shard, the 1-D
    # table, a merged payload, and ids seen out of order
    r0, r1 = _RUNGS[0], _RUNGS[1]
    for name, per, kw in [
        ("rung_edges", [r0, r0 + 1], {}),
        ("rung_edges_4", [r0 + 1, 3, r0, r1 + 1], {}),
        ("id0", [500, 700], dict(with_zero=True)),
        ("empty_shard", [r1 + 2, 0, 9, 0], {}),
        ("one_shard", [0, 0, 0, r1], {}),
        ("one_row", [0, 1], {}),
        ("1d", [r0 - 1, r1], dict(shape=())),
        ("with_inv", [800, r0 + 5], dict(with_inv=True)),
        ("interleaved_pads", [400, 377], dict(interleave=True)),
    ]:
        cases.append((f"shards_{name}", dict(
            count=sum(per), per_shard=per, shards=True, **kw)))
    # K under LADDER_MIN_SLOTS — the one-rung ladder: the tiered store's
    # and the exchange steps' sizes
    cases += [
        ("small_merged_payload", dict(small=_small_merged_payload)),
        ("small_apply_only_1d", dict(small=_small_apply_only_1d)),
        ("small_pads_and_real_id0", dict(small=_small_pads_and_real_id0)),
    ]
    return [pytest.param(kw, id=name) for name, kw in cases]


@pytest.mark.parametrize("case", _sized_apply_cases())
def test_sized_apply_matches_sparse_adagrad_update(case):
    """The apply — live prefix, one rung of the ladder, no second dedup —
    against ``embed.table.sparse_adagrad_update`` (the chain it replaced:
    ``jnp.unique`` + ``segment_sum`` + K-slot scatter-adds) on the same
    inputs, to the last FMA-contraction ulp (XLA fuses ``accum + g*g``
    into an fma on CPU; docs/KERNELS.md): at one live slot, at every
    rung's edge and one past it, with a real id 0, for ``w[V]`` and ``[V,
    d]``, with ``inv``, with interleaved pads (which must take the
    undeclared branch), with the table sharded over ``embed`` on four
    devices, and under the ladder's floor where there is one rung."""
    from lightctr_tpu.embed.table import SparseAdagradState, \
        sparse_adagrad_update

    kw = dict(case)
    mesh = kw.pop("mesh", False)
    shards = len(kw["per_shard"]) if kw.pop("shards", False) else 0
    lr, eps, denom = 0.1, 1e-7, 2.0
    if "small" in kw:
        (table, accum, uids, rows, inv), over = kw["small"](
            np.random.default_rng(0))
        lr, denom = over["lr"], over["denom"]
        count = int(((uids != 0) | (np.arange(uids.size) == 0)).sum())
    else:
        count = kw["count"]
        table, accum, uids, rows, inv = _live_case(
            np.random.default_rng(count), **kw)
    k = uids.shape[0]
    rungs = sk.apply_ladder(k)

    # the chain the apply replaced (pad slots zeroed for inv=None
    # payloads, as merge_apply's contract has them)
    if inv is not None:
        merged = jax.ops.segment_sum(jnp.asarray(rows), jnp.asarray(inv),
                                     num_segments=k)
    else:
        pad = (uids == 0) & (np.arange(k) > 0)
        merged = jnp.asarray(rows * (~pad).reshape((-1,) + (1,) * (rows.ndim - 1)))
    w0, st = sparse_adagrad_update(
        jnp.asarray(table), SparseAdagradState(accum=jnp.asarray(accum)),
        jnp.asarray(uids), merged / denom, lr, eps=eps)
    a0 = st.accum

    args = [jnp.asarray(table), jnp.asarray(accum), jnp.asarray(uids),
            jnp.asarray(rows), None if inv is None else jnp.asarray(inv)]
    fn = jax.jit(lambda w, a, u, r, i: sk.merge_apply(
        w, a, u, r, i, lr=lr, eps=eps, denom=denom))
    if mesh or shards:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        m = Mesh(np.array(jax.devices()[:shards or 4]), ("embed",))
        rowwise = NamedSharding(m, P("embed", *([None] * (table.ndim - 1))))
    if shards:
        # each shard applies its own rows: table and accumulator by rows,
        # ids and payload whole; the sum of squares is the whole payload's
        fn = jax.jit(jax.shard_map(
            lambda w, a, u, r, i: sk.merge_apply(
                w, a, u, r, i, lr=lr, eps=eps, denom=denom,
                shard_axis="embed"),
            mesh=m, in_specs=(P("embed"), P("embed"), P(), P(), P()),
            out_specs=(P("embed"), P("embed"), P())))
    if mesh:
        args[0] = jax.device_put(args[0], rowwise)
        args[1] = jax.device_put(args[1], rowwise)
        args[2] = jax.device_put(args[2], NamedSharding(m, P()))
        args[3] = jax.device_put(args[3], NamedSharding(m, P()))
    w1, a1, s1 = fn(*args)
    if mesh or shards:
        assert w1.sharding.is_equivalent_to(rowwise, table.ndim)

    np.testing.assert_allclose(np.asarray(w1), np.asarray(w0),
                               rtol=0, atol=2e-7)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a0),
                               rtol=2e-6, atol=0)
    np.testing.assert_allclose(float(s1), float(jnp.sum(
        (merged / denom) ** 2)), rtol=1e-5)
    untouched = np.setdiff1d(np.arange(table.shape[0]), uids)
    np.testing.assert_array_equal(np.asarray(w1)[untouched], table[untouched])
    np.testing.assert_array_equal(np.asarray(a1)[untouched], accum[untouched])
    if uids[0] != 0:
        # id 0 was never live: neither a pad nor noise in a pad may move it
        np.testing.assert_array_equal(np.asarray(w1)[0], table[0])
        np.testing.assert_array_equal(np.asarray(a1)[0], accum[0])
    elif np.asarray(merged)[0].any():
        assert np.asarray(w1)[0].tolist() != table[0].tolist()  # id 0 trained

    # the rung the device takes is the one the host names from the count
    # — the whole table's, or each shard's from its own rows
    v = table.shape[0] // max(shards, 1)
    for e, n in enumerate(kw.get("per_shard", [count])):
        _, branch, start, live = sk.shard_plan(
            jnp.asarray(uids), v, e * v if shards else None)
        if kw.get("interleave"):
            assert int(branch) == len(rungs)        # observed unsorted
            assert int(start) == 0
        else:
            assert int(live) == n
            assert rungs[int(branch)] == sk.ladder_slots(k, n)
            assert n <= rungs[int(branch)]
            assert int(branch) == 0 or rungs[int(branch) - 1] < n


def test_apply_ladder_is_a_function_of_k_alone():
    """One rung under the small-K floor; above it at most eight ascending
    rungs ending at K; and the host function behind
    ``trainer_apply_slots_total`` names the rung the device's switch
    takes, over a sweep of live counts."""
    import inspect

    assert list(inspect.signature(sk.apply_ladder).parameters) == ["k"]
    for k in (1, 8, 24, 160, 4096, sk.LADDER_MIN_SLOTS - 1):
        assert sk.apply_ladder(k) == (k,)
        assert sk.ladder_slots(k, 1) == sk.ladder_slots(k, k) == k
    for k in (sk.LADDER_MIN_SLOTS, 10_000, 159_744, 4096 * 39 * 2 + 3):
        ladder = sk.apply_ladder(k)
        assert 2 <= len(ladder) <= 8 and ladder[-1] == k
        assert list(ladder) == sorted(set(ladder))
    assert sk.apply_ladder(159_744)[3:5] == (49_920, 59_904)

    k, vocab = 10_000, 50_000
    ladder = sk.apply_ladder(k)
    plan = jax.jit(lambda u: sk.live_plan(u, vocab)[1])
    counts = sorted({1, 2, k - 1, k, *ladder, *(s + 1 for s in ladder[:-1]),
                     *np.random.default_rng(0).integers(1, k, size=12)})
    for count in counts:
        uids = np.zeros(k, np.int32)
        uids[:count] = np.arange(1, count + 1)
        assert ladder[int(plan(jnp.asarray(uids)))] == \
            sk.ladder_slots(k, int(count)), count


def _shard_plan_cases():
    r0, r1 = _RUNGS[0], _RUNGS[1]
    cases = [
        ("two_even", [700, 900], {}),
        ("rung_edges", [r0, r0 + 1], {}),
        ("four", [3, r0 + 1, 0, r1], {}),
        ("id0", [40, 50], dict(with_zero=True)),
        ("empty_first", [0, 321], {}),
        ("all_in_first", [r1 + 1, 0, 0, 0], {}),
        ("full", [_LK // 2, _LK // 2], {}),
        ("unsorted", [300, 300], dict(interleave=True)),
    ]
    return [pytest.param(per, kw, id=name) for name, per, kw in cases]


@pytest.mark.parametrize("per_shard,kw", _shard_plan_cases())
def test_shard_plan_is_a_pure_function_of_uids_and_the_row_range(per_shard,
                                                                 kw):
    """``shard_plan`` per row range: ``start`` is the count of live ids
    under the range, ``count`` the distinct ids in it, ``idx[:count]`` the
    LOCAL rows ascending, every slot behind them past the local table and
    still ascending, the rung the smallest that holds ``count``; the
    ranges' runs tile the live prefix; ids seen out of order keep their
    slots (``start`` 0) and take the undeclared branch; and the whole
    table (``lo=None``) is ``live_plan``."""
    count = sum(per_shard)
    vocab = 20000
    _, _, uids, _, _ = _live_case(np.random.default_rng(count), count,
                                  vocab=vocab, per_shard=per_shard, **kw)
    n, v = len(per_shard), vocab // len(per_shard)
    plan = jax.jit(lambda u, lo: sk.shard_plan(u, v, lo))
    slot = np.arange(_LK)
    live = (uids != 0) | (slot == 0)
    covered = np.zeros(_LK, bool)
    for e, want in enumerate(per_shard):
        idx, branch, start, got = (np.asarray(x) for x in
                                   plan(jnp.asarray(uids), e * v))
        own = live & (uids >= e * v) & (uids < (e + 1) * v)
        assert own.sum() == want
        if kw.get("interleave"):
            assert int(branch) == len(_RUNGS) and int(start) == 0
            np.testing.assert_array_equal(idx[own], uids[own] - e * v)
            assert (idx[~own] >= v).all()
            covered |= own
            continue
        assert (int(start), int(got)) == (int((live & (uids < e * v)).sum()),
                                          want)
        np.testing.assert_array_equal(
            idx[:want], uids[start:start + want] - e * v)
        assert (idx[want:] >= v).all()                 # pads: past the table
        assert (np.diff(idx.astype(np.int64)) > 0).all()   # ascending, unique
        assert _RUNGS[int(branch)] == sk.ladder_slots(_LK, want)
        covered[start:start + want] = True
    np.testing.assert_array_equal(covered, live)
    idx, branch, start, got = sk.shard_plan(jnp.asarray(uids), vocab)
    idx0, branch0 = sk.live_plan(jnp.asarray(uids), vocab)
    assert start == 0 and int(branch) == int(branch0)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(idx0))
    if not kw.get("interleave"):
        assert int(got) == count


def test_shard_plan_sees_an_own_slot_outside_its_run():
    """Out-of-order ids whose window reads ascending — one own id in slot
    0, ahead of the lower shard's — still take the undeclared branch: the
    plan counts its own slots inside the run it took."""
    v = 10000
    uids = np.zeros(_LK, np.int32)
    uids[:2] = (15000, 5)
    for e, local in ((0, 5), (1, 5000)):
        idx, branch, start, count = sk.shard_plan(jnp.asarray(uids), v, e * v)
        assert (int(branch), int(start), int(count)) == (len(_RUNGS), 0, 1)
        assert int(idx[1 - e]) == local and (np.asarray(idx) >= v).sum() == _LK - 1


@pytest.mark.parametrize("per_shard,kw", _shard_plan_cases())
def test_gather_shards_joins_each_shards_own_rows(per_shard, kw):
    """``gather_shards`` inside a shard_map over the table's row shards:
    every live slot holds its table row, every other slot zeros — clip's
    last row of a shard never leaks into the sum — for ``[V, d]`` and
    ``w[V]``."""
    from jax.sharding import Mesh, PartitionSpec as P

    count, vocab = sum(per_shard), 20000
    rng = np.random.default_rng(count)
    table, _, uids, _, _ = _live_case(rng, count, vocab=vocab,
                                      per_shard=per_shard, **kw)
    m = Mesh(np.array(jax.devices()[:len(per_shard)]), ("embed",))
    gather = jax.jit(jax.shard_map(
        partial(sk.gather_shards, axis_name="embed"), mesh=m,
        in_specs=(P("embed"), P()), out_specs=P()))
    live = (uids != 0) | (np.arange(_LK) == 0)
    for block in (table, table[:, 0]):
        got = np.asarray(gather(jnp.asarray(block), jnp.asarray(uids)))
        np.testing.assert_array_equal(got[live], block[uids[live]])
        assert not got[~live].any()


@lru_cache(maxsize=None)
def _joins(n, tail):
    """``(join_live, a plain psum of the whole array)`` over ``n`` members
    of a ``data`` axis, each handed its own ``[_LK, *tail]`` addend."""
    from jax.sharding import Mesh, PartitionSpec as P

    m = Mesh(np.array(jax.devices()[:n]), ("data",))

    def over(fn):
        return jax.jit(jax.shard_map(
            lambda x, u: fn(x[0], u), mesh=m, in_specs=(P("data"), P()),
            out_specs=P(), check_vma=False))

    return (over(lambda x, u: sk.join_live(x, u, "data", 20000)),
            over(lambda x, u: jax.lax.psum(x, "data")))


@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("tail", [(), (32,), (64,)],
                         ids=["scalar", "d32", "d64"])
@pytest.mark.parametrize("branch", range(len(_RUNGS) + 1))
def test_join_live_is_a_psum_of_the_whole_array(branch, tail, n):
    """``join_live`` over 2 and 4 members against one ``psum`` of all K
    rows, bit for bit: on every rung of the ladder with a live prefix
    that ends exactly on it, and on the undeclared full-K branch (ids out
    of order), where rows may stand anywhere."""
    rng = np.random.default_rng(branch)
    ordered = branch < len(_RUNGS)
    count = _RUNGS[branch] if ordered else 600
    _, _, uids, _, _ = _live_case(rng, count, interleave=not ordered)
    _, got_branch = sk.live_plan(jnp.asarray(uids), 20000)
    assert int(got_branch) == branch
    x = rng.normal(size=(n, _LK) + tail).astype(np.float32)
    if ordered:
        x[:, count:] = 0
    join, plain = _joins(n, tail)
    got = np.asarray(join(jnp.asarray(x), jnp.asarray(uids)))
    assert got.shape == (_LK,) + tail
    np.testing.assert_array_equal(
        got, np.asarray(plain(jnp.asarray(x), jnp.asarray(uids))))
    assert got[:count].all()                       # the sum, not zeros


def test_forward_gather_reads_the_live_prefix(rng):
    """``_dedup_and_gather`` above the small-K floor: every slot ``inv``
    can name holds its table row, the slots behind the rung hold zeros,
    for a ``[V, d]`` and a ``w[V]`` table on one id stream."""
    from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer

    vocab, b, f = 30_000, 256, 39
    fids = rng.integers(0, 2500, size=(b, f)).astype(np.int32)
    params = {"v": jnp.asarray(rng.normal(size=(vocab, 3)), jnp.float32),
              "w": jnp.asarray(rng.normal(size=(vocab,)), jnp.float32)}
    _, _, batch2, uids, rows, _ = jax.jit(
        lambda p, bt: SparseTableCTRTrainer._dedup_and_gather(
            {"v": ("fids",), "w": ("fids",)}, p, bt)
    )(params, {"fids": jnp.asarray(fids)})
    u, inv = np.unique(fids.reshape(-1), return_inverse=True)
    rung = sk.ladder_slots(b * f, u.size)
    assert u.size <= rung < b * f
    np.testing.assert_array_equal(np.asarray(batch2["fids"]).reshape(-1), inv)
    for k in ("v", "w"):
        got = np.asarray(rows[k])
        assert got.shape == (b * f,) + params[k].shape[1:]
        np.testing.assert_array_equal(got[:u.size], np.asarray(params[k])[u])
        assert not got[rung:].any()


def test_trainer_counts_live_rows_and_rung_slots():
    """``trainer_apply_live_rows_total`` / ``trainer_apply_slots_total``:
    incremented from the live count and the branch the step reports in
    its health vector, per table, and printed as the live share by
    ``metrics_report --kernels``."""
    from lightctr_tpu import TrainConfig, obs
    from lightctr_tpu.models import fm
    from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer
    from lightctr_tpu.obs import health
    from tools import metrics_report

    vocab, b, f = 40_000, 256, 39                 # K = 9,984 ids a stream
    rng = np.random.default_rng(3)
    tr = SparseTableCTRTrainer(
        fm.init(jax.random.PRNGKey(0), vocab, 4), fm.logits,
        TrainConfig(learning_rate=0.05), sparse_tables={"w": ["fids"],
                                                        "v": ["fids"]},
        fused_fn=fm.logits_with_l2)
    tr.telemetry = obs.MetricsRegistry()
    tr.health = health.HealthMonitor(registry=obs.MetricsRegistry())
    health.ensure_trainer_detectors(tr.health, tables=True)
    live = slots = 0
    try:
        with obs.override(True):
            for hot in (300, 6000):
                fids = rng.integers(1, hot, size=(b, f)).astype(np.int32)
                tr.train_step({
                    "fids": fids,
                    "fields": np.tile(np.arange(f, dtype=np.int32), (b, 1)),
                    "vals": np.ones((b, f), np.float32),
                    "mask": np.ones((b, f), np.float32),
                    "labels": (rng.random(b) > 0.5).astype(np.float32)})
                n = int(np.unique(fids).size)
                live += n
                slots += sk.ladder_slots(b * f, n)
            tr.flush_health()
    finally:
        tr.health.close()
    counters = tr.telemetry.snapshot()["counters"]
    for table in ("w", "v"):
        assert counters[obs.labeled("trainer_apply_live_rows_total",
                                    table=table)] == live
        assert counters[obs.labeled("trainer_apply_slots_total",
                                    table=table)] == slots
    assert slots < 2 * b * f                      # the ladder engaged
    report = metrics_report.summarize_kernels(tr.telemetry.snapshot())
    assert report["apply"]["w"] == {
        "live_rows": live, "slots": slots, "scatter_slots": 2 * slots,
        "live_share": round(live / slots, 4), "scatters_per_slot": 2.0}
    # v[40000, 4] is kept in a fused store (r = 32): it counts lane rows
    # too, and its apply scatters once where w's pair scatters twice
    assert report["apply"]["w"]["scatters_per_slot"] == 2.0
    assert report["apply"]["v"]["scatters_per_slot"] == 1.0
    same = ("live_rows", "slots", "live_share")
    assert {k: report["apply"]["v"][k] for k in same} \
        == {k: report["apply"]["w"][k] for k in same}


# -- (b'') the fused store: a table and its accumulator in one lane row -----


@pytest.mark.parametrize("shape,shard,want", [
    ((4096, 32), None, 4), ((4096, 8), None, 16), ((4096, 4), None, 32),
    ((4096, 32), (1024, 32), 4),            # four row shards, whole lane rows
    ((4096, 64), None, 1),                  # r = 2: under MIN_LANE_PACK
    ((4096, 128), None, 1),                 # a row is a lane row already
    ((4096, 24), None, 1),                  # 24 does not divide 128
    ((4098, 32), None, 1),                  # V not a multiple of r
    ((4104, 32), (1026, 32), 1),            # a shard's rows are not
    ((4096, 32), (4096, 16), 1),            # columns sharded
    ((4096,), None, 1), ((64, 4, 8), None, 1),
])
def test_lane_pack_reads_shapes_only(shape, shard, want):
    assert sk.lane_pack(shape, shard) == want


@pytest.mark.parametrize("lane_rows", [48, 64, 100, 129],
                         ids=["one_chunk", "two_chunks", "tail", "odd_tail"])
def test_lane_fused_is_a_concatenate_and_reshape_made_in_chunks(
        lane_rows, monkeypatch):
    """``lane_fused`` is ``concatenate([table, accum], axis=1).reshape(-1,
    128)`` a half at a time — a fresh store has zeros in the other half, a
    store that is given keeps it — and ``lane_unfused`` reads either half
    back, also where the store is relaid a few lane rows at a time (on a
    TPU a whole ``[V, 32]`` reshape goes through a lane-padded copy)."""
    monkeypatch.setattr(sk, "_RELAYOUT_LANE_ROWS", 32)
    rng = np.random.default_rng(lane_rows)
    table = rng.normal(size=(lane_rows * 2, 32)).astype(np.float32)
    accum = rng.normal(size=(lane_rows * 2, 32)).astype(np.float32)
    fuse = partial(jax.jit, static_argnames=("pack", "half"))(sk.lane_fused)
    unfuse = partial(jax.jit, static_argnames=("pack", "half"))(
        sk.lane_unfused)
    store = fuse(jnp.asarray(table), pack=4, half=0)
    np.testing.assert_array_equal(
        np.asarray(store),
        np.concatenate([table, 0 * accum], axis=1).reshape(lane_rows, 128))
    store = fuse(jnp.asarray(accum), store, pack=4, half=1)
    np.testing.assert_array_equal(
        np.asarray(store),
        np.concatenate([table, accum], axis=1).reshape(lane_rows, 128))
    store = fuse(jnp.asarray(2 * table), store, pack=4, half=0)
    np.testing.assert_array_equal(np.asarray(unfuse(store, pack=4, half=0)),
                                  2 * table)
    np.testing.assert_array_equal(np.asarray(unfuse(store, pack=4, half=1)),
                                  accum)


def _fused(table, accum):
    """The fused store of a logical table and accumulator, by numpy."""
    return jnp.asarray(np.concatenate([table, accum], axis=1)
                       .reshape(-1, sk.LANES))


def _packed_case(name, d=32):
    """``(table, accum, uids, g, count)`` for a ``[V, d]`` table whose
    live ids share the fused store's lane rows — ``r = 64 // d`` ids in
    each — as the case says (a run longer than ``r`` is cut to it)."""
    r = sk.LANES // (2 * d)
    rng = np.random.default_rng(sum(map(ord, name)))
    k, vocab = _LK, 40_000

    def runs(lengths):
        """ids in runs of the given lengths, each run in a lane row of
        its own, lane rows ascending."""
        rows = np.sort(rng.choice(np.arange(1, vocab // r), size=len(lengths),
                                  replace=False))
        return np.concatenate([
            row * r + np.sort(rng.choice(r, size=n, replace=False))
            for row, n in zip(rows, lengths)])

    interleave = False
    if name.startswith("runs_of_"):
        ids = runs([min(r, int(name[-1]))] * 300)
    elif name == "mixed_runs":
        ids = runs(list(rng.integers(1, r + 1, size=500)))
    elif name == "live_id0":                 # lane row 0 whole, id 0 live
        ids = np.concatenate([np.arange(r), runs([2] * 50)])
    elif name.startswith("edge"):            # a rung's edge, and one past it
        n = _RUNGS[1] + int(name.endswith("+1"))
        ids = runs([r] * (n // r) + [n % r or r])[:n]
    elif name == "under_the_ladder":
        k, ids = 64, runs([min(r, 3), 1, r, 2])
    elif name == "unsorted":                 # the undeclared branch
        ids, interleave = runs([r] * 90 + [1] * 40), True
    uids = np.zeros(k, np.int32)
    g = np.zeros((k, d), np.float32)
    slots = np.arange(ids.size)
    if interleave:
        slots = np.concatenate([[0], np.sort(rng.choice(
            np.arange(1, k), size=ids.size - 1, replace=False))])
        half = ids.size // 2
        ids = np.concatenate([ids[half:], ids[:half]])
    uids[slots] = ids
    g[slots] = rng.normal(size=(ids.size, d))
    table = rng.uniform(-1, 1, size=(vocab, d)).astype(np.float32)
    table[::7] *= -0.0                        # signed zeros must survive
    accum = np.abs(rng.normal(size=(vocab, d))).astype(np.float32)
    return table, accum, uids, g, ids.size


_PACKED_CASES = ["runs_of_1", "runs_of_2", "runs_of_3", "runs_of_4",
                 "mixed_runs", "live_id0", f"edge{_RUNGS[1]}",
                 f"edge{_RUNGS[1]}+1", "under_the_ladder", "unsorted"]


@pytest.mark.parametrize("half", [0, 1], ids=["rows", "accumulator"])
@pytest.mark.parametrize("d", [32, 8])
@pytest.mark.parametrize("case", _PACKED_CASES)
def test_packed_gather_is_take_on_the_logical_table(case, d, half):
    """``gather_live`` over the fused ``[2V // r, 128]`` store against
    ``jnp.take`` on the logical ``[V, d]`` table (``half`` 0: the forward
    pass's read) and accumulator (1: the apply's): every live slot its
    row, zeros behind the rung, and with ``zero_pads`` zeros in the pads
    inside it too."""
    table, accum, uids, _, count = _packed_case(case, d)
    r = sk.LANES // d
    k = uids.size
    idx, branch = sk.live_plan(jnp.asarray(uids), table.shape[0])
    live = (uids != 0) | (np.arange(k) == 0)
    rung = k if case == "unsorted" else sk.ladder_slots(k, count)
    assert int(branch) == (len(sk.apply_ladder(k)) if case == "unsorted"
                           else sk.apply_ladder(k).index(rung))
    want = np.asarray(jnp.take(jnp.asarray((table, accum)[half]),
                               jnp.asarray(uids), axis=0))
    for zero_pads in (False, True):
        got = np.asarray(jax.jit(partial(
            sk.gather_live, zero_pads=zero_pads, pack=r, half=half))(
                _fused(table, accum), idx, branch))
        assert got.shape == (k, d)
        np.testing.assert_array_equal(got[live].view(np.uint32),
                                      want[live].view(np.uint32))
        assert not got[rung:].any()
        if zero_pads:
            assert not got[~live].any()


def _fused_apply_cases():
    """(case, d, shards): every id layout at d = 32 on one device and
    per row shard; the other widths the rule packs on the layouts that
    share lane rows most."""
    out = [pytest.param(c, 32, shards,
                        id=f"{c}-{'embed2' if shards else 'one_device'}")
           for c in _PACKED_CASES for shards in (0, 2)]
    out += [pytest.param(c, d, shards,
                         id=f"{c}-d{d}-{'embed2' if shards else 'one_device'}")
            for d in (4, 8, 16) for shards in (0, 2)
            for c in ("mixed_runs", "live_id0", "unsorted")]
    return out


@pytest.mark.parametrize("case,d,shards", _fused_apply_cases())
def test_packed_apply_matches_sparse_adagrad_update(case, d, shards):
    """``merge_apply`` over a fused store — ONE scatter for table and
    accumulator — against ``segment_sum -> sparse_adagrad_update`` on the
    logical pair: TABLE AND ACCUMULATOR TO THE BIT (the same float adds
    per element), and every logical row the batch did not touch
    bit-identical, signed zeros included, though the live ids of its lane
    row were written: over shared lane rows, a live id 0, pads, a rung's
    edge, K under the ladder, ids out of order (the undeclared branch), d
    in {4, 8, 16, 32}, and per row shard inside a ``shard_map``."""
    from lightctr_tpu.embed.table import SparseAdagradState, \
        sparse_adagrad_update

    table, accum, uids, g, count = _packed_case(case, d)
    r, lr, eps = sk.LANES // d, 0.1, 1e-7
    vocab, k = table.shape[0], uids.size
    pad = (uids == 0) & (np.arange(k) > 0)
    # the gradient arrives unmerged, every slot's row in two addends
    parts = np.stack([0.25 * g, 0.75 * g]).reshape(2 * k, d)
    inv = np.tile(np.arange(k, dtype=np.int32), 2)
    merged = jax.ops.segment_sum(jnp.asarray(parts), jnp.asarray(inv),
                                 num_segments=k)
    w0, st = jax.jit(partial(sparse_adagrad_update, lr=lr, eps=eps))(
        jnp.asarray(table), SparseAdagradState(accum=jnp.asarray(accum)),
        jnp.asarray(uids), merged * ~pad[:, None])

    def fn(store, u, rows, inv, axis=None):
        out = sk.merge_apply(store, None, u, rows, inv, lr=lr, eps=eps,
                             shard_axis=axis, pack=r)
        assert out[1] is None                 # the store holds its own
        return out[0], out[2]

    if shards:
        from jax.sharding import Mesh, PartitionSpec as P

        m = Mesh(np.array(jax.devices()[:shards]), ("embed",))
        fn = jax.shard_map(
            partial(fn, axis="embed"), mesh=m,
            in_specs=(P("embed"), P(), P(), P()),
            out_specs=(P("embed"), P()))
    s1, ss = jax.jit(fn)(_fused(table, accum), jnp.asarray(uids),
                         jnp.asarray(parts), jnp.asarray(inv))
    assert s1.shape == (2 * vocab // r, sk.LANES)
    s1 = np.asarray(s1).reshape(vocab, 2 * d)
    w1, a1 = s1[:, :d], s1[:, d:]
    np.testing.assert_array_equal(w1.view(np.uint32),
                                  np.asarray(w0).view(np.uint32))
    np.testing.assert_array_equal(a1.view(np.uint32),
                                  np.asarray(st.accum).view(np.uint32))
    np.testing.assert_allclose(
        float(ss), float(np.sum(np.square(np.asarray(merged), dtype=np.float64))),
        rtol=1e-5)
    live = uids[~pad]
    assert live.size == count
    assert (w1[live] != table[live]).any(axis=1).all()     # every one trained
    untouched = np.setdiff1d(np.arange(vocab), live)
    np.testing.assert_array_equal(w1[untouched].view(np.uint32),
                                  table[untouched].view(np.uint32))
    np.testing.assert_array_equal(a1[untouched].view(np.uint32),
                                  accum[untouched].view(np.uint32))


def test_trainer_counts_lane_rows():
    """``trainer_apply_lane_rows_total``: the lane rows the fused store's
    apply writes (two ids each at d = 32), counted in the step and read
    off its health vector; only a table in a fused store counts them, and
    ``metrics_report --kernels`` prints their share of the live rows."""
    from lightctr_tpu import TrainConfig, obs
    from lightctr_tpu.models import fm
    from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer
    from lightctr_tpu.obs import health
    from tools import metrics_report

    vocab, b, f, d = 4096, 64, 8, 32
    rng = np.random.default_rng(5)
    tr = SparseTableCTRTrainer(
        fm.init(jax.random.PRNGKey(0), vocab, d), fm.logits,
        TrainConfig(learning_rate=0.05),
        sparse_tables={"w": ["fids"], "v": ["fids"]},
        fused_fn=fm.logits_with_l2)
    assert tr._lane_pack == {"v": 4}
    tr.telemetry = obs.MetricsRegistry()
    tr.health = health.HealthMonitor(registry=obs.MetricsRegistry())
    health.ensure_trainer_detectors(tr.health, tables=True)
    live = lane_rows = 0
    try:
        with obs.override(True):
            for hot in (vocab, 96):           # hardly shared, then mostly
                fids = rng.integers(0, hot, size=(b, f)).astype(np.int32)
                tr.train_step({
                    "fids": fids,
                    "fields": np.tile(np.arange(f, dtype=np.int32), (b, 1)),
                    "vals": np.ones((b, f), np.float32),
                    "mask": np.ones((b, f), np.float32),
                    "labels": (rng.random(b) > 0.5).astype(np.float32)})
                live += np.unique(fids).size
                lane_rows += np.unique(fids // 2).size
            tr.flush_health()
    finally:
        tr.health.close()
    counters = tr.telemetry.snapshot()["counters"]
    assert counters[obs.labeled("trainer_apply_lane_rows_total",
                                table="v")] == lane_rows
    assert obs.labeled("trainer_apply_lane_rows_total",
                       table="w") not in counters
    assert lane_rows < live
    report = metrics_report.summarize_kernels(tr.telemetry.snapshot())
    assert report["apply"]["v"]["lane_rows"] == lane_rows
    assert report["apply"]["v"]["lane_row_share"] == round(lane_rows / live, 4)
    assert "lane_rows" not in report["apply"]["w"]


def test_aot_step_report_knows_a_packed_leaf():
    """``tools/aot_step.summarize_hlo`` finds copies and scatters of a
    table by the shape the step holds it in — ``[2V // r, 128]`` for a
    fused store — and ``aliased_parameters`` reads off the module's
    header which parameters the step writes in place."""
    from tools import aot_step

    text = """HloModule jit_step, is_scheduled=true, input_output_alias={ {0}: (0, {}, may-alias), {2}: (3, {}, may-alias) }, entry_computation_layout={(f32[2048,128]{1,0:T(8,128)})->f32[2048,128]{1,0:T(8,128)}}

%fused_scatter (p0: f32[2048,128], p1: s32[64], p2: f32[64,128]) -> f32[2048,128] {
  %p0 = f32[2048,128]{1,0:T(8,128)} parameter(0)
  ROOT %scatter.1 = f32[2048,128]{1,0:T(8,128)} scatter(%p0, %p1, %p2), to_apply=%add
}

ENTRY %main.1 (a: f32[2048,128]) -> f32[2048,128] {
  %a = f32[2048,128]{1,0:T(8,128)} parameter(0)
  %copy.7 = f32[2048,128]{1,0:T(8,128)} copy(%a), metadata={op_name="jit(step)/sparse_tables/apply/copy"}
  ROOT %fusion.3 = f32[2048,128]{1,0:T(8,128)} fusion(%copy.7, %i, %u), kind=kCustom, calls=%fused_scatter, metadata={op_name="jit(step)/sparse_tables/apply/scatter-add"}
}
"""
    report = aot_step.summarize_hlo(text, [(2048, 128)])
    assert [c["name"] for c in report["table_copies"]] == ["copy.7"]
    assert [c["name"] for c in report["scatters"]] == ["fusion.3"]
    assert not aot_step.summarize_hlo(text, [(8192, 32)])["table_copies"]
    assert aot_step.aliased_parameters(text) == {0, 3}
    assert aot_step.aliased_parameters(text.split("\n", 1)[1]) == set()


# -- (c) quantize pack: bit-identical codes ------------------------------


def test_quantize_pack_bit_identical_to_codec(rng):
    x = (3.0 * rng.normal(size=(57, 9))).astype(np.float32)
    for mode in ("uniform", "log"):
        t = quantize.build_table(-2.0, 2.0, bits=8, mode=mode)
        want = quantize.compress(t, jnp.asarray(x))
        got = sk.KERNELS["quantize_pack"].pallas(t, jnp.asarray(x),
                                                 interpret=True)
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=mode)


def test_quantize_pack_wide_tables_take_the_reference(monkeypatch):
    """Codes wider than 8 bits keep the XLA form on a TPU too (a static
    rule on the table, counted as ``xla``): the compare-count sweep would
    pay 2^bits compares per element."""
    from lightctr_tpu import obs

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sk.resolve_impl("quantize_pack") == "pallas"
    reg = obs.default_registry()
    key = obs.labeled("trainer_kernel_path_total", phase="pack", impl="xla")
    before = reg.snapshot()["counters"].get(key, 0)
    t = quantize.build_table(-1.0, 1.0, bits=16)
    x = jnp.asarray(np.linspace(-1.5, 1.5, 31, dtype=np.float32))
    got = sk.quantize_pack(t, x)
    assert got.dtype == jnp.uint16
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(quantize.compress(t, x)))
    assert reg.snapshot()["counters"].get(key, 0) == before + 1


def test_quantize_pack_ef_update_folds_the_residual_scatter(rng):
    """The EF pack with the carry update: codes, decoded view AND the
    written-back residual are bit-identical to the chain written out
    here — gather / compensate / ``quantize.compress`` / ``extract`` /
    scatter — including a real id 0 at slot 0, padded repeats that must
    leave their carry untouched, and untouched rows that must keep
    theirs."""
    t = quantize.build_table(-1.0, 1.0, bits=8)
    vocab, dim, s = 96, 5, 24
    u = np.unique(rng.integers(1, vocab, 17)).astype(np.int32)
    uids = np.zeros(s, np.int32)
    uids[:u.size] = u
    rows = (0.6 * rng.normal(size=(s, dim))).astype(np.float32)
    rows[u.size:] = 0.0
    residual = (0.2 * rng.normal(size=(vocab, dim))).astype(np.float32)
    for real_id0 in (False, True):
        if real_id0:
            # the dedup convention with a REAL id 0: sorted unique ids
            # (0 first), pads repeat id 0 beyond the real entries
            reals = np.sort(np.concatenate([[0], u[:12]])).astype(np.int32)
            uu = np.zeros(s, np.int32)
            uu[:reals.size] = reals
            rr = rows.copy()
            rr[reals.size:] = 0.0
        else:
            uu, rr = uids, rows
        mask = (~((uu == 0) & (np.arange(s) > 0))).astype(
            np.float32).reshape(-1, 1)
        carried = residual[uu]
        val = rr + carried * mask
        c0 = quantize.compress(t, jnp.asarray(val))
        d0 = np.asarray(quantize.extract(t, c0))
        r0 = residual.copy()
        np.add.at(r0, uu, (val - d0 - carried) * mask)
        c1, r1, d1 = sk.quantize_pack_ef_update(
            t, jnp.asarray(rr), jnp.asarray(uu), jnp.asarray(residual),
            jnp.asarray(mask))
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c0))
        np.testing.assert_array_equal(np.asarray(r1), r0)
        np.testing.assert_array_equal(np.asarray(d1), d0)
        untouched = np.setdiff1d(np.arange(vocab), uu)
        np.testing.assert_array_equal(np.asarray(r1)[untouched],
                                      residual[untouched])


def test_quantize_pack_ef_bit_identical(rng):
    """EF-folded pack: codes AND the fresh-error delta match the
    reference compensate/encode/decode/error chain bitwise."""
    t = quantize.build_table(-1.0, 1.0, bits=8)
    rows = (2.5 * rng.normal(size=(33, 4))).astype(np.float32)
    carried = (0.3 * rng.normal(size=(33, 4))).astype(np.float32)
    mask = (rng.random((33, 1)) > 0.25).astype(np.float32)
    args = (t, jnp.asarray(rows), jnp.asarray(carried), jnp.asarray(mask))
    c0, d0 = sk.KERNELS["quantize_pack_ef"].reference(*args)
    c1, d1 = sk.KERNELS["quantize_pack_ef"].pallas(*args, interpret=True)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c0))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d0))


def test_quantize_pack_packed_nibble_bit_parity(rng):
    """The sub-byte wire form (ISSUE 15): a 4-bit table's packed bytes
    carry TWO codes per byte, unpack back to exactly the reference
    codec's codes (``quantize.compress``), decode to exactly the
    reference's values, and weigh exactly what the cost model prices
    (``_wire_row_bytes(dim, 4)`` per row) — even and odd row widths,
    the odd tail's pad nibble sliced back off."""
    from lightctr_tpu.dist.collectives import _wire_row_bytes
    from lightctr_tpu.ops.quantize import pack_nibbles, unpack_nibbles

    t4 = quantize.build_table(-1.0, 1.0, bits=4)
    for n_rows, dim in ((32, 8), (17, 5)):
        x = jnp.asarray(
            (1.5 * rng.normal(size=(n_rows, dim))).astype(np.float32))
        codes = quantize.compress(t4, x)
        packed = sk.quantize_pack_packed(t4, x)
        assert packed.dtype == jnp.uint8
        assert packed.size == n_rows * dim // 2 + (n_rows * dim) % 2
        # the cost model prices per ROW (frames pack row-major, one pad
        # nibble at most per frame — n_rows * per_row bounds it)
        assert packed.size <= n_rows * _wire_row_bytes(dim, 4)
        got = unpack_nibbles(packed, n_rows * dim).reshape(n_rows, dim)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(codes))
        np.testing.assert_array_equal(
            np.asarray(quantize.extract(t4, got)),
            np.asarray(quantize.extract(t4, codes)))
    # wider tables pass through unpacked (one code per byte)
    t8 = quantize.build_table(-1.0, 1.0, bits=8)
    x = jnp.asarray(rng.normal(size=(8, 8)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(sk.quantize_pack_packed(t8, x)),
        np.asarray(sk.quantize_pack(t8, x)))


def test_pack_nibbles_round_trip_orders(rng):
    """Little-nibble order: the EVEN element rides the low nibble —
    pinned so both wire ends agree byte-for-byte."""
    from lightctr_tpu.ops.quantize import pack_nibbles, unpack_nibbles

    codes = jnp.asarray(np.array([1, 15, 0, 7, 9], np.uint8))
    packed = np.asarray(pack_nibbles(codes))
    np.testing.assert_array_equal(
        packed, np.array([1 | (15 << 4), 0 | (7 << 4), 9], np.uint8))
    np.testing.assert_array_equal(
        np.asarray(unpack_nibbles(jnp.asarray(packed), 5)),
        np.array([1, 15, 0, 7, 9], np.uint8))


# -- the registry: the kernels with two implementations --------------------


def test_dispatch_counts_kernel_path(rng):
    from lightctr_tpu import obs

    reg = obs.default_registry()
    key = obs.labeled("trainer_kernel_path_total", phase="pack", impl="xla")
    before = reg.snapshot()["counters"].get(key, 0)
    sk.quantize_pack(quantize.build_table(-1.0, 1.0, bits=8),
                     jnp.asarray(rng.normal(size=16).astype(np.float32)))
    after = reg.snapshot()["counters"].get(key, 0)
    assert after == before + 1


def test_registry_contract(monkeypatch):
    """Every registered kernel declares BOTH impls, a known phase, and a
    pallas impl that accepts interpret= (the CPU parity path); the pick
    is the backend's: the XLA form here, Pallas on a TPU."""
    import inspect

    import lightctr_tpu.nn.flash_attention    # noqa: F401 (self-registers)

    assert set(sk.KERNELS) == {"quantize_pack", "quantize_pack_ef",
                               "flash_attention"}
    for name, kd in sk.KERNELS.items():
        assert kd.phase in sk.KERNEL_PHASES, name
        assert callable(kd.reference) and callable(kd.pallas), name
        sig = inspect.signature(kd.pallas)
        assert "interpret" in sig.parameters, (
            f"{name}: pallas impl must accept interpret= for the CPU "
            "parity path")
        assert sk.resolve_impl(name) == "xla"    # the virtual CPU mesh
    with pytest.raises(KeyError):
        sk.resolve_impl("dedup_ids")             # one implementation: no pick
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert {sk.resolve_impl(name) for name in sk.KERNELS} == {"pallas"}
