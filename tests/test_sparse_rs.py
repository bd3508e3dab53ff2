"""Sparse collectives v2 (ISSUE 5): the owner-partitioned reduce-scatter
exchange (`sparse_reduce_scatter`), the three-way trace-time algorithm pick
(`pick_exchange_algo`), shared batch-field id streams, the host-side
capacity check + allgather fallback, and error feedback for clipped
fixed-range sparse payloads — on the 8-device virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np

from lightctr_tpu import TrainConfig
from lightctr_tpu.core.mesh import MeshSpec, make_mesh
from lightctr_tpu.dist import (
    LinkBandwidth,
    dense_ring_bytes,
    expected_union,
    hier_exchange_bytes,
    hier_wire_bytes,
    pick_exchange_algo,
    rs_default_caps,
    rs_fits,
    sparse_all_reduce,
    sparse_ef_residual_init,
    sparse_exchange_bytes,
    sparse_reduce_scatter,
    sparse_rs_bytes,
)
from lightctr_tpu.dist.collectives import rs_owner_partition, rs_scatter_rows
from lightctr_tpu.models import fm
from lightctr_tpu.models.ctr_trainer import CTRTrainer
from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer

N = 8  # conftest pins 8 virtual CPU devices; sub-meshes use the first k


def dense_scatter(vocab, dim, uids, rows):
    """Reference oracle: the [vocab, dim] array a (uids, rows) pair denotes
    under .add scatter semantics."""
    out = np.zeros((vocab, dim), np.float32)
    np.add.at(out, np.asarray(uids).reshape(-1),
              np.asarray(rows).reshape(-1, dim))
    return out


def convention_pairs(rng, n, vocab, k, dim, lo=1):
    """Per-member (uids, rows) following the dedup convention: sorted
    unique ids, trailing slots padded with id 0 + zero rows."""
    uids = np.zeros((n, k), np.int64)
    rows = np.zeros((n, k, dim), np.float32)
    for m in range(n):
        u = np.unique(rng.integers(lo, vocab, size=k))
        uids[m, :u.size] = u
        rows[m, :u.size] = rng.normal(size=(u.size, dim))
    return uids, rows


# -- reduce-scatter collective ------------------------------------------


def test_reduce_scatter_parity_world_sizes(rng):
    """The acceptance parity: the rs exchange equals the dense mean (psum
    semantics) on world sizes 2, 4 and 8, every member holding the
    identical merged result."""
    for n in (2, 4, 8):
        mesh = make_mesh(MeshSpec(data=n))
        vocab, k, dim = 256, 32, 5
        uids, rows = convention_pairs(rng, n, vocab, k, dim)
        gu, merged, over = sparse_reduce_scatter(
            mesh, jnp.asarray(uids), jnp.asarray(rows),
            bucket_cap=k, shard_cap=min(n * k, vocab // n + 2),
        )
        assert int(np.asarray(over).sum()) == 0
        want = sum(dense_scatter(vocab, dim, uids[m], rows[m])
                   for m in range(n)) / n
        got = dense_scatter(vocab, dim, np.asarray(gu)[0],
                            np.asarray(merged)[0])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(
            np.asarray(gu), np.tile(np.asarray(gu)[:1], (n, 1))
        )
        np.testing.assert_allclose(
            np.asarray(merged),
            np.tile(np.asarray(merged)[:1], (n, 1, 1)), rtol=0, atol=0,
        )


def test_reduce_scatter_duplicate_id_merge(rng):
    """Ids shared by MANY members (a hot pool) merge at the owner exactly
    once each — the owner-side segment_sum counterpart of the allgather
    variant's duplicate-key merge."""
    mesh = make_mesh(MeshSpec(data=N))
    vocab, k, dim = 64, 16, 3
    uids, rows = convention_pairs(rng, N, 32, k, dim)  # heavy overlap
    gu, merged, over = sparse_reduce_scatter(
        mesh, jnp.asarray(uids), jnp.asarray(rows),
        bucket_cap=k, shard_cap=N * k, average=False,
    )
    assert int(np.asarray(over).sum()) == 0
    want = sum(dense_scatter(vocab, dim, uids[m], rows[m]) for m in range(N))
    got = dense_scatter(vocab, dim, np.asarray(gu)[0], np.asarray(merged)[0])
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_reduce_scatter_padding_noop_with_real_id0(rng):
    """Padded slots (repeated id 0, zero rows) contribute nothing and eat
    no bucket capacity — including when id 0 is a REAL touched id on one
    member (slot 0, the dedup convention)."""
    mesh = make_mesh(MeshSpec(data=N))
    vocab, k, dim = 64, 8, 3
    uids = np.zeros((N, k), np.int64)
    rows = np.zeros((N, k, dim), np.float32)
    rows[0, 0] = 1.0  # member 0: a real id-0 row plus pure padding
    for m in range(1, N):
        uids[m, 0], uids[m, 1] = 2 * m, 2 * m + 1
        rows[m, 0], rows[m, 1] = m, -m
    gu, merged, over = sparse_reduce_scatter(
        mesh, jnp.asarray(uids), jnp.asarray(rows),
        bucket_cap=2, shard_cap=6, average=False,
    )
    # tiny bucket_cap: pads MUST have been dropped or they would overflow
    assert int(np.asarray(over).sum()) == 0
    want = sum(dense_scatter(vocab, dim, uids[m], rows[m]) for m in range(N))
    got = dense_scatter(vocab, dim, np.asarray(gu)[0], np.asarray(merged)[0])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)


def test_reduce_scatter_compressed_payload(rng):
    """Quantile-coded rs payload (two single-shot encodes: buckets +
    merged shards) stays within a few buckets of exact."""
    mesh = make_mesh(MeshSpec(data=N))
    vocab, k, dim = 128, 16, 4
    uids, rows = convention_pairs(rng, N, vocab, k, dim)
    exact = sparse_reduce_scatter(
        mesh, jnp.asarray(uids), jnp.asarray(rows),
        bucket_cap=k, shard_cap=N * k,
    )
    coded = sparse_reduce_scatter(
        mesh, jnp.asarray(uids), jnp.asarray(rows),
        bucket_cap=k, shard_cap=N * k,
        compress_bits=16, compress_range="dynamic",
    )
    np.testing.assert_array_equal(np.asarray(coded[0]), np.asarray(exact[0]))
    np.testing.assert_allclose(
        np.asarray(coded[1]), np.asarray(exact[1]), rtol=0, atol=1e-3
    )


def test_owner_partition_round_trip(rng):
    """rs_owner_partition + rs_scatter_rows reconstruct the input multiset
    exactly: every bucket entry is owned by its destination (uid % n), and
    the scattered (ids, rows) denote the same dense array as the input."""
    n, vocab, k, dim = 4, 64, 24, 3
    u = np.unique(rng.integers(1, vocab, size=k))
    uids = np.zeros(k, np.int64)
    rows = np.zeros((k, dim), np.float32)
    uids[:u.size] = u
    rows[:u.size] = rng.normal(size=(u.size, dim))
    dest, order, bucket_ids, over = jax.jit(
        rs_owner_partition, static_argnums=(1, 2)
    )(jnp.asarray(uids), n, k)
    assert int(over) == 0
    bucket_rows = rs_scatter_rows(jnp.asarray(rows), dest, order, n, k)
    b_ids = np.asarray(bucket_ids)
    b_rows = np.asarray(bucket_rows)
    # ownership: every real entry sits in the bucket of its modulo owner
    for d in range(n):
        nz = b_ids[d][np.any(b_rows[d] != 0, axis=-1)]
        assert (nz % n == d).all()
    got = dense_scatter(vocab, dim, b_ids.reshape(-1),
                        b_rows.reshape(-1, dim))
    want = dense_scatter(vocab, dim, uids, rows)
    np.testing.assert_allclose(got, want, rtol=0, atol=0)
    # an undersized bucket reports the overflowed entries instead of
    # silently dropping them unannounced
    *_, over2 = jax.jit(rs_owner_partition, static_argnums=(1, 2))(
        jnp.asarray(uids), n, 2
    )
    counts = np.bincount(u % n, minlength=n)
    assert int(over2) == int(np.maximum(counts - 2, 0).sum())


def test_rs_fits_predicts_overflow():
    """The host-side capacity check matches the in-jit overflow counter:
    fits=True streams run overflow-free, a skewed stream (every id owned
    by one member) is rejected."""
    n = 4
    good = [np.arange(1, 9) + 8 * m for m in range(n)]
    assert rs_fits(good, n, bucket_cap=4, shard_cap=16)
    skew = [np.arange(1, 9) * n for _ in range(n)]  # all ids ≡ 0 (mod n)
    assert not rs_fits(skew, n, bucket_cap=4, shard_cap=16)
    # shard bound: disjoint members, per-owner union exceeds the cap
    wide = [np.arange(1, 40) + 40 * m for m in range(n)]
    assert not rs_fits(wide, n, bucket_cap=40, shard_cap=10)


def test_cost_model_matches_payload_shapes_and_pick_crossover():
    """The three-way pick agrees with the bytes derived from the ACTUAL
    payload shapes each collective ships (the bench's accounting), across
    the (density x world) grid and on both sides of every crossover."""
    vocab, dim = 2048, 16
    for n in (2, 4, 8):
        for density in (0.05, 0.25, 0.5, 1.0):
            k = max(1, int(vocab * density))
            # allgather payload: (n-1) forwarded segments of K int32 ids
            # + [K, dim] fp32 rows
            ag_measured = (n - 1) * (4 * k + 4 * k * dim)
            assert sparse_exchange_bytes(n, k, dim) == ag_measured
            # rs payload: (n-1) ppermute hops of one [bucket_cap] +
            # [bucket_cap, dim] bucket, then (n-1) all_gather segments of
            # one [shard_cap] + [shard_cap, dim] merged shard
            bucket, shard = rs_default_caps(n, k, vocab)
            rs_measured = (n - 1) * ((4 + 4 * dim) * bucket
                                     + (4 + 4 * dim) * shard)
            assert sparse_rs_bytes(n, bucket, shard, dim) == rs_measured
            algo, b = pick_exchange_algo(n, k, vocab, dim)
            table = {
                "sparse": ag_measured,
                "sparse_rs": rs_measured,
                "dense": dense_ring_bytes(vocab, dim, n),
            }
            assert b == table[algo]
            assert b == min(table.values()), (n, density, algo, table)
    # the modeled crossover exists: at fixed density the allgather grows
    # with n while rs saturates, so rs must win for large enough worlds
    k = vocab // 2
    assert pick_exchange_algo(2, k, vocab, dim)[0] == "sparse"
    assert pick_exchange_algo(8, k, vocab, dim)[0] == "sparse_rs"
    # rs hysteresis vs dense: a near-tie on bytes (the 2^14 bench cell —
    # rs 1.0006x the dense ring, measurably slower wall-clock) must stay
    # on the worst-case-safe dense path, not flip for a marginal edge
    algo, b = pick_exchange_algo(8, 9984, 1 << 14, 16)
    assert algo == "dense", (algo, b)
    assert pick_exchange_algo(8, 9984, 1 << 14, 16, rs_margin=1.0)[0] \
        == "sparse_rs"


# -- bandwidth-aware cost model: the four-way pick (ISSUE 10) ------------


def test_cost_model_hier_predicted_bytes_match_payload_shapes():
    """The hierarchical branch's returned bytes equal the bytes derived
    from the payload shapes the exchange actually ships: push the
    expected local union + pull the expected global union, each entry an
    int32 id + dim fp32 values (fp16 with wire_bits=16) — the same
    helper-level contract the PR 5 cost-model test pins for the flat
    algorithms."""
    vocab, dim, local_n, n = 4096, 16, 8, 16
    for k in (256, 2048):
        k_out = expected_union(k, vocab, local_n)
        k_in = expected_union(k, vocab, n)
        manual = (k_out + k_in) * (4 + 4 * dim)
        assert hier_wire_bytes(k_out, k_in, dim) == manual
        assert hier_wire_bytes(k_out, k_in, dim, wire_bits=16) == \
            (k_out + k_in) * (4 + 2 * dim)
        local_algo, local_b, wire_b = hier_exchange_bytes(
            local_n, n // local_n, k, vocab, dim
        )
        assert wire_b == manual
        assert local_b == {
            "sparse": sparse_exchange_bytes(local_n, k, dim),
            "sparse_rs": sparse_rs_bytes(
                local_n, *rs_default_caps(local_n, k, vocab), dim),
        }[local_algo]
        # a DCN slow enough that the wire dominates: the pick takes hier
        # and returns exactly the wire bytes
        algo, b = pick_exchange_algo(
            n, k, vocab, dim, local_n=local_n,
            bw=LinkBandwidth(4e9, 1e7, "env"),
        )
        assert (algo, b) == ("hier", wire_b)
        # the CODED wire (ISSUE 13): wire_bits=8 prices one byte per
        # value — the same payload-shape invariant, and the pick's
        # returned bytes are exactly the coded model's
        coded_manual = (k_out + k_in) * (4 + 1 * dim)
        assert hier_wire_bytes(k_out, k_in, dim, wire_bits=8) == \
            coded_manual
        _, _, coded_wire_b = hier_exchange_bytes(
            local_n, n // local_n, k, vocab, dim, wire_bits=8,
        )
        assert coded_wire_b == coded_manual
        algo, b = pick_exchange_algo(
            n, k, vocab, dim, local_n=local_n, wire_bits=8,
            bw=LinkBandwidth(4e9, 1e7, "env"),
        )
        assert (algo, b) == ("hier", coded_manual)
        # the SUB-BYTE wire (ISSUE 15): wire_bits=4 prices two codes per
        # byte, odd dims round up — exactly len(pack_nibbles(codes)) per
        # row (the payload-shape test in test_sparse_kernels.py pins the
        # codec side of the same byte count)
        for d4 in (dim, dim + 1):  # even and odd row widths
            nib_manual = (k_out + k_in) * (4 + (d4 + 1) // 2)
            assert hier_wire_bytes(k_out, k_in, d4, wire_bits=4) == \
                nib_manual
            _, _, nib_wire_b = hier_exchange_bytes(
                local_n, n // local_n, k, vocab, d4, wire_bits=4,
            )
            assert nib_wire_b == nib_manual
        algo, b = pick_exchange_algo(
            n, k, vocab, dim, local_n=local_n, wire_bits=4,
            bw=LinkBandwidth(4e9, 1e7, "env"),
        )
        assert (algo, b) == (
            "hier", hier_wire_bytes(k_out, k_in, dim, wire_bits=4))


def test_cost_model_crossover_in_bandwidth_ratio():
    """Synthetic ICI/DCN sweeps: with the DCN the bottleneck the pick
    aggregates before the slow link (hier); as the DCN approaches and
    passes the ICI the flat single-fabric algorithm wins back.  The flip
    is monotone — exactly one crossover along the sweep."""
    vocab, dim, local_n, n, k = 4096, 16, 8, 16, 2048
    ici = 4e9
    picks = []
    for dcn in (1e7, 1e8, 1e9, 4e9, 1e10, 4e10, 1e12):
        algo, _ = pick_exchange_algo(
            n, k, vocab, dim, local_n=local_n,
            bw=LinkBandwidth(ici, dcn, "env"),
        )
        picks.append(algo)
    assert picks[0] == "hier", picks
    assert picks[-1] != "hier", picks
    flips = sum(1 for a, b_ in zip(picks, picks[1:]) if a != b_)
    assert flips == 1, picks
    # single-fabric form unchanged: local_n None/==n is the byte pick
    flat = pick_exchange_algo(n, k, vocab, dim)
    assert pick_exchange_algo(n, k, vocab, dim, local_n=n) == flat
    assert flat[0] in ("sparse", "sparse_rs", "dense")
    import pytest

    with pytest.raises(ValueError, match="whole number"):
        pick_exchange_algo(n, k, vocab, dim, local_n=5,
                           bw=LinkBandwidth(1e9, 1e8, "env"))


def test_cost_model_hysteresis_never_flaps():
    """The incumbent-pick hysteresis: around the crossover bandwidth, a
    re-probe jittering a few percent must not flip the decision in either
    direction — a flapping per-table pick re-traces the whole step
    program."""
    vocab, dim, local_n, n, k = 4096, 16, 8, 16, 2048
    ici = 4e9

    def pick_at(dcn, prev=None):
        return pick_exchange_algo(
            n, k, vocab, dim, local_n=local_n,
            bw=LinkBandwidth(ici, dcn, "env"), prev=prev,
        )[0]

    # locate the crossover by bisection (prev-free picks)
    lo, hi = 1e7, 1e12
    assert pick_at(lo) == "hier" and pick_at(hi) != "hier"
    for _ in range(60):
        mid = (lo * hi) ** 0.5
        if pick_at(mid) == "hier":
            lo = mid
        else:
            hi = mid
    boundary = (lo * hi) ** 0.5
    # at the boundary, whatever the incumbent is it KEEPS the pick under
    # +-10% probe jitter — in both directions
    for prev in (pick_at(lo), pick_at(hi)):
        for jitter in (0.9, 0.95, 1.0, 1.05, 1.1):
            assert pick_at(boundary * jitter, prev=prev) == prev, (
                prev, jitter,
            )
    # hysteresis does not trap the pick forever: far from the boundary
    # the challenger's win clears PICK_FLAP_MARGIN and the pick moves
    assert pick_at(1e7, prev=pick_at(hi)) == "hier"
    assert pick_at(1e12, prev="hier") != "hier"

    # the CODED wire (ISSUE 13): an 8-bit wire moves the crossover (the
    # hier candidate got ~4x cheaper on the DCN) but the hystereses keep
    # it exactly as flap-free — re-run the whole boundary drill at
    # wire_bits=8
    def pick_coded(dcn, prev=None):
        return pick_exchange_algo(
            n, k, vocab, dim, local_n=local_n, wire_bits=8,
            bw=LinkBandwidth(ici, dcn, "env"), prev=prev,
        )[0]

    lo, hi = 1e7, 1e12
    assert pick_coded(lo) == "hier" and pick_coded(hi) != "hier"
    for _ in range(60):
        mid = (lo * hi) ** 0.5
        if pick_coded(mid) == "hier":
            lo = mid
        else:
            hi = mid
    boundary_c = (lo * hi) ** 0.5
    assert boundary_c > boundary, (
        "the cheaper coded wire must extend hier's winning regime to "
        "faster DCNs", boundary, boundary_c,
    )
    for prev in (pick_coded(lo), pick_coded(hi)):
        for jitter in (0.9, 0.95, 1.0, 1.05, 1.1):
            assert pick_coded(boundary_c * jitter, prev=prev) == prev, (
                prev, jitter,
            )


# -- shared id streams ---------------------------------------------------


def test_shared_id_stream_rewrite(rng):
    """Tables listing the identical field tuple share ONE (uids, inv):
    dedup runs once, the rewrite matches the per-table computation, and
    tables with a different stream keep their own."""
    vocab = 128
    batch = {
        "fids": rng.integers(1, vocab, size=(16, 4)).astype(np.int32),
        "other": rng.integers(1, vocab, size=(16, 2)).astype(np.int32),
    }
    params = {
        "a": jnp.asarray(rng.normal(size=(vocab, 2)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(vocab, 3)), jnp.float32),
        "c": jnp.asarray(rng.normal(size=(vocab, 2)), jnp.float32),
    }
    spec = {"a": ("fids",), "b": ("fids",), "c": ("other",)}
    tables, dense, batch2, uids, rows, counts = \
        SparseTableCTRTrainer._dedup_and_gather(spec, params, batch)
    assert uids["a"] is uids["b"]  # literally one shared stream
    assert uids["c"] is not uids["a"]
    ids = batch["fids"].reshape(-1).astype(np.int32)
    u, inv = np.unique(ids, return_inverse=True)
    # one distinct count a stream, the dedup's own
    assert {f: int(c) for f, c in counts.items()} == {
        ("fids",): u.size, ("other",): np.unique(batch["other"]).size}
    np.testing.assert_array_equal(np.asarray(uids["a"])[:u.size], u)
    np.testing.assert_array_equal(
        np.asarray(batch2["fids"]).reshape(-1), inv
    )
    # the gather serves the live prefix (the slots ``inv`` can name); what
    # it leaves behind it is never read
    assert int(inv.max()) < u.size
    np.testing.assert_allclose(
        np.asarray(rows["b"])[:u.size], np.asarray(params["b"])[u]
    )


def test_shared_stream_byte_accounting(rng):
    """In the hybrid exchange only the FIRST table of a (stream, algo)
    group pays the wire id bytes; the others ride the shared stream."""
    f = 4096
    batch = {
        "fids": rng.integers(0, f, size=(64, 6)).astype(np.int32),
        "fields": np.zeros((64, 6), np.int32),
        "vals": np.ones((64, 6), np.float32),
        "mask": np.ones((64, 6), np.float32),
        "labels": (rng.random(64) > 0.5).astype(np.float32),
    }
    params = fm.init(jax.random.PRNGKey(0), f, 4)
    mesh = make_mesh(MeshSpec(data=N))
    tr = SparseTableCTRTrainer(
        params, fm.logits, TrainConfig(learning_rate=0.1),
        sparse_tables={"w": ["fids"], "v": ["fids"]},
        fused_fn=fm.logits_with_l2, mesh=mesh,
    )
    tr.train_step(batch)
    assert tr.exchange_policy == {"w": "sparse", "v": "sparse"}
    k = batch["fids"].size // N
    assert tr.exchange_bytes_per_step["w"] == \
        sparse_exchange_bytes(N, k, 1)  # first in the group: ids + rows
    assert tr.exchange_bytes_per_step["v"] == \
        sparse_exchange_bytes(N, k, 4, include_ids=False)  # rows only


# -- error feedback for clipped fixed-range payloads ---------------------


def test_sparse_ef_residual_drains_and_recovers_clip(rng):
    """Fixed compress_range + spike beyond it: WITHOUT EF the clipped mass
    is lost; WITH the residual carry the remainder is delivered over the
    following rounds of a constant(-id) gradient stream and the residual
    drains to quantization noise — the dense ring's clip-free bound."""
    n, vocab, k, dim, bits, crange = 4, 32, 6, 3, 8, 1.0
    mesh = make_mesh(MeshSpec(data=n))
    uids = np.tile(np.array([1, 2, 5, 9, 0, 0], np.int64), (n, 1))
    spike = np.zeros((n, k, dim), np.float32)
    spike[:, :4] = 2.5  # 2.5x the codec range: clips hard
    zero = np.zeros_like(spike)

    # single-shot, no EF: the spike round delivers at most the range
    gu, m = sparse_all_reduce(
        mesh, jnp.asarray(uids), jnp.asarray(spike), average=False,
        compress_bits=bits, compress_range=crange,
    )
    lost = dense_scatter(vocab, dim, np.asarray(gu)[0], np.asarray(m)[0])
    assert lost[1, 0] < n * crange * 1.01  # clipped at ~n*range, not n*2.5

    # with EF: carry the clip remainder, stream zero gradients after
    # (jitted once — the loop re-dispatches one program)
    step = jax.jit(lambda u, r, res: sparse_all_reduce(
        mesh, u, r, average=False, compress_bits=bits,
        compress_range=crange, residual=res))
    res = sparse_ef_residual_init(mesh, (vocab, dim))
    applied = np.zeros((vocab, dim), np.float32)
    for t in range(8):
        g = spike if t == 0 else zero
        gu, m, res = step(jnp.asarray(uids), jnp.asarray(g), res)
        applied += dense_scatter(vocab, dim, np.asarray(gu)[0],
                                 np.asarray(m)[0])
    bucket_w = 2 * crange / (1 << bits)
    assert float(np.max(np.abs(np.asarray(res)))) <= bucket_w, (
        "residual must drain to sub-bucket noise"
    )
    want = sum(dense_scatter(vocab, dim, uids[m_], spike[m_])
               for m_ in range(n))
    # every clipped element recovered to within a few buckets of noise
    np.testing.assert_allclose(applied, want, rtol=0,
                               atol=8 * n * bucket_w)


def test_sparse_ef_requires_fixed_range(rng):
    import pytest

    mesh = make_mesh(MeshSpec(data=2))
    uids = np.tile(np.arange(1, 5, dtype=np.int64), (2, 1))
    rows = np.ones((2, 4, 2), np.float32)
    res = sparse_ef_residual_init(mesh, (8, 2))
    with pytest.raises(ValueError, match="dynamic"):
        sparse_all_reduce(mesh, jnp.asarray(uids), jnp.asarray(rows),
                          compress_bits=8, compress_range="dynamic",
                          residual=res)
    with pytest.raises(ValueError, match="dynamic"):
        sparse_reduce_scatter(mesh, jnp.asarray(uids), jnp.asarray(rows),
                              vocab=8, compress_bits=8,
                              compress_range="dynamic", residual=res)


def test_rs_ef_residual_drains_and_recovers_clip(rng):
    """The reduce-scatter mirror of the allgather EF drain test (the PR 7
    follow-up): fixed compress_range + a spike beyond it.  WITHOUT EF the
    clipped mass is lost at the member-side scatter encode; WITH the
    residual carry the remainder is delivered over the following rounds
    and the carry drains to sub-bucket noise.  Ids are owner-spread (one
    per ``uid % n`` owner) so the default capacities hold — overflow has
    its own carry-forward test below.  Mean exchange: stage 2 (the merged
    owner shards) cannot clip, so stage-1 EF recovers everything up to
    per-round rounding (see _rs_gather_rows)."""
    n, vocab, k, dim, bits, crange = 4, 32, 6, 3, 8, 1.0
    mesh = make_mesh(MeshSpec(data=n))
    # owners 1, 2, 3, 0 — one id per owner, no bucket pressure
    uids = np.tile(np.array([1, 2, 7, 8, 0, 0], np.int64), (n, 1))
    spike = np.zeros((n, k, dim), np.float32)
    spike[:, :4] = 2.5  # 2.5x the codec range: clips hard
    zero = np.zeros_like(spike)
    touched = [1, 2, 7, 8]

    # single-shot, no EF: the spike round delivers at most ~range/member
    # (jitted once — the drain loops re-dispatch one program each)
    plain = jax.jit(lambda u, r: sparse_reduce_scatter(
        mesh, u, r, average=True, vocab=vocab,
        compress_bits=bits, compress_range=crange))
    with_ef = jax.jit(lambda u, r, res: sparse_reduce_scatter(
        mesh, u, r, average=True, vocab=vocab,
        compress_bits=bits, compress_range=crange, residual=res))
    applied_no = np.zeros((vocab, dim), np.float32)
    for t in range(8):
        g = spike if t == 0 else zero
        gu, m, over = plain(jnp.asarray(uids), jnp.asarray(g))
        assert int(np.asarray(over)[0]) == 0
        applied_no += dense_scatter(vocab, dim, np.asarray(gu)[0],
                                    np.asarray(m)[0])
    assert applied_no[1, 0] < crange * 1.01  # clipped at ~range, not 2.5

    res = sparse_ef_residual_init(mesh, (vocab, dim))
    applied = np.zeros((vocab, dim), np.float32)
    for t in range(8):
        g = spike if t == 0 else zero
        gu, m, over, res = with_ef(jnp.asarray(uids), jnp.asarray(g), res)
        applied += dense_scatter(vocab, dim, np.asarray(gu)[0],
                                 np.asarray(m)[0])
    bucket_w = 2 * crange / (1 << bits)
    assert float(np.max(np.abs(np.asarray(res)))) <= bucket_w, (
        "residual must drain to sub-bucket noise"
    )
    # touched rows recover the full mean (2.5) to within rounding; the
    # id-0 dump row keeps the coded path's half-bucket junk and is
    # excluded (pre-existing coded-exchange behavior, not an EF effect)
    np.testing.assert_allclose(applied[touched], 2.5, rtol=0,
                               atol=8 * n * bucket_w)
    # acceptance: delivered clipped mass beats the no-EF baseline
    assert applied[touched].mean() > 1.5 * applied_no[touched].mean()


def test_rs_owner_ef_drains_sum_mode_stage2_clip(rng):
    """ISSUE 10 satellite (the PR 9 follow-up): in SUM mode the owner's
    merged shard reaches ``n * value`` and the STAGE-2 encode clips where
    the mean exchange cannot — mirrored by the owner-side residual: the
    clipped merged mass is carried at the owner's row slots and delivered
    over the following rounds, draining to sub-bucket noise, while the
    no-carry run loses everything past the range."""
    n, vocab, k, dim, bits, crange = 4, 32, 6, 3, 8, 1.0
    mesh = make_mesh(MeshSpec(data=n))
    # one id per owner, no bucket pressure; per-member value 0.6 stays
    # inside the range (stage 1 cannot clip) but the 4-way merged sum
    # 2.4 blows past it (stage 2 clips without the owner carry)
    uids = np.tile(np.array([1, 2, 7, 8, 0, 0], np.int64), (n, 1))
    spike = np.zeros((n, k, dim), np.float32)
    spike[:, :4] = 0.6
    zero = np.zeros_like(spike)
    touched = [1, 2, 7, 8]

    # jitted once: the drain loop re-dispatches the same program
    plain = jax.jit(lambda u, r: sparse_reduce_scatter(
        mesh, u, r, average=False, vocab=vocab,
        compress_bits=bits, compress_range=crange))
    with_ef = jax.jit(lambda u, r, res: sparse_reduce_scatter(
        mesh, u, r, average=False, vocab=vocab,
        compress_bits=bits, compress_range=crange, owner_residual=res))

    applied_no = np.zeros((vocab, dim), np.float32)
    for t in range(2):
        g = spike if t == 0 else zero
        gu, m, over = plain(jnp.asarray(uids), jnp.asarray(g))
        assert int(np.asarray(over)[0]) == 0
        applied_no += dense_scatter(vocab, dim, np.asarray(gu)[0],
                                    np.asarray(m)[0])
    assert applied_no[1, 0] < crange * 1.01  # stage-2 clip: ~range, not 2.4

    ores = sparse_ef_residual_init(mesh, (vocab, dim))
    applied = np.zeros((vocab, dim), np.float32)
    for t in range(6):
        g = spike if t == 0 else zero
        gu, m, over, ores = with_ef(jnp.asarray(uids), jnp.asarray(g), ores)
        applied += dense_scatter(vocab, dim, np.asarray(gu)[0],
                                 np.asarray(m)[0])
    bucket_w = 2 * crange / (1 << bits)
    # the carry partitions by owner: row u only ever moves on member
    # u % n's carry, and it must have drained
    assert float(np.max(np.abs(np.asarray(ores)[:, touched]))) <= bucket_w
    np.testing.assert_allclose(applied[touched], n * 0.6, rtol=0,
                               atol=6 * n * bucket_w)
    assert applied[touched].mean() > 1.8 * applied_no[touched].mean()


def test_rs_owner_ef_rejected_in_mean_mode(rng):
    import pytest

    mesh = make_mesh(MeshSpec(data=2))
    uids = np.tile(np.arange(1, 5, dtype=np.int64), (2, 1))
    rows = np.ones((2, 4, 2), np.float32)
    ores = sparse_ef_residual_init(mesh, (8, 2))
    with pytest.raises(ValueError, match="SUM-mode"):
        sparse_reduce_scatter(mesh, jnp.asarray(uids), jnp.asarray(rows),
                              vocab=8, average=True, compress_bits=8,
                              compress_range=1.0, owner_residual=ores)


def test_rs_both_stage_carries_compose_under_clip(rng):
    """Stage-1 (member) + stage-2 (owner) carries together: a payload
    that clips BOTH encodes (per-member value past the range AND a merged
    sum past it) still delivers the full sum over the rounds — each
    stage's loss lands in its own carry."""
    n, vocab, k, dim, bits, crange = 4, 32, 6, 2, 8, 1.0
    mesh = make_mesh(MeshSpec(data=n))
    uids = np.tile(np.array([1, 2, 7, 8, 0, 0], np.int64), (n, 1))
    spike = np.zeros((n, k, dim), np.float32)
    spike[:, :4] = 1.7  # past the range: stage 1 clips; 4x sum clips too
    zero = np.zeros_like(spike)
    touched = [1, 2, 7, 8]
    step = jax.jit(lambda u, r, res, ores: sparse_reduce_scatter(
        mesh, u, r, average=False, vocab=vocab,
        compress_bits=bits, compress_range=crange,
        residual=res, owner_residual=ores))
    res = sparse_ef_residual_init(mesh, (vocab, dim))
    ores = sparse_ef_residual_init(mesh, (vocab, dim))
    applied = np.zeros((vocab, dim), np.float32)
    for t in range(12):
        g = spike if t == 0 else zero
        gu, m, over, res, ores = step(jnp.asarray(uids), jnp.asarray(g),
                                      res, ores)
        applied += dense_scatter(vocab, dim, np.asarray(gu)[0],
                                 np.asarray(m)[0])
    bucket_w = 2 * crange / (1 << bits)
    np.testing.assert_allclose(applied[touched], n * 1.7, rtol=0,
                               atol=16 * n * bucket_w)


def test_rs_ef_overflow_carries_full_value(rng):
    """A bucket-overflow victim (3 ids on one owner, bucket_cap=2) ships
    nothing — without EF that mass is silently dropped; with EF the FULL
    value lands in the carry instead (the documented dropped-entry
    contract), so the in-jit overflow counter plus the carry account for
    every bit of gradient mass."""
    n, vocab, dim, bits, crange = 4, 32, 2, 8, 1.0
    mesh = make_mesh(MeshSpec(data=n))
    # owners: 1, 1, 1 — uid 9 overflows bucket_cap=2 deterministically
    uids = np.tile(np.array([1, 5, 9, 0], np.int64), (n, 1))
    rows = 0.5 * np.ones((n, 4, dim), np.float32)
    rows[:, 3] = 0.0
    res = sparse_ef_residual_init(mesh, (vocab, dim))
    gu, m, over, res = sparse_reduce_scatter(
        mesh, jnp.asarray(uids), jnp.asarray(rows), average=True,
        vocab=vocab, bucket_cap=2, shard_cap=8,
        compress_bits=bits, compress_range=crange, residual=res,
    )
    assert int(np.asarray(over)[0]) > 0
    merged = dense_scatter(vocab, dim, np.asarray(gu)[0], np.asarray(m)[0])
    assert abs(merged[9, 0]) < 1e-6          # victim shipped nothing
    r0 = np.asarray(res)[0]
    np.testing.assert_allclose(r0[9], 0.5, rtol=0, atol=1e-6)  # full carry
    bucket_w = 2 * crange / (1 << bits)
    assert np.abs(r0[[1, 5]]).max() <= bucket_w / 2 + 1e-7  # quant noise


# -- hybrid trainer: rs pick, parity, fallback ---------------------------


def _fm_batch(rng, n_rows, f, nnz):
    return {
        "fids": rng.integers(1, f, size=(n_rows, nnz)).astype(np.int32),
        "fields": np.zeros((n_rows, nnz), np.int32),
        "vals": np.ones((n_rows, nnz), np.float32),
        "mask": np.ones((n_rows, nnz), np.float32),
        "labels": (rng.random(n_rows) > 0.5).astype(np.float32),
    }


def test_hybrid_rs_trainer_matches_dense_psum(rng):
    """A density/world regime where the pick takes the reduce-scatter path
    for the embedding table: the trajectory still equals the dense-psum
    data-parallel trainer's to fp32 tolerance."""
    f = 4096
    batch = _fm_batch(rng, 2048, f, 8)
    params = fm.init(jax.random.PRNGKey(0), f, 16)
    cfg = TrainConfig(learning_rate=0.1, lambda_l2=0.001)
    mesh = make_mesh(MeshSpec(data=N))
    dense_tr = CTRTrainer(params, fm.logits, cfg,
                          fused_fn=fm.logits_with_l2, mesh=mesh)
    sparse_tr = SparseTableCTRTrainer(
        params, fm.logits, cfg, sparse_tables={"w": ["fids"], "v": ["fids"]},
        fused_fn=fm.logits_with_l2, mesh=mesh,
    )
    plan = sparse_tr._exchange_plan(batch)
    assert plan["v"][1] == "sparse_rs", plan  # the regime under test
    assert sparse_tr._rs_batch_fits(batch, plan)
    ld = dense_tr.fit_fullbatch_scan(batch, 8)
    ls = sparse_tr.fit_fullbatch_scan(batch, 8)
    assert sparse_tr.exchange_policy["v"] == "sparse_rs"
    np.testing.assert_allclose(ls, ld, rtol=1e-4, atol=1e-5)
    for key in ("w", "v"):
        np.testing.assert_allclose(
            np.asarray(sparse_tr.params[key]),
            np.asarray(dense_tr.params[key]), rtol=1e-4, atol=1e-5,
        )


def test_hybrid_rs_trainer_world4(rng):
    """Same rs-picked parity on a 4-way mesh (world-size coverage at the
    trainer level)."""
    f = 2048
    batch = _fm_batch(rng, 512, f, 8)
    params = fm.init(jax.random.PRNGKey(1), f, 16)
    cfg = TrainConfig(learning_rate=0.1)
    mesh = make_mesh(MeshSpec(data=4))
    dense_tr = CTRTrainer(params, fm.logits, cfg, mesh=mesh)
    sparse_tr = SparseTableCTRTrainer(
        params, fm.logits, cfg, sparse_tables={"w": ["fids"], "v": ["fids"]},
        mesh=mesh,
    )
    plan = sparse_tr._exchange_plan(batch)
    assert plan["v"][1] == "sparse_rs", plan
    ld = dense_tr.fit_fullbatch_scan(batch, 6)
    ls = sparse_tr.fit_fullbatch_scan(batch, 6)
    np.testing.assert_allclose(ls, ld, rtol=1e-4, atol=1e-5)


def test_hybrid_rs_overflow_falls_back_to_allgather(rng):
    """A batch whose ids all land on one owner (uid ≡ 0 mod n) would
    overflow the rs buckets: the host check routes it to the allgather
    fallback program, the trajectory still matches the dense trainer, and
    the fallback is counted."""
    from lightctr_tpu.obs import MetricsRegistry

    f = 4096
    batch = _fm_batch(rng, 2048, f, 8)
    # skew every id onto owner 0 while keeping them unique-ish and nonzero
    batch["fids"] = np.maximum(batch["fids"] // N, 1).astype(np.int32) * N
    params = fm.init(jax.random.PRNGKey(0), f, 16)
    cfg = TrainConfig(learning_rate=0.1)
    mesh = make_mesh(MeshSpec(data=N))
    dense_tr = CTRTrainer(params, fm.logits, cfg, mesh=mesh)
    sparse_tr = SparseTableCTRTrainer(
        params, fm.logits, cfg, sparse_tables={"w": ["fids"], "v": ["fids"]},
        mesh=mesh,
    )
    sparse_tr.telemetry = MetricsRegistry()
    plan = sparse_tr._exchange_plan(batch)
    assert plan["v"][1] == "sparse_rs", plan   # rs is still the static pick
    assert not sparse_tr._rs_batch_fits(batch, plan)
    for _ in range(3):
        ld = dense_tr.train_step(batch)
        ls = sparse_tr.train_step(batch)
    assert sparse_tr._last_step_fallback
    assert sparse_tr._fallback_policy["v"] == "sparse"
    snap = sparse_tr.telemetry.snapshot()
    assert snap["counters"]["trainer_rs_fallback_total"] == 3
    np.testing.assert_allclose(float(ls), float(ld), rtol=1e-5, atol=1e-6)
    for key in ("w", "v"):
        np.testing.assert_allclose(
            np.asarray(sparse_tr.params[key]),
            np.asarray(dense_tr.params[key]), rtol=1e-4, atol=1e-5,
        )


# -- hybrid trainer: sparse EF on fixed-range configs (ISSUE 7 satellite) --


def _ef_fm_batch(seed, vals_scale=1.0, f=1 << 15, n_rows=128, nnz=4,
                 labels=None):
    r = np.random.default_rng(seed)
    fids = r.integers(1, f, size=(n_rows, nnz)).astype(np.int32)
    return {
        "fids": fids,
        "fields": np.zeros_like(fids),
        "vals": vals_scale * np.ones((n_rows, nnz), np.float32),
        "mask": np.ones((n_rows, nnz), np.float32),
        "labels": (labels if labels is not None
                   else (r.random(n_rows) > 0.5).astype(np.float32)),
    }


def _ef_trainer(params, mesh, crange, ef):
    tr = SparseTableCTRTrainer(
        params, fm.logits, TrainConfig(learning_rate=0.05),
        sparse_tables={"w": ["fids"], "v": ["fids"]}, mesh=mesh,
        compress_bits=8, compress_range=crange, compress_mode="uniform",
        error_feedback=ef,
    )
    tr.health = None
    return tr


def test_hybrid_fixed_range_allocates_sparse_residual_state():
    """Fixed float compress_range + error_feedback => per-table [n, vocab,
    ...] EF carries in the opt state; dynamic range (never clips) and
    EF-off configs allocate none."""
    f = 1 << 15
    params = fm.init(jax.random.PRNGKey(0), f, 8)
    mesh = make_mesh(MeshSpec(data=2))
    tr = _ef_trainer(params, mesh, 0.05, True)
    assert tr._use_sparse_ef()
    assert set(tr.opt_state["sres"]) == {"w", "v"}
    assert tr.opt_state["sres"]["v"].shape == (2, f, 8)
    assert tr.opt_state["sres"]["w"].shape == (2, f)
    assert "sres" not in _ef_trainer(params, mesh, 0.05, False).opt_state
    tr_dyn = SparseTableCTRTrainer(
        params, fm.logits, TrainConfig(learning_rate=0.05),
        sparse_tables={"w": ["fids"], "v": ["fids"]}, mesh=mesh,
        compress_bits=8, compress_range="dynamic", error_feedback=True,
    )
    assert "sres" not in tr_dyn.opt_state


def test_hybrid_fixed_range_ef_captures_clip_and_drains(rng):
    """The trainer-level mirror of the collectives EF drain test: a batch
    whose gradients blow past the fixed range leaves the clipped mass in
    the residual; streaming the same ids afterwards delivers it (the
    carry drains to sub-bucket noise) and the table ends up having moved
    FURTHER than the no-EF run, whose clipped mass is simply lost."""
    f = 1 << 15
    spike = _ef_fm_batch(0, vals_scale=20.0,
                         labels=np.ones(128, np.float32))
    normal = _ef_fm_batch(0, vals_scale=1.0,
                          labels=np.ones(128, np.float32))
    params = fm.init(jax.random.PRNGKey(0), f, 8)
    mesh = make_mesh(MeshSpec(data=2))
    tr, tr_no = (_ef_trainer(params, mesh, 0.05, True),
                 _ef_trainer(params, mesh, 0.05, False))
    assert tr.exchange_policy == {}   # nothing traced yet
    tr.train_step(spike)
    tr_no.train_step(spike)
    assert tr.exchange_policy == {"w": "sparse", "v": "sparse"}
    res_after_spike = float(
        np.abs(np.asarray(tr.opt_state["sres"]["w"])).max())
    assert res_after_spike > 0.05, "clip mass must land in the carry"
    for _ in range(11):
        tr.train_step(normal)
        tr_no.train_step(normal)
    bucket_w = 2 * 0.05 / 256
    res_final = float(np.abs(np.asarray(tr.opt_state["sres"]["w"])).max())
    assert res_final <= 5 * bucket_w, (res_after_spike, res_final)
    touched = np.unique(spike["fids"])
    w0 = np.asarray(params["w"])
    dw_ef = (np.asarray(tr.params["w"]) - w0)[touched]
    dw_no = (np.asarray(tr_no.params["w"]) - w0)[touched]
    # labels=1 spike pushes w UP; EF delivers the clipped remainder late,
    # no-EF loses it — EF must have moved the touched rows further
    assert dw_ef.mean() > dw_no.mean() * 1.2, (dw_ef.mean(), dw_no.mean())


def test_hybrid_fixed_range_ef_tracks_exact_under_coarse_codec(rng):
    """Parity under clipping/rounding: a coarse fixed-range codec (range
    1.0 over ~1e-3 gradients, so every payload rounds to a ~0.004-wide
    bucket) drifts far from the dense-psum trajectory WITHOUT EF; with
    the carry the trainer tracks the exact trajectory several times
    closer — the dense ring's clip-free bound, now on the sparse path."""
    f = 1 << 15
    batch = _ef_fm_batch(3)
    params = fm.init(jax.random.PRNGKey(0), f, 8)
    mesh = make_mesh(MeshSpec(data=2))
    exact = CTRTrainer(params, fm.logits,
                       TrainConfig(learning_rate=0.05), mesh=mesh)
    exact.health = None
    tr, tr_no = (_ef_trainer(params, mesh, 1.0, True),
                 _ef_trainer(params, mesh, 1.0, False))
    for _ in range(30):
        exact.train_step(batch)
        tr.train_step(batch)
        tr_no.train_step(batch)
    assert tr.exchange_policy == {"w": "sparse", "v": "sparse"}
    touched = np.unique(batch["fids"])
    for key in ("w", "v"):
        err_ef = np.abs(np.asarray(tr.params[key])
                        - np.asarray(exact.params[key]))[touched].mean()
        err_no = np.abs(np.asarray(tr_no.params[key])
                        - np.asarray(exact.params[key]))[touched].mean()
        assert err_ef < 0.5 * err_no, (key, err_ef, err_no)


def test_hybrid_rs_fixed_range_ef_delivers_clipped_mass(rng):
    """The REDUCE-SCATTER mirror of the fixed-range EF trainer test (the
    ISSUE 9 satellite closing the PR 7 follow-up): a wide embedding table
    in the rs-picked regime under a tight fixed range — the spike's
    clipped mass lands in the per-table carry (stage-1 member-side EF on
    the scatter encode) and is delivered over the following steps, so the
    touched rows move measurably further than the no-EF run, whose
    clipped mass is simply lost."""
    f, nrows, nnz, dim = 4096, 1024, 8, 64
    fids = rng.integers(1, f, size=(nrows, nnz)).astype(np.int32)
    ones = np.ones(nrows, np.float32)

    def mk(vals_scale):
        return {
            "fids": fids, "fields": np.zeros_like(fids),
            "vals": vals_scale * np.ones((nrows, nnz), np.float32),
            "mask": np.ones((nrows, nnz), np.float32), "labels": ones,
        }

    spike, normal = mk(20.0), mk(1.0)
    params = fm.init(jax.random.PRNGKey(0), f, dim)
    mesh = make_mesh(MeshSpec(data=N))

    def trainer(ef):
        tr = SparseTableCTRTrainer(
            params, fm.logits, TrainConfig(learning_rate=0.05),
            sparse_tables={"w": ["fids"], "v": ["fids"]}, mesh=mesh,
            compress_bits=8, compress_range=0.05, compress_mode="uniform",
            error_feedback=ef,
        )
        tr.health = None
        return tr

    tr, tr_no = trainer(True), trainer(False)
    plan = tr._exchange_plan(spike)
    assert plan["v"][1] == "sparse_rs", plan   # the regime under test
    assert tr._rs_batch_fits(spike, plan)
    tr.train_step(spike)
    tr_no.train_step(spike)
    assert tr.exchange_policy["v"] == "sparse_rs"
    res_after_spike = float(
        np.abs(np.asarray(tr.opt_state["sres"]["v"])).max())
    assert res_after_spike > 0.05, "clip mass must land in the rs carry"
    for _ in range(8):
        tr.train_step(normal)
        tr_no.train_step(normal)
    touched = np.unique(fids)
    v0 = np.asarray(params["v"])
    dv_ef = np.abs(np.asarray(tr.params["v"]) - v0)[touched]
    dv_no = np.abs(np.asarray(tr_no.params["v"]) - v0)[touched]
    # labels=1 spike pushes the touched rows; EF delivers the clipped
    # remainder late, no-EF loses it (measured ~2x in this regime)
    assert dv_ef.mean() > 1.2 * dv_no.mean(), (dv_ef.mean(), dv_no.mean())
