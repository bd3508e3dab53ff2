"""Hierarchical two-level sparse exchange (ISSUE 10): the DCN reduce
rendezvous (`dist/hier.py`), the hier trainer mode (local ICI merge -> one
merged payload per host over the wire -> replicated apply), the local
overflow fallback, and the 2-process x multi-replica acceptance — the
trajectory must match the dense-psum-exact oracle and the cross-host wire
bytes must stay FLAT when the local replica count doubles."""

import os
import socket
import subprocess
import sys
import textwrap
import threading
from pathlib import Path

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightctr_tpu import TrainConfig
from lightctr_tpu.core.mesh import MeshSpec, make_mesh
from lightctr_tpu.dist.hier import HierExchangeClient, SparseReduceShard
from lightctr_tpu.models import fm
from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer
from lightctr_tpu.obs import MetricsRegistry

REPO_ROOT = str(Path(__file__).resolve().parents[1])


# -- the reduce rendezvous ------------------------------------------------


def test_reduce_shard_merges_rounds_and_withholds():
    """One shard, two hosts: a pull before both pushes lands is WITHHELD
    (the SSP status byte — the client retries); once complete, every host
    pulls the identical merged union (duplicate ids segment-summed in
    host order), and the round is garbage-collected after the last
    pull."""
    shard = SparseReduceShard(n_hosts=2)
    c0 = HierExchangeClient([shard.address], host_id=0, n_hosts=2,
                            pull_timeout_s=5.0)
    c1 = HierExchangeClient([shard.address], host_id=1, n_hosts=2,
                            pull_timeout_s=5.0)
    try:
        u0 = np.array([1, 2, 5], np.int64)
        r0 = np.arange(6, dtype=np.float32).reshape(3, 2)
        u1 = np.array([2, 3], np.int64)
        r1 = np.ones((2, 2), np.float32)
        c0.push(0, u0, r0, epoch=0)
        with pytest.raises(TimeoutError):
            HierExchangeClient([shard.address], 0, 2,
                               pull_timeout_s=0.05).pull(0, 0, 2)
        assert shard.stats()["withheld"] >= 1
        c1.push(0, u1, r1, epoch=0)
        g0 = c0.pull(0, 0, 2)
        g1 = c1.pull(0, 0, 2)
        np.testing.assert_array_equal(g0[0], [1, 2, 3, 5])
        np.testing.assert_allclose(
            g0[1], [[0, 1], [3, 4], [1, 1], [4, 5]], rtol=0, atol=0)
        np.testing.assert_array_equal(g0[0], g1[0])
        np.testing.assert_allclose(g0[1], g1[1], rtol=0, atol=0)
        # a pull whose REPLY was lost retries and must be SERVED (the
        # round is retained past the last pull), never withheld until
        # the timeout — pulls are as at-least-once-safe as pushes
        g0_again = c0.pull(0, 0, 2)
        np.testing.assert_array_equal(g0_again[0], g0[0])
        # retention is bounded: the epoch-lag GC reaps completed rounds
        # once newer epochs advance past the lag window
        c0.push(1, u0[:1], r0[:1],
                epoch=shard.ROUND_GC_LAG + 1)
        assert (0, 0) not in shard._rounds
    finally:
        c0.close()
        c1.close()
        shard.close()


def test_reduce_client_owner_partitions_across_shards():
    """Two shards: uids split by ``uid % n_shards`` (the PS modulo
    family), empty per-shard frames still check in (the round bar counts
    hosts), and the spliced pull is globally sorted.  Both wire codecs
    round-trip; f16 quantizes to half precision."""
    shards = [SparseReduceShard(n_hosts=1) for _ in range(2)]
    addrs = [s.address for s in shards]
    try:
        for codec, atol in (("f32", 0.0), ("f16", 1e-2)):
            c = HierExchangeClient(addrs, host_id=0, n_hosts=1, codec=codec)
            uids = np.array([3, 4, 7, 10, 21], np.int64)  # odd/even mix
            rows = np.linspace(-1, 1, 15).astype(np.float32).reshape(5, 3)
            gu, gr = c.exchange(5 if codec == "f16" else 4, uids, rows,
                                epoch=0)
            np.testing.assert_array_equal(gu, uids)
            np.testing.assert_allclose(gr, rows, rtol=0, atol=atol)
            c.close()
        # all ids on one shard: the OTHER shard still completes its round
        c = HierExchangeClient(addrs, host_id=0, n_hosts=1)
        uids = np.array([2, 4], np.int64)  # both even -> shard 0
        gu, gr = c.exchange(6, uids, np.ones((2, 1), np.float32), epoch=1)
        np.testing.assert_array_equal(gu, uids)
        c.close()
    finally:
        for s in shards:
            s.close()


def test_reduce_shard_rejects_malformed_and_counts():
    """Unsorted push keys are a protocol error (loud, counted), and the
    bandwidth probe rides single-contributor negative-epoch rounds
    without peer hosts."""
    shard = SparseReduceShard(n_hosts=2)
    c = HierExchangeClient([shard.address], host_id=0, n_hosts=2)
    try:
        with pytest.raises(ValueError, match="sorted unique"):
            c.push(0, np.array([5, 3], np.int64),
                   np.ones((2, 2), np.float32), epoch=0)
        bw = c.probe_bw(payload_bytes=1 << 14, reps=2)
        assert bw > 0
        assert shard.stats()["rounds_open"] == 0  # probe rounds GC'd
        # probe rounds are EXEMPT from the epoch-lag GC (their negative
        # epochs would read as infinitely stale): a mid-run re-probe
        # after real epochs advanced must still complete
        c.push(2, np.array([2], np.int64), np.ones((1, 2), np.float32),
               epoch=40)
        assert c.probe_bw(payload_bytes=1 << 12, reps=1) > 0
    finally:
        c.close()
        shard.close()


# -- the streaming rendezvous (ISSUE 16) ----------------------------------


def test_chunked_striped_exchange_matches_single_shot_bit_identical(rng):
    """THE streaming parity gate: the same two-host contribution pushed
    (a) single-shot to one shard and (b) chunked into 3-row windows
    across TWO striped shards pulls back the bit-identical merged union
    — chunk boundaries and stripe splits change packets, never floats —
    and the client's chunk-fill counters plus the per-stripe byte
    counters land."""
    from lightctr_tpu import obs
    from lightctr_tpu.obs import labeled

    dim, n = 5, 23
    uids = [np.unique(rng.integers(1, 200, 40))[:n].astype(np.int64),
            np.unique(rng.integers(1, 200, 40))[:n].astype(np.int64)]
    rows = [rng.normal(size=(u.size, dim)).astype(np.float32)
            for u in uids]

    def run(n_shards, chunk_rows):
        shards = [SparseReduceShard(n_hosts=2) for _ in range(n_shards)]
        regs = [MetricsRegistry(), MetricsRegistry()]
        cs = [HierExchangeClient([s.address for s in shards], host_id=h,
                                 n_hosts=2, chunk_rows=chunk_rows,
                                 registry=regs[h])
              for h in (0, 1)]
        try:
            for h in (0, 1):
                cs[h].push_async(0, uids[h], rows[h], epoch=0)
            got = [cs[h].pull(0, 0, dim) for h in (0, 1)]
            stats = [s.stats() for s in shards]
            counters = (cs[0].chunk_pushes_total, cs[0].chunk_rows_total,
                        cs[0].chunk_capacity_rows_total)
            snap = regs[0].snapshot()["counters"]
        finally:
            for c in cs:
                c.close()
            for s in shards:
                s.close()
        return got, stats, counters, snap

    with obs.override(True):
        (base, _, base_counters, _) = run(n_shards=1, chunk_rows=None)
        (got, stats, counters, snap) = run(n_shards=2, chunk_rows=3)
    # hosts agree with each other and with the single-shot oracle, bit
    # for bit (two f32 addends per uid commute; windows touch disjoint
    # uid ranges so each (host, uid) lands exactly once)
    for g in (base[1], got[0], got[1]):
        np.testing.assert_array_equal(base[0][0], g[0])
        np.testing.assert_array_equal(base[0][1], g[1])
    # the pull committed the in-flight chunks first: no frame was lost
    assert all(s["streaming"] for s in stats)
    assert all(s["peak_round_bytes"] > 0 for s in stats)
    # chunk-fill accounting: every window counted, capacity >= rows,
    # unchunked pushes count capacity == rows (fill 1.0 by construction)
    assert counters[0] > base_counters[0]
    assert counters[2] >= counters[1] == n
    assert base_counters[2] == base_counters[1] == n
    # per-stripe byte counters: BOTH stripes carried frames
    for s in ("0", "1"):
        assert snap[labeled("hier_stripe_push_bytes_total",
                            stripe=s)] > 0
        assert snap[labeled("hier_stripe_pull_bytes_total",
                            stripe=s)] > 0


def test_streaming_out_of_order_duplicate_and_skewed_chunks(rng):
    """The at-least-once chunk contract, against the shard surface
    directly: chunks may arrive in ANY order, a retried duplicate chunk
    is counted exactly once, the round completes only when every host's
    declared total is in, a chunk-count skew inside one round fails
    loud, and the frozen arrival ring carries the per-chunk timeline
    (first/last offsets + chunk counts)."""
    dim = 3
    shard = SparseReduceShard(n_hosts=2)
    try:
        # host 0: three chunks, delivered 2, 0, 1; host 1: single-shot
        u = np.arange(1, 10, dtype=np.int64)
        r = rng.normal(size=(9, dim)).astype(np.float32)
        chunks = [(u[0:3], r[0:3]), (u[3:6], r[3:6]), (u[6:9], r[6:9])]
        shard._push(0, 0, 7, *chunks[2], dim, chunk=(2, 3))
        assert shard._pull(0, 0, 7) is None  # withheld: incomplete
        shard._push(0, 0, 7, *chunks[0], dim, chunk=(0, 3))
        shard._push(0, 0, 7, *chunks[0], dim, chunk=(0, 3))  # dup retry
        # a mid-round chunk-count skew is a protocol violation
        with pytest.raises(ValueError, match="chunk-count skew"):
            shard._push(0, 0, 7, *chunks[1], dim, chunk=(1, 4))
        shard._push(0, 0, 7, *chunks[1], dim, chunk=(1, 3))
        assert shard._pull(0, 0, 7) is None  # host 1 still missing
        u1 = np.array([2, 5, 40], np.int64)
        r1 = rng.normal(size=(3, dim)).astype(np.float32)
        shard._push(1, 0, 7, u1, r1, dim, chunk=(0, 1))
        ku, kr = shard._pull(0, 0, 7)
        # oracle: duplicate chunk counted once, every id summed once
        want_u = np.unique(np.concatenate([u, u1]))
        want = np.zeros((want_u.size, dim), np.float32)
        want[np.searchsorted(want_u, u)] += r
        want[np.searchsorted(want_u, u1)] += r1
        np.testing.assert_array_equal(ku, want_u)
        np.testing.assert_allclose(kr, want, rtol=0, atol=0)
        ring = shard.stats()["arrivals"]
        assert ring and ring[-1]["epoch"] == 0
        entry = ring[-1]
        assert entry["chunks"] == {"0": 3, "1": 1}
        assert set(entry["arrivals"]) == {"0", "1"}
        # last-chunk offsets bound the first-chunk offsets per host
        for h in ("0", "1"):
            assert entry["last"][h] >= entry["arrivals"][h]
        assert entry["wait_s"] == max(entry["arrivals"].values())
    finally:
        shard.close()


def test_barrier_mode_chunk_merge_and_streaming_memory_flat(rng):
    """streaming=False keeps the PR 10 barrier shape (chunks buffered,
    one deterministic (host, chunk) merge at the first pull) and both
    modes agree on grid-representable values; the streaming
    accumulator's peak memory stays FLAT (+-10%) when n_hosts doubles
    over the same id universe — the barrier buffer grows linearly."""
    dim, n = 4, 30
    u = np.arange(1, n + 1, dtype=np.int64)

    def run(streaming, n_hosts):
        shard = SparseReduceShard(n_hosts=n_hosts, streaming=streaming)
        try:
            for h in range(n_hosts):
                # grid values: exact under any accumulation order
                r = (rng.integers(-8, 9, size=(n, dim)) * 0.25
                     ).astype(np.float32)
                for ci in range(3):
                    lo, hi = ci * 10, (ci + 1) * 10
                    shard._push(h, 0, 0, u[lo:hi], r[lo:hi], dim,
                                chunk=(ci, 3))
            out = shard._pull(0, 0, 0)
            return out, shard.stats()
        finally:
            shard.close()

    rng_state = rng.bit_generator.state
    (su, sr), s_stats = run(streaming=True, n_hosts=2)
    rng.bit_generator.state = rng_state
    (bu, br), b_stats = run(streaming=False, n_hosts=2)
    assert s_stats["streaming"] and not b_stats["streaming"]
    np.testing.assert_array_equal(su, bu)
    np.testing.assert_array_equal(sr, br)  # grid values: bit-equal modes
    # memory: the streaming accumulator is bounded by the UNION, so
    # doubling the contributor count leaves the peak flat; the barrier
    # buffer holds every contribution and roughly doubles
    _, s2 = run(streaming=True, n_hosts=2)
    _, s4 = run(streaming=True, n_hosts=4)
    p2, p4 = s2["peak_round_bytes"], s4["peak_round_bytes"]
    assert abs(p4 - p2) <= 0.1 * p2, (p2, p4)
    _, b4 = run(streaming=False, n_hosts=4)
    assert b4["peak_round_bytes"] > 1.5 * p4, (b4["peak_round_bytes"], p4)


def test_owner_coded_encode_once_under_chunked_pushes(rng):
    """The q8_ef/q4_ef owner contract survives chunking: however many
    chunks fed the round, the owner-side encode happens EXACTLY once
    (coded_rounds), every host pulls byte-identical code sections, a
    retried pull re-serves the cached bytes, and the owner EF carry
    advances once per ROUND — two identical rounds decode to different
    bytes only through the carried residual."""
    dim = 6
    for bits, codec in ((8, "q8_ef"), (4, "q4_ef")):
        shard = SparseReduceShard(n_hosts=2)
        cs = [HierExchangeClient([shard.address], host_id=h, n_hosts=2,
                                 codec=codec, chunk_rows=2)
              for h in (0, 1)]
        try:
            u = np.arange(1, 8, dtype=np.int64)
            r = (0.1 * rng.normal(size=(7, dim))).astype(np.float32)
            outs = []
            for epoch in (0, 1):
                for h in (0, 1):
                    cs[h].push(0, u, r, epoch=epoch)
                raw = [shard._pull(h, epoch, 0, coded=True,
                                   bits=cs[0]._coded_bits)
                       for h in (0, 1)]
                # encode-once: every pull (including a retry) serves the
                # SAME cached bytes
                assert raw[0] == raw[1]
                assert shard._pull(0, epoch, 0, coded=True,
                                   bits=cs[0]._coded_bits) == raw[0]
                outs.append(raw[0])
                got = [cs[h].pull(0, epoch, dim) for h in (0, 1)]
                np.testing.assert_array_equal(got[0][0], got[1][0])
                np.testing.assert_array_equal(got[0][1], got[1][1])
            stats = shard.stats()
            assert stats["coded_rounds"] == 2  # one encode per round
            # the carry advanced between rounds: identical payloads
            # encode to different bytes only via the carried residual,
            # and the residual stays sub-bucket
            assert outs[0] != outs[1]
            mass = stats["owner_ef_mass"]["0"]
            assert 0.0 < mass < 2.0, mass
            # member-side carries advanced once per chunked push round
            assert cs[0].carry_mass() > 0.0
        finally:
            for c in cs:
                c.close()
            shard.close()


# -- in-process hier trainer (threads as hosts) ---------------------------


def _fm_batch(rng, n_rows, f, nnz=4):
    fids = rng.integers(1, f, size=(n_rows, nnz)).astype(np.int32)
    return {
        "fids": fids, "fields": np.zeros_like(fids),
        "vals": np.ones((n_rows, nnz), np.float32),
        "mask": np.ones((n_rows, nnz), np.float32),
        "labels": (np.arange(n_rows) % 2).astype(np.float32),
    }


def _run_hier_hosts(params, cfg, halves, addrs, n_hosts, local_n, steps,
                    registries=None, codec="f32"):
    """Drive ``n_hosts`` hier trainers from threads (the rendezvous
    barrier synchronizes them) -> {host: (losses, params, trainer)}."""
    results = {}
    errors = []

    def run_host(hid):
        client = HierExchangeClient(addrs, host_id=hid, n_hosts=n_hosts,
                                    codec=codec)
        try:
            tr = SparseTableCTRTrainer(
                params, fm.logits, cfg,
                sparse_tables={"w": ["fids"], "v": ["fids"]},
                fused_fn=fm.logits_with_l2,
                mesh=make_mesh(MeshSpec(data=local_n)),
                hier_exchange=client,
            )
            tr.health = None
            if registries is not None:
                tr.telemetry = registries[hid]
            losses = [float(tr.train_step(halves[hid]))
                      for _ in range(steps)]
            results[hid] = (losses,
                            {k: np.asarray(v) for k, v in tr.params.items()},
                            tr)
        except Exception as e:  # surface thread failures to the test
            errors.append((hid, repr(e)))
        finally:
            client.close()

    threads = [threading.Thread(target=run_host, args=(h,))
               for h in range(n_hosts)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    assert set(results) == set(range(n_hosts))
    return results


def test_hier_trainer_matches_single_process_oracle(rng):
    """2 hosts x 2 local replicas in one process (threads): the hier
    trajectory equals the single-device full-batch trainer's (the
    dense-psum-exact oracle) to fp32 tolerance, both hosts end
    bit-identical, the policy records ``hier`` and the per-hop byte
    counters land."""
    f, dim, steps = 512, 8, 4
    full = _fm_batch(rng, 128, f)
    halves = [{k: v[:64] for k, v in full.items()},
              {k: v[64:] for k, v in full.items()}]
    params = fm.init(jax.random.PRNGKey(0), f, dim)
    cfg = TrainConfig(learning_rate=0.1)
    shards = [SparseReduceShard(n_hosts=2) for _ in range(2)]
    regs = {0: MetricsRegistry(), 1: MetricsRegistry()}
    try:
        results = _run_hier_hosts(
            params, cfg, halves, [s.address for s in shards], 2, 2, steps,
            registries=regs,
        )
    finally:
        for s in shards:
            s.close()

    oracle = SparseTableCTRTrainer(
        params, fm.logits, cfg,
        sparse_tables={"w": ["fids"], "v": ["fids"]},
        fused_fn=fm.logits_with_l2,
    )
    oracle.health = None
    o_losses = [float(oracle.train_step(full)) for _ in range(steps)]

    l0, p0, tr0 = results[0]
    l1, p1, _ = results[1]
    np.testing.assert_allclose(l0, l1, rtol=0, atol=1e-6)
    np.testing.assert_allclose(l0, o_losses, rtol=1e-4, atol=1e-6)
    for k in ("w", "v"):
        np.testing.assert_array_equal(p0[k], p1[k])
        np.testing.assert_allclose(p0[k], np.asarray(oracle.params[k]),
                                   rtol=1e-4, atol=1e-5)
    assert tr0.exchange_policy == {"w": "hier", "v": "hier"}
    # the hier programs' health vector carries no counts: the host counts
    assert tr0._step_counts is None and oracle._step_counts is not None
    assert tr0.hier_local_policy["w"] in ("sparse", "sparse_rs")
    assert all(b > 0 for b in tr0.exchange_bytes_per_step.values())
    snap = regs[0].snapshot()
    c = snap["counters"]
    assert c["trainer_hier_wire_bytes_total"] > 0
    assert c["trainer_hier_local_bytes_total"] > 0
    from lightctr_tpu.obs import labeled

    assert c[labeled("trainer_exchange_algo_total",
                     table="v", algo="hier")] == steps


def test_hier_coded_wire_tracks_oracle_and_carries_drain(rng):
    """codec="q8_ef" (ISSUE 13): the quantized error-feedback wire keeps
    the trajectory within the EF bound of the exact run — loss tracks
    the dense-psum oracle to ~1e-3 where the codec moves ~KB-scale
    payloads as 1-byte codes — hosts stay bit-identical (they decode the
    same bytes), MEMBER and OWNER EF carries drain to sub-bucket noise,
    and the wire-codec honesty counters record a real >=3x compression
    of the table payloads plus a nonzero shared-id-stream saving (w and
    v share the fids stream)."""
    f, dim, steps = 512, 8, 5
    full = _fm_batch(rng, 128, f)
    halves = [{k: v[:64] for k, v in full.items()},
              {k: v[64:] for k, v in full.items()}]
    params = fm.init(jax.random.PRNGKey(0), f, dim)
    cfg = TrainConfig(learning_rate=0.1)
    shards = [SparseReduceShard(n_hosts=2) for _ in range(2)]
    regs = {0: MetricsRegistry(), 1: MetricsRegistry()}
    try:
        results = _run_hier_hosts(
            params, cfg, halves, [s.address for s in shards], 2, 2, steps,
            registries=regs, codec="q8_ef",
        )
        # owner-side carries live on the shards: read before close
        owner_mass = [s.stats()["owner_ef_mass"] for s in shards]
        coded_rounds = sum(s.stats()["coded_rounds"] for s in shards)
    finally:
        for s in shards:
            s.close()

    oracle = SparseTableCTRTrainer(
        params, fm.logits, cfg,
        sparse_tables={"w": ["fids"], "v": ["fids"]},
        fused_fn=fm.logits_with_l2,
    )
    oracle.health = None
    o_losses = [float(oracle.train_step(full)) for _ in range(steps)]

    l0, p0, tr0 = results[0]
    l1, p1, _ = results[1]
    # hosts decode identical bytes -> bit-identical replicas
    np.testing.assert_allclose(l0, l1, rtol=0, atol=0)
    for k in ("w", "v"):
        np.testing.assert_array_equal(p0[k], p1[k])
    # the EF bound: the coded trajectory tracks the exact oracle to well
    # under the gradient scale (the fp32-wire run matches the oracle to
    # ~1e-5 here; the codec adds only delayed sub-bucket noise)
    np.testing.assert_allclose(l0, o_losses, rtol=0, atol=2e-3)

    client = tr0._hier_client
    assert client.carry_mass() > 0.0  # EF is live
    # member carries drain to SUB-BUCKET noise: each carried row is the
    # last encode's quantization error, bounded by half a bucket of a
    # dynamic range that tracks the (shrinking) gradient scale
    for t, carry in client._carry.items():
        assert carry.max_abs() < 5e-3, (t, carry.max_abs())
    # owner carries too (per reduce shard, per table)
    assert coded_rounds >= 2 * steps  # w and v rounds, every step
    for shard_mass in owner_mass:
        assert shard_mass  # the shards actually carried
        for t, m in shard_mass.items():
            assert m < 2.0, (t, m)  # sum|carry| over O(1e3) rows
    # wire-codec honesty counters: measured socket bytes >=3x under the
    # fp32 equivalent (the exact dense+loss stream dilutes the table
    # payloads' ~4x), and the shared fids stream saved real id bytes
    c = regs[0].snapshot()["counters"]
    packed = c["trainer_hier_wire_packed_bytes_total"]
    fp32_eq = c["trainer_hier_wire_fp32_bytes_total"]
    assert packed > 0 and fp32_eq > 3.0 * packed, (packed, fp32_eq)
    assert c["trainer_hier_wire_id_saved_bytes_total"] > 0
    assert regs[0].snapshot()["gauges"]["trainer_hier_wire_ef_mass"] > 0


def test_hier_trainer_local_overflow_falls_back_to_allgather(rng):
    """A batch skewed onto one LOCAL owner (every id ≡ 0 mod local_n)
    would overflow the local reduce-scatter buckets: the host capacity
    check routes the LOCAL merge to the allgather program (counted in
    ``trainer_rs_fallback_total``), the wire payload is unchanged, and
    the trajectory still matches the oracle — hosts do NOT need to agree
    on the local program family."""
    f, dim, steps, local_n = 2048, 16, 3, 4
    full = _fm_batch(rng, 1024, f, nnz=8)
    # skew HOST 0's ids onto local owner 0; host 1 keeps a natural batch
    skewed = np.maximum(full["fids"][:512] // local_n, 1) * local_n
    full["fids"][:512] = skewed.astype(np.int32)
    halves = [{k: v[:512] for k, v in full.items()},
              {k: v[512:] for k, v in full.items()}]
    params = fm.init(jax.random.PRNGKey(1), f, dim)
    cfg = TrainConfig(learning_rate=0.05)
    shards = [SparseReduceShard(n_hosts=2)]
    regs = {0: MetricsRegistry(), 1: MetricsRegistry()}
    try:
        results = _run_hier_hosts(
            params, cfg, halves, [s.address for s in shards], 2, local_n,
            steps, registries=regs,
        )
    finally:
        for s in shards:
            s.close()
    tr0, tr1 = results[0][2], results[1][2]
    # the regime under test: the local pick IS reduce-scatter, host 0's
    # skew overflows it (fallback every step), host 1 never does
    plan0 = tr0._hier_local_plan(halves[0])
    assert plan0["v"][1] == "sparse_rs", plan0
    assert not tr0._rs_batch_fits(halves[0], plan0)
    assert tr1._rs_batch_fits(halves[1], tr1._hier_local_plan(halves[1]))
    assert regs[0].snapshot()["counters"][
        "trainer_rs_fallback_total"] == steps
    assert "trainer_rs_fallback_total" not in \
        regs[1].snapshot()["counters"]
    assert tr0._hier_fb_local_policy["v"] == "sparse"
    assert tr1.hier_local_policy["v"] == "sparse_rs"
    oracle = SparseTableCTRTrainer(
        params, fm.logits, cfg,
        sparse_tables={"w": ["fids"], "v": ["fids"]},
        fused_fn=fm.logits_with_l2,
    )
    oracle.health = None
    o_losses = [float(oracle.train_step(full)) for _ in range(steps)]
    np.testing.assert_allclose(results[0][0], o_losses, rtol=1e-4,
                               atol=1e-6)
    for k in ("w", "v"):
        np.testing.assert_allclose(
            results[0][1][k], np.asarray(oracle.params[k]),
            rtol=1e-4, atol=1e-5)


def test_hier_trainer_rejects_unsupported_configs(rng):
    shard = SparseReduceShard(n_hosts=1)
    client = HierExchangeClient([shard.address], host_id=0, n_hosts=1)
    params = fm.init(jax.random.PRNGKey(0), 64, 4)
    try:
        with pytest.raises(ValueError, match="mesh"):
            SparseTableCTRTrainer(
                params, fm.logits, TrainConfig(),
                sparse_tables={"w": ["fids"], "v": ["fids"]},
                hier_exchange=client,
            )
        with pytest.raises(ValueError, match="compress_bits"):
            SparseTableCTRTrainer(
                params, fm.logits, TrainConfig(),
                sparse_tables={"w": ["fids"], "v": ["fids"]},
                mesh=make_mesh(MeshSpec(data=2)), compress_bits=8,
                hier_exchange=client,
            )
        tr = SparseTableCTRTrainer(
            params, fm.logits, TrainConfig(),
            sparse_tables={"w": ["fids"], "v": ["fids"]},
            mesh=make_mesh(MeshSpec(data=2)), hier_exchange=client,
        )
        with pytest.raises(ValueError, match="scan"):
            tr.fit_fullbatch_scan(_fm_batch(rng, 16, 64), 2)
    finally:
        client.close()
        shard.close()


# -- the 2-process x multi-replica acceptance -----------------------------

_WORKER = textwrap.dedent(
    """
    import sys
    host_id, local_n, port0, port1, data_path, out_path = (
        int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]),
        int(sys.argv[4]), sys.argv[5], sys.argv[6])
    codec = sys.argv[7] if len(sys.argv) > 7 else "f32"
    # "<codec>+stream" turns on the streaming rendezvous: chunked
    # windows, striped dispatch, dispatch/commit overlap (ISSUE 16)
    chunk_rows = None
    if codec.endswith("+stream"):
        codec, chunk_rows = codec[: -len("+stream")], 16
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    from lightctr_tpu.utils.devicecheck import pin_cpu_platform
    pin_cpu_platform(local_n)
    import numpy as np
    import jax
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.core.mesh import MeshSpec, make_mesh
    from lightctr_tpu.dist.hier import HierExchangeClient
    from lightctr_tpu.models import fm
    from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer

    data = np.load(data_path)
    half = slice(None, 128) if host_id == 0 else slice(128, None)
    batch = {k: data[k][half] for k in
             ("fids", "fields", "vals", "mask", "labels")}
    params = fm.init(jax.random.PRNGKey(0), int(data["f"]), int(data["dim"]))
    client = HierExchangeClient(
        [("127.0.0.1", port0), ("127.0.0.1", port1)],
        host_id=host_id, n_hosts=2, codec=codec, chunk_rows=chunk_rows)
    tr = SparseTableCTRTrainer(
        params, fm.logits, TrainConfig(learning_rate=0.1),
        sparse_tables={"w": ["fids"], "v": ["fids"]},
        fused_fn=fm.logits_with_l2,
        mesh=make_mesh(MeshSpec(data=local_n)), hier_exchange=client)
    tr.health = None
    losses = [float(tr.train_step(batch)) for _ in range(4)]
    np.savez(
        out_path,
        losses=np.asarray(losses, np.float64),
        w=np.asarray(tr.params["w"]),
        v=np.asarray(tr.params["v"]),
        socket_bytes=np.int64(client.bytes_sent + client.bytes_received),
        wire_model_bytes=np.int64(
            sum(tr.exchange_bytes_per_step.values())
            + tr._hier_wire_dense_bytes),
        policy_hier=np.bool_(
            set(tr.exchange_policy.values()) == {"hier"}),
        carry_mass=np.float64(client.carry_mass()),
        id_saved=np.int64(client.shared_id_saved_bytes),
        chunk_pushes=np.int64(client.chunk_pushes_total),
        chunk_rows=np.int64(client.chunk_rows_total),
        chunk_capacity=np.int64(client.chunk_capacity_rows_total),
    )
    client.close()
    print("WORKER_DONE", host_id, flush=True)
    """
)


def test_two_process_hier_acceptance(tmp_path, rng):
    """THE acceptance criterion: 2 OS processes x {2, then 4} local
    replicas train through the reduce rendezvous hosted here.  The
    hierarchical trajectory matches the dense-psum-exact oracle (the
    single-device full-batch trainer), both hosts agree bit-for-bit, and
    the measured cross-host wire bytes/step stay FLAT (+-10%) when the
    local replica count doubles — the whole point of merging before the
    DCN."""
    f, dim = 512, 8
    full = _fm_batch(rng, 256, f)
    data_path = tmp_path / "batch.npz"
    np.savez(data_path, f=np.int64(f), dim=np.int64(dim), **full)

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # each worker pins its OWN device count
    env["PYTHONPATH"] = REPO_ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    script = tmp_path / "hier_worker.py"
    script.write_text(_WORKER)

    # every config runs CONCURRENTLY (each against its own pair of
    # reduce shards) — eight workers, one wall-clock wait: fp32 wire at
    # {2, 4} local replicas, the q8_ef CODED wire at 2 replicas (the
    # ISSUE 13 acceptance: trajectory within the EF bound of the
    # fp32-wire run, wire bytes well under it), and the STREAMING
    # rendezvous (ISSUE 16) — chunked + striped + overlapped q8_ef —
    # which must keep every one of those guarantees
    cases = [("r2", 2, "f32"), ("r4", 4, "f32"), ("q8", 2, "q8_ef"),
             ("qs", 2, "q8_ef+stream")]
    configs = {}
    try:
        for name, local_n, codec in cases:
            shards = [SparseReduceShard(n_hosts=2) for _ in range(2)]
            procs = []
            for hid in (0, 1):
                out = tmp_path / f"{name}_h{hid}.npz"
                procs.append((out, subprocess.Popen(
                    [sys.executable, str(script), str(hid), str(local_n),
                     str(shards[0].address[1]), str(shards[1].address[1]),
                     str(data_path), str(out), codec],
                    stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                    text=True, env=env, cwd=REPO_ROOT,
                )))
            configs[name] = (shards, procs)
        by_case = {}
        for name, (shards, procs) in configs.items():
            outs = []
            for out, p in procs:
                stdout, stderr = p.communicate(timeout=240)
                assert p.returncode == 0, stderr[-3000:]
                assert "WORKER_DONE" in stdout
                outs.append(dict(np.load(out)))
            by_case[name] = outs
    finally:
        for shards, procs in configs.values():
            for _, p in procs:
                if p.poll() is None:
                    p.kill()
            for s in shards:
                s.close()
    by_replicas = {2: by_case["r2"], 4: by_case["r4"]}

    # oracle: single-device full-batch trainer in THIS process
    params = fm.init(jax.random.PRNGKey(0), f, dim)
    oracle = SparseTableCTRTrainer(
        params, fm.logits, TrainConfig(learning_rate=0.1),
        sparse_tables={"w": ["fids"], "v": ["fids"]},
        fused_fn=fm.logits_with_l2,
    )
    oracle.health = None
    o_losses = [float(oracle.train_step(full)) for _ in range(4)]

    for local_n, (h0, h1) in by_replicas.items():
        assert bool(h0["policy_hier"]) and bool(h1["policy_hier"])
        np.testing.assert_allclose(h0["losses"], h1["losses"],
                                   rtol=0, atol=1e-6)
        np.testing.assert_allclose(h0["losses"], o_losses,
                                   rtol=1e-4, atol=1e-6, err_msg=(
                                       f"local_n={local_n} trajectory"))
        for k in ("w", "v"):
            np.testing.assert_array_equal(h0[k], h1[k])
            np.testing.assert_allclose(
                h0[k], np.asarray(oracle.params[k]), rtol=1e-4, atol=1e-5)

    # cross-host bytes FLAT in local replica count: the per-host batch is
    # fixed, so doubling the replicas must not move the wire bytes beyond
    # the +-10% acceptance band — in the model AND on the real sockets
    w2 = float(by_replicas[2][0]["wire_model_bytes"])
    w4 = float(by_replicas[4][0]["wire_model_bytes"])
    assert abs(w4 - w2) <= 0.1 * w2, (w2, w4)
    s2 = float(by_replicas[2][0]["socket_bytes"])
    s4 = float(by_replicas[4][0]["socket_bytes"])
    assert abs(s4 - s2) <= 0.1 * s2, (s2, s4)

    # -- the CODED wire (ISSUE 13) ------------------------------------
    q0, q1 = by_case["q8"]
    assert bool(q0["policy_hier"]) and bool(q1["policy_hier"])
    # hosts decode identical bytes -> bit-identical, across PROCESSES
    np.testing.assert_allclose(q0["losses"], q1["losses"], rtol=0, atol=0)
    for k in ("w", "v"):
        np.testing.assert_array_equal(q0[k], q1[k])
    # trajectory within the EF bound of the fp32-wire run: the codec
    # adds only delayed sub-bucket noise, never a divergence
    np.testing.assert_allclose(
        q0["losses"], by_replicas[2][0]["losses"], rtol=0, atol=2e-3,
        err_msg="q8_ef trajectory left the EF bound of the fp32 wire",
    )
    # the wire itself shrank (dense+loss stream stays exact fp32, so the
    # measured whole-step ratio is below the tables' ~4x — the bench's
    # hier_grid isolates that number)
    sq = float(q0["socket_bytes"])
    assert sq < 0.4 * s2, (sq, s2)
    # the member EF carry drained to sub-bucket noise, and the shared
    # fids stream (w + v) saved real id bytes on the wire
    assert 0.0 < float(q0["carry_mass"]) < 1.0, q0["carry_mass"]
    assert int(q0["id_saved"]) > 0

    # -- the STREAMING rendezvous (ISSUE 16) --------------------------
    s0, s1 = by_case["qs"]
    assert bool(s0["policy_hier"]) and bool(s1["policy_hier"])
    # chunking really happened: more frames than the 2-shard minimum,
    # and the windows shipped real rows under their declared capacity
    assert int(s0["chunk_pushes"]) > int(q0["chunk_pushes"])
    assert 0 < int(s0["chunk_rows"]) <= int(s0["chunk_capacity"])
    # chunked + striped + overlapped rounds keep the PROCESS-level
    # bit-identity: both hosts decode the same accumulator bytes
    np.testing.assert_allclose(s0["losses"], s1["losses"], rtol=0, atol=0)
    for k in ("w", "v"):
        np.testing.assert_array_equal(s0[k], s1[k])
    # and the trajectory stays within the SAME EF bound of the fp32-wire
    # run the unchunked coded wire is held to (per-chunk dynamic ranges
    # change the quantization grid, not the contract)
    np.testing.assert_allclose(
        s0["losses"], by_replicas[2][0]["losses"], rtol=0, atol=2e-3,
        err_msg="streaming q8_ef trajectory left the EF bound",
    )
    # the streamed wire stays compressed: same budget band as unchunked
    # q8_ef despite the per-chunk section headers
    assert float(s0["socket_bytes"]) < 0.5 * s2, (s0["socket_bytes"], s2)
