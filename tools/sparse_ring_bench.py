"""Sparse vs dense gradient exchange under data parallelism — the
O(touched) vs O(vocab) evidence artifact.

A Criteo-like batch touches a few thousand rows of a 2^20-row table, yet
the dense data-parallel exchange ships the whole [vocab, dim] gradient
every step.  This bench sweeps the vocabulary (density = touched/vocab)
on the 8-member virtual mesh and reports, per table leaf:

  - bytes/step each member actually transmits under the hybrid trainer's
    decision, read from the trainer's LIVE telemetry
    (``SparseTableCTRTrainer.exchange_bytes_per_step`` + the obs registry
    counters ``trainer_sparse_exchange_bytes_total`` /
    ``trainer_dense_ring_bytes_total``) — the same series a production
    scrape reads, so this artifact and live monitoring cannot disagree;
  - bytes/step the dense ring/psum exchange WOULD have cost (the
    counterfactual baseline, ``dense_ring_bytes``) — linear in vocab;
  - the SparCML-style static switch decision the hybrid trainer takes
    (``prefer_sparse_exchange`` / ``SparseTableCTRTrainer.exchange_policy``);
  - measured examples/s for both trainers and the max loss-trajectory
    divergence between them over the timed steps (step-level parity).

Run:  python -m tools.sparse_ring_bench [--steps 4] [--out SPARSE_RING_BENCH.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from lightctr_tpu.utils.devicecheck import pin_cpu_platform  # noqa: E402

N_DEV = int(os.environ.get("SPARSE_BENCH_DEVS", "8"))
pin_cpu_platform(N_DEV)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from lightctr_tpu import TrainConfig  # noqa: E402
from lightctr_tpu.core.mesh import MeshSpec, make_mesh  # noqa: E402
from lightctr_tpu.dist import (  # noqa: E402
    dense_ring_bytes,
    pick_exchange_algo,
    rs_default_caps,
    rs_fits,
    sparse_all_reduce,
    sparse_exchange_bytes,
    sparse_reduce_scatter,
    sparse_rs_bytes,
)
from lightctr_tpu.obs import MetricsRegistry, set_enabled  # noqa: E402
from lightctr_tpu.models import fm, widedeep  # noqa: E402
from lightctr_tpu.models.ctr_trainer import CTRTrainer  # noqa: E402
from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer  # noqa: E402

# Criteo-shaped workload: 39 fields, a categorical id per field
N_FIELDS = 39
DIM = 16
BATCH = 2048


def synth_batch(rng, vocab: int):
    fids = rng.integers(0, vocab, size=(BATCH, N_FIELDS)).astype(np.int32)
    fields = np.tile(np.arange(N_FIELDS, dtype=np.int32), (BATCH, 1))
    mask = np.ones((BATCH, N_FIELDS), np.float32)
    rep, rep_mask = widedeep.field_representatives(fids, fields, mask,
                                                   N_FIELDS)
    return {
        "fids": fids, "fields": fields,
        "vals": np.ones((BATCH, N_FIELDS), np.float32), "mask": mask,
        "labels": (rng.random(BATCH) > 0.5).astype(np.float32),
        "rep_fids": rep, "rep_mask": rep_mask,
    }


def timed_steps(tr, batch, steps: int):
    """examples/s over ``steps`` post-compile steps plus the loss at each
    (the parity trace)."""
    losses = [float(tr.train_step(batch))]  # compile + step 0
    t0 = time.perf_counter()
    for _ in range(steps):
        losses.append(float(tr.train_step(batch)))
    wall = time.perf_counter() - t0
    return BATCH * steps / wall, losses


def _dense_oracle(vocab, dim, uids, rows):
    out = np.zeros((vocab, dim), np.float32)
    np.add.at(out, np.asarray(uids).reshape(-1),
              np.asarray(rows).reshape(-1, dim))
    return out


def _timed_exchange(fn, reps=3):
    """Post-compile wall time of one jitted exchange (median of reps)."""
    out = fn()
    jax.block_until_ready(out)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def rs_grid(rng, vocab=2048, dim=16,
            densities=(0.05, 0.25, 0.5), worlds=(2, 4, 8)):
    """(density x world_size) grid: allgather vs reduce-scatter bytes per
    member per step (derived from the STATIC payload shapes each
    collective actually ships — the same helpers the trainer's live
    telemetry uses), parity of both against the dense oracle, the
    three-way trace-time pick, and the measured byte winner.  Shows the
    rs-variant's per-member bytes staying roughly flat in world size at
    fixed density while the allgather's grow linearly."""
    cells = []
    for density in densities:
        k = max(1, int(vocab * density))
        for n in worlds:
            mesh = make_mesh(MeshSpec(data=n))
            uids = np.zeros((n, k), np.int64)
            rows = np.zeros((n, k, dim), np.float32)
            for m in range(n):
                u = np.unique(rng.integers(1, vocab, size=k))
                uids[m, :u.size] = u
                rows[m, :u.size] = rng.normal(size=(u.size, dim))
            bucket, shard = rs_default_caps(n, k, vocab)
            fits = rs_fits([uids[m][uids[m] > 0] for m in range(n)],
                           n, bucket, shard)
            ju, jr = jnp.asarray(uids), jnp.asarray(rows)
            want = sum(_dense_oracle(vocab, dim, uids[m], rows[m])
                       for m in range(n)) / n

            gu, merged = sparse_all_reduce(mesh, ju, jr)
            np.testing.assert_allclose(
                _dense_oracle(vocab, dim, np.asarray(gu)[0],
                              np.asarray(merged)[0]),
                want, rtol=1e-5, atol=1e-6)
            ag_t = _timed_exchange(lambda: sparse_all_reduce(mesh, ju, jr))

            rs_t = None
            overflow = None
            if fits:
                ru, rm, over = sparse_reduce_scatter(
                    mesh, ju, jr, bucket_cap=bucket, shard_cap=shard)
                overflow = int(np.asarray(over).sum())
                assert overflow == 0, (density, n, overflow)
                np.testing.assert_allclose(
                    _dense_oracle(vocab, dim, np.asarray(ru)[0],
                                  np.asarray(rm)[0]),
                    want, rtol=1e-5, atol=1e-6)
                rs_t = _timed_exchange(lambda: sparse_reduce_scatter(
                    mesh, ju, jr, bucket_cap=bucket, shard_cap=shard))

            ag_b = sparse_exchange_bytes(n, k, dim)
            rs_b = sparse_rs_bytes(n, bucket, shard, dim)
            dense_b = dense_ring_bytes(vocab, dim, n)
            pick, pick_b = pick_exchange_algo(n, k, vocab, dim)
            by_bytes = {"sparse": ag_b, "sparse_rs": rs_b, "dense": dense_b}
            winner = min(by_bytes, key=by_bytes.get)
            if pick == winner:
                assert pick_b == by_bytes[winner], (density, n, by_bytes)
            else:
                # the only sanctioned divergence: rs is the raw byte
                # argmin but sits inside the RS_DENSE_MARGIN near-tie
                # band vs the dense ring, where the pick deliberately
                # declines it (latency hysteresis)
                from lightctr_tpu.dist.collectives import RS_DENSE_MARGIN

                assert (winner == "sparse_rs"
                        and rs_b > RS_DENSE_MARGIN * dense_b), (
                    "trace-time pick must match the measured byte winner "
                    "outside the rs/dense hysteresis band",
                    density, n, pick, by_bytes,
                )
            cells.append({
                "vocab": vocab, "dim": dim, "density": density,
                "world_size": n, "k_per_member": k,
                "rs_caps": {"bucket": bucket, "shard": shard,
                            "fits": bool(fits)},
                "bytes_per_step_per_member": {
                    "sparse_allgather": ag_b,
                    "sparse_rs": rs_b,
                    "dense_ring": dense_b,
                },
                "pick": pick,
                "measured_byte_winner": winner,
                "exchange_wall_s": {
                    "sparse_allgather": round(ag_t, 6),
                    "sparse_rs": round(rs_t, 6) if rs_t is not None
                    else None,
                },
                "rs_overflow": overflow,
                "rs_vs_allgather_x": round(ag_b / rs_b, 2),
            })
            print(f"density={density} n={n}: ag={ag_b:,}B rs={rs_b:,}B "
                  f"dense={dense_b:,}B pick={pick}", file=sys.stderr,
                  flush=True)
    # crossover rows: per density, the smallest world size where the rs
    # variant wins the three-way pick
    crossover = []
    for density in densities:
        row = {"density": density, "rs_wins_from_world": None}
        for c in cells:
            if c["density"] == density and c["pick"] == "sparse_rs":
                row["rs_wins_from_world"] = c["world_size"]
                break
        crossover.append(row)
    return cells, crossover


def rs_trainer_cell(rng, steps=4):
    """One LIVE hybrid-trainer cell in the rs-picked regime (FM, dim 16,
    half-vocab density on the full mesh): the trace-time pick takes
    sparse_rs, live bytes come from the trainer's registry counters
    (trainer_sparse_rs_bytes_total), and the loss trajectory matches the
    dense-psum trainer."""
    f, rows_n, nnz, dim = 4096, 2048, 8, 16
    mesh = make_mesh(MeshSpec(data=N_DEV))
    batch = {
        "fids": rng.integers(1, f, size=(rows_n, nnz)).astype(np.int32),
        "fields": np.zeros((rows_n, nnz), np.int32),
        "vals": np.ones((rows_n, nnz), np.float32),
        "mask": np.ones((rows_n, nnz), np.float32),
        "labels": (rng.random(rows_n) > 0.5).astype(np.float32),
    }
    params = fm.init(jax.random.PRNGKey(0), f, dim)
    cfg = TrainConfig(learning_rate=0.05)
    sparse_tr = SparseTableCTRTrainer(
        params, fm.logits, cfg, sparse_tables={"w": ["fids"], "v": ["fids"]},
        fused_fn=fm.logits_with_l2, mesh=mesh,
    )
    sparse_tr.telemetry = MetricsRegistry()
    dense_tr = CTRTrainer(params, fm.logits, cfg,
                          fused_fn=fm.logits_with_l2, mesh=mesh)
    ex_s, l_s = timed_steps(sparse_tr, batch, steps)
    ex_d, l_d = timed_steps(dense_tr, batch, steps)
    assert sparse_tr.exchange_policy.get("v") == "sparse_rs", \
        sparse_tr.exchange_policy
    snap = sparse_tr.telemetry.snapshot()
    n_steps = snap["counters"]["trainer_steps_total"]
    rs_counted = snap["counters"].get("trainer_sparse_rs_bytes_total", 0)
    assert rs_counted == sparse_tr.exchange_bytes_per_step["v"] * n_steps
    k = batch["fids"].size // N_DEV
    return {
        "model": f"fm vocab={f} dim={dim} batch={rows_n}x{nnz}",
        "exchange_policy": dict(sparse_tr.exchange_policy),
        "bytes_per_step_per_member": {
            "live_exchange": dict(sparse_tr.exchange_bytes_per_step),
            "sparse_allgather_counterfactual": {
                "w": sparse_exchange_bytes(N_DEV, k, 1),
                "v": sparse_exchange_bytes(N_DEV, k, dim),
            },
        },
        "registry_counters": {
            kk: v for kk, v in snap["counters"].items() if "bytes" in kk
        },
        "rs_fallback_steps": snap["counters"].get(
            "trainer_rs_fallback_total", 0),
        "examples_per_sec": {"sparse_rs": round(ex_s, 1),
                             "dense_psum": round(ex_d, 1)},
        "max_loss_diff_vs_dense_psum": float(
            np.max(np.abs(np.asarray(l_s) - np.asarray(l_d)))),
    }


def hier_grid(rng, vocab=4096, dim=16, host_rows=1024, nnz=8,
              replicas=(1, 2, 4), n_hosts=2):
    """(local-replicas x world) grid for the HIERARCHICAL two-level
    exchange (ISSUE 10): a FIXED per-host batch is split across R local
    replicas, merged in-jit over the local mesh, and exactly one merged
    payload per host rides the reduce rendezvous (hosted in-process over
    real sockets).  Wire bytes come from the client byte counters — the
    acceptance claim is that they stay FLAT as R doubles, while the
    per-replica-push counterfactual (today's PS wire: every replica ships
    its own rows) grows linearly."""
    from lightctr_tpu.dist import hier_wire_bytes, sparse_exchange_bytes
    from lightctr_tpu.dist.hier import HierExchangeClient, SparseReduceShard

    # per-host id streams FIXED across the grid (the per-host union is
    # what rides the wire, so cells are byte-comparable across R)
    host_ids = [rng.integers(1, vocab, size=(host_rows, nnz)).astype(np.int64)
                for _ in range(n_hosts)]
    cells = []
    for r_local in replicas:
        mesh = make_mesh(MeshSpec(data=r_local))
        shards = [SparseReduceShard(n_hosts=n_hosts) for _ in range(2)]
        addrs = [s.address for s in shards]
        clients = [HierExchangeClient(addrs, host_id=h, n_hosts=n_hosts)
                   for h in range(n_hosts)]
        try:
            merged_per_host = []
            for h in range(n_hosts):
                # per-replica dedup of the host batch's R shards, then the
                # in-jit local merge (SUM) — the trainer's program-A path
                shard_rows = host_rows // r_local
                k = shard_rows * nnz
                uids = np.zeros((r_local, k), np.int64)
                rows = np.zeros((r_local, k, dim), np.float32)
                for m in range(r_local):
                    ids = host_ids[h][m * shard_rows:(m + 1) * shard_rows]
                    u = np.unique(ids)
                    uids[m, :u.size] = u
                    rows[m, :u.size] = rng.normal(size=(u.size, dim))
                gu, gm = sparse_all_reduce(
                    mesh, jnp.asarray(uids), jnp.asarray(rows),
                    average=False,
                )
                u0 = np.asarray(gu)[0]
                m0 = np.asarray(gm)[0].reshape(len(u0), dim)
                # the trainer's own pad-strip/sort (one copy of the
                # wire-facing convention, bench and trainer alike)
                merged_per_host.append(
                    SparseTableCTRTrainer._hier_strip_pads(u0, m0))
            # the wire hop: push every host, then pull (one process plays
            # all hosts, so pushes must land before any pull blocks)
            b0 = [c.bytes_sent + c.bytes_received for c in clients]
            for h, c in enumerate(clients):
                c.push(0, *merged_per_host[h], epoch=0)
            pulls = [c.pull(0, 0, dim) for c in clients]
            sock = [c.bytes_sent + c.bytes_received - b for c, b in
                    zip(clients, b0)]
            k_out = len(merged_per_host[0][0])
            k_in = len(pulls[0][0])
            per_replica_k = host_rows // r_local * nnz
            cells.append({
                "local_replicas": r_local,
                "n_hosts": n_hosts,
                "world": r_local * n_hosts,
                "host_union": k_out,
                "global_union": k_in,
                "wire_bytes_measured_host0": int(sock[0]),
                "wire_bytes_model": hier_wire_bytes(k_out, k_in, dim),
                "local_ici_bytes_model": sparse_exchange_bytes(
                    r_local, per_replica_k, dim) if r_local > 1 else 0,
                "per_replica_push_counterfactual": int(
                    r_local * hier_wire_bytes(
                        len(np.unique(host_ids[0][:host_rows // r_local])),
                        k_in, dim,
                    )),
            })
            print(f"hier r={r_local}: wire {sock[0]:,}B measured "
                  f"(model {cells[-1]['wire_bytes_model']:,}B), "
                  f"counterfactual {cells[-1]['per_replica_push_counterfactual']:,}B",
                  file=sys.stderr, flush=True)
        finally:
            for c in clients:
                c.close()
            for s in shards:
                s.close()
    # the acceptance shape: measured wire bytes flat (+-10%) in R while
    # the per-replica counterfactual grows
    measured = [c["wire_bytes_measured_host0"] for c in cells]
    assert max(measured) <= 1.1 * min(measured), measured
    assert cells[-1]["per_replica_push_counterfactual"] > \
        2.0 * cells[-1]["wire_bytes_model"], cells[-1]
    return cells


def hier_codec_grid(rng, vocab=8192, dims=(1, 16), host_rows=1024, nnz=8,
                    n_hosts=2):
    """Wire-codec cells for the hier grid (ISSUE 13): the SAME per-host
    merged payloads — an FM-shaped 2-table group (w dim 1 + v dim 16)
    sharing one fids stream — pushed and pulled through real sockets
    under three wires: the PR 10 default (exact fp32, per-table frames),
    the q8_ef coded wire WITHOUT grouping (codec saving alone), and the
    q8_ef coded wire with grouped shared-id frames (the shipped
    configuration).  The headline is measured socket bytes, not a model;
    the shared-id-stream saving is reported separately (ungrouped minus
    grouped, plus the client's own counter)."""
    from lightctr_tpu.dist.hier import HierExchangeClient, SparseReduceShard

    host_payloads = []
    for h in range(n_hosts):
        ids = rng.integers(1, vocab, size=(host_rows, nnz)).astype(np.int64)
        u = np.unique(ids)
        rows = [(0.3 * rng.normal(size=(u.size, d))).astype(np.float32)
                for d in dims]
        host_payloads.append((u, rows))

    def run_wire(codec, grouped):
        shards = [SparseReduceShard(n_hosts=n_hosts) for _ in range(2)]
        clients = [
            HierExchangeClient([s.address for s in shards], host_id=h,
                               n_hosts=n_hosts, codec=codec)
            for h in range(n_hosts)
        ]
        try:
            b0 = [c.bytes_sent + c.bytes_received for c in clients]
            for h, c in enumerate(clients):
                u, rows = host_payloads[h]
                if grouped:
                    c.push_group(list(range(len(dims))), u, rows, epoch=0)
                else:
                    for ti, r in enumerate(rows):
                        c.push(ti, u, r, epoch=0)
            for c in clients:
                if grouped:
                    c.pull_group(list(range(len(dims))), 0, list(dims))
                else:
                    for ti, d in enumerate(dims):
                        c.pull(ti, 0, d)
            moved = [c.bytes_sent + c.bytes_received - b
                     for c, b in zip(clients, b0)]
            return (moved[0], clients[0].shared_id_saved_bytes,
                    clients[0].carry_mass(),
                    shards[0].stats()["owner_ef_mass"])
        finally:
            for c in clients:
                c.close()
            for s in shards:
                s.close()

    fp32_b, _, _, _ = run_wire("f32", grouped=False)
    q8u_b, _, _, _ = run_wire("q8_ef", grouped=False)
    q8g_b, saved_counter, member_mass, owner_mass = run_wire(
        "q8_ef", grouped=True
    )
    n_vals = sum(len(u) * sum(dims) for u, _ in host_payloads[:1])
    cell = {
        "model": f"FM-shaped group dims={list(dims)} sharing one id "
                 f"stream, vocab={vocab}, {n_hosts} hosts, host union "
                 f"{len(host_payloads[0][0])}",
        "fp32_wire_bytes": int(fp32_b),
        "q8_ef_wire_bytes": int(q8g_b),
        "reduction_x": round(fp32_b / q8g_b, 3),
        "q8_ef_ungrouped_bytes": int(q8u_b),
        "codec_only_reduction_x": round(fp32_b / q8u_b, 3),
        "shared_id_stream_saving_bytes": int(q8u_b - q8g_b),
        "shared_id_saved_bytes_counter": int(saved_counter),
        "member_ef_mass": round(member_mass, 3),
        "member_ef_mass_per_value": round(member_mass / n_vals, 6),
        "owner_ef_mass_shard0": owner_mass,
    }
    assert cell["reduction_x"] >= 4.0, cell
    assert cell["shared_id_stream_saving_bytes"] > 0, cell
    print(f"hier codec: fp32 {fp32_b:,}B -> q8_ef {q8g_b:,}B "
          f"({cell['reduction_x']}x; codec alone "
          f"{cell['codec_only_reduction_x']}x, shared ids save "
          f"{cell['shared_id_stream_saving_bytes']:,}B)",
          file=sys.stderr, flush=True)
    return cell


def hier_stream_grid(rng, dim=16, vocab=16384, draws=32768,
                     hosts_sweep=(2, 4), link_bps=6.25e6, rounds=4,
                     chunk_rows=512):
    """Barrier-vs-streaming A/B for the rendezvous (ISSUE 16), under a
    PACED wire standing in for a constrained DCN (the LIGHTCTR_LINK_BW
    regime): every push frame sleeps ``bytes / link_bps`` before
    transmitting, so the outbound leg costs what the slow link would.
    The pace is per CONNECTION — each rendezvous shard is its own link,
    the way distinct remote shard hosts are — so striping multiplies the
    aggregate bandwidth, which is the point.  The barrier arm runs the
    pre-streaming shape end to end: ONE unsplit shard
    (``streaming=False``), compute then push then pull, serially.  The
    streaming arm is the shipped configuration: two striped shards,
    chunked pushes dispatched FIRST, the compute leg overlapped under
    the in-flight transmissions, commit, then pull.  Reported per
    n_hosts: measured step walls, the speedup (>=1.5x asserted), and
    the shard peak-round-bytes column — the streaming accumulator is
    bounded by the UNION, so it stays flat (+-10% asserted) when
    n_hosts doubles while the barrier buffer (every contribution held
    to the merge) grows ~linearly.  A stripe-scaling subcell isolates
    the striping term: the same streamed payload over 1 vs 2 shards,
    commit wall ~halving."""
    from lightctr_tpu.dist.hier import HierExchangeClient, SparseReduceShard

    def paced_client(addrs, h, n, chunked):
        c = HierExchangeClient(addrs, host_id=h, n_hosts=n,
                               chunk_rows=chunk_rows if chunked else None)
        for pc in c.clients:
            real = pc._rpc

            def paced(msg, payload, _real=real):
                # both directions ride the constrained link: the frame
                # out, the (possibly megabyte-scale pull) reply back
                time.sleep(len(payload) / link_bps)
                reply = _real(msg, payload)
                time.sleep(len(reply) / link_bps)
                return reply

            pc._rpc = paced
        return c

    # fixed per-host payloads: heavy union overlap (draws >> vocab / n),
    # so the GLOBAL union — the streaming accumulator's bound — barely
    # moves when n_hosts doubles
    def payload(h):
        g = np.random.default_rng(1000 + h)
        u = np.unique(g.integers(1, vocab, size=draws)).astype(np.int64)
        return u, g.normal(size=(u.size, dim)).astype(np.float32) * 0.1

    payloads = [payload(h) for h in range(max(hosts_sweep))]
    row_b = 8 + dim * 4
    # compute leg sized to the paced per-stripe push wall: the regime
    # where overlap hides the most
    compute_s = payloads[0][0].size * row_b / 2 / link_bps

    def run_arm(n, streaming, n_shards=2):
        import threading

        shards = [SparseReduceShard(n_hosts=n, streaming=streaming)
                  for _ in range(n_shards)]
        addrs = [s.address for s in shards]
        # hosts move in LOCKSTEP (a barrier per round): the A/B measures
        # the step shapes, not the withheld-retry backoff an artificially
        # drifted puller would accumulate waiting on a straggler
        gate = threading.Barrier(n)
        walls = [[] for _ in range(rounds)]
        push_walls = [[] for _ in range(rounds)]
        errors = []

        def host_fn(h):
            c = paced_client(addrs, h, n, chunked=streaming)
            try:
                for ep in range(rounds):
                    gate.wait(timeout=120)
                    t0 = time.perf_counter()
                    if streaming:
                        c.push_async(0, *payloads[h], epoch=ep)
                        time.sleep(compute_s)  # overlapped compute
                        c.commit()
                    else:
                        time.sleep(compute_s)  # serial compute
                        c.push(0, *payloads[h], epoch=ep)
                    push_walls[ep].append(time.perf_counter() - t0)
                    c.pull(0, ep, dim)
                    walls[ep].append(time.perf_counter() - t0)
            except Exception as e:  # pragma: no cover - surfaced below
                errors.append((h, repr(e)))
                gate.abort()
            finally:
                c.close()

        threads = [threading.Thread(target=host_fn, args=(h,))
                   for h in range(n)]
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
            peak = max(s.stats()["peak_round_bytes"] for s in shards)
        finally:
            for s in shards:
                s.close()
        assert not errors, errors
        # a round costs what its SLOWEST host paid (barrier semantics);
        # the first round carries the connects, so take the median
        return (float(np.median([max(w) for w in walls])),
                float(np.median([max(w) for w in push_walls])),
                peak)

    cells = []
    for n in hosts_sweep:
        b_wall, _, b_peak = run_arm(n, streaming=False, n_shards=1)
        s_wall, s_push, s_peak = run_arm(n, streaming=True)
        cells.append({
            "n_hosts": n,
            "paced_link_bps": link_bps,
            "compute_s": round(compute_s, 6),
            "barrier_step_s": round(b_wall, 6),
            "streaming_step_s": round(s_wall, 6),
            "speedup_x": round(b_wall / s_wall, 3),
            "shard_peak_round_bytes": {"barrier": int(b_peak),
                                       "streaming": int(s_peak)},
        })
        print(f"hier stream n={n}: barrier {b_wall * 1e3:.1f}ms vs "
              f"streaming {s_wall * 1e3:.1f}ms "
              f"({cells[-1]['speedup_x']}x), peak "
              f"{s_peak:,}B vs {b_peak:,}B barrier",
              file=sys.stderr, flush=True)
    # acceptance: the overlapped step is >=1.5x faster under the paced
    # link, and the streaming accumulator's peak stays flat (+-10%)
    # when n_hosts doubles while the barrier buffer grows
    for c in cells:
        assert c["speedup_x"] >= 1.5, c
    peaks = [c["shard_peak_round_bytes"]["streaming"] for c in cells]
    assert max(peaks) <= 1.1 * min(peaks), peaks
    assert cells[-1]["shard_peak_round_bytes"]["barrier"] > \
        1.5 * cells[-1]["shard_peak_round_bytes"]["streaming"], cells[-1]

    # stripe scaling: the same streamed payload, 1 vs 2 shards — the
    # paced transmissions run one pipeline per stripe, so the commit
    # wall (no compute overlap here: compute_s still sleeps, the PUSH
    # wall is what shrinks) reflects the aggregate bandwidth doubling
    _, p1, _ = run_arm(2, streaming=True, n_shards=1)
    _, p2, _ = run_arm(2, streaming=True, n_shards=2)
    stripe = {"push_wall_1_shard_s": round(p1, 6),
              "push_wall_2_shards_s": round(p2, 6),
              "bandwidth_scaling_x": round(p1 / p2, 3)}
    assert stripe["bandwidth_scaling_x"] >= 1.3, stripe
    return cells, stripe


def hier_trainer_cell(rng, steps=3):
    """One LIVE hier-trainer cell: two threaded hosts x 2 local replicas
    through the in-process rendezvous — the trace-time policy records
    ``hier`` for every table, live bytes come from the registry's
    per-hop counters, and the loss trajectory matches the single-device
    full-batch oracle (the dense-psum-exact contract)."""
    import threading

    from lightctr_tpu.dist.hier import HierExchangeClient, SparseReduceShard
    from lightctr_tpu.models import fm as fm_mod

    f, dim, rows_n = 2048, 16, 512
    fids = rng.integers(1, f, size=(rows_n, 8)).astype(np.int32)
    full = {
        "fids": fids, "fields": np.zeros_like(fids),
        "vals": np.ones((rows_n, 8), np.float32),
        "mask": np.ones((rows_n, 8), np.float32),
        "labels": (rng.random(rows_n) > 0.5).astype(np.float32),
    }
    halves = [{k: v[:rows_n // 2] for k, v in full.items()},
              {k: v[rows_n // 2:] for k, v in full.items()}]
    params = fm_mod.init(jax.random.PRNGKey(0), f, dim)
    cfg = TrainConfig(learning_rate=0.05)
    shards = [SparseReduceShard(n_hosts=2) for _ in range(2)]
    regs = [MetricsRegistry() for _ in range(2)]
    results = {}

    def run_host(hid):
        client = HierExchangeClient([s.address for s in shards],
                                    host_id=hid, n_hosts=2)
        try:
            tr = SparseTableCTRTrainer(
                params, fm_mod.logits, cfg,
                sparse_tables={"w": ["fids"], "v": ["fids"]},
                fused_fn=fm_mod.logits_with_l2,
                mesh=make_mesh(MeshSpec(data=2)), hier_exchange=client)
            tr.health = None
            tr.telemetry = regs[hid]
            t0 = time.perf_counter()
            losses = [float(tr.train_step(halves[hid]))
                      for _ in range(steps + 1)]
            results[hid] = (losses, time.perf_counter() - t0, tr,
                            client.bytes_sent + client.bytes_received)
        finally:
            client.close()

    threads = [threading.Thread(target=run_host, args=(h,)) for h in (0, 1)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        for s in shards:
            s.close()
    assert set(results) == {0, 1}
    oracle = SparseTableCTRTrainer(
        params, fm_mod.logits, cfg,
        sparse_tables={"w": ["fids"], "v": ["fids"]},
        fused_fn=fm_mod.logits_with_l2)
    oracle.health = None
    o_losses = [float(oracle.train_step(full)) for _ in range(steps + 1)]
    losses, wall, tr, sock = results[0]
    assert tr.exchange_policy == {"w": "hier", "v": "hier"}
    snap = regs[0].snapshot()
    return {
        "model": f"fm vocab={f} dim={dim}, 2 hosts x 2 local replicas",
        "exchange_policy": dict(tr.exchange_policy),
        "hier_local_policy": dict(tr.hier_local_policy),
        "wire_bytes_per_step_model": dict(tr.exchange_bytes_per_step),
        "registry_counters": {
            k: v for k, v in snap["counters"].items() if "hier" in k
        },
        "socket_bytes_per_step_host0": int(sock // (steps + 1)),
        "max_loss_diff_vs_oracle": float(
            np.max(np.abs(np.asarray(losses) - np.asarray(o_losses)))),
    }


def run(steps: int = 4, out: str = "SPARSE_RING_BENCH.json",
        vocab_sweep=(1 << 14, 1 << 16, 1 << 18, 1 << 20)):
    set_enabled(True)  # byte numbers come from the live registry
    rng = np.random.default_rng(0)
    mesh = make_mesh(MeshSpec(data=N_DEV))
    tables = {"w": ["fids"], "embed": ["rep_fids"]}
    sweep = []
    for vocab in vocab_sweep:
        batch = synth_batch(rng, vocab)
        params = widedeep.init(jax.random.PRNGKey(0), vocab, N_FIELDS, DIM)
        cfg = TrainConfig(learning_rate=0.05)

        # per-member padded id counts (the jit-static sparse payload size)
        k_w = batch["fids"].size // N_DEV
        k_e = batch["rep_fids"].size // N_DEV
        touched = {"w": int(np.unique(batch["fids"]).size),
                   "embed": int(np.unique(batch["rep_fids"]).size)}
        # counterfactual baseline: what the dense ring WOULD ship
        dense_b = {"w": dense_ring_bytes(vocab, 1, N_DEV),
                   "embed": dense_ring_bytes(vocab, DIM, N_DEV)}
        dense_b["total"] = dense_b["w"] + dense_b["embed"]

        sparse_tr = SparseTableCTRTrainer(
            params, widedeep.logits, cfg, sparse_tables=tables, mesh=mesh)
        # isolated registry: this sweep cell's live counters only
        sparse_tr.telemetry = MetricsRegistry()
        dense_tr = CTRTrainer(params, widedeep.logits, cfg, mesh=mesh)
        ex_s_sparse, l_sparse = timed_steps(sparse_tr, batch, steps)
        ex_s_dense, l_dense = timed_steps(dense_tr, batch, steps)

        # live byte accounting from the trainer's telemetry, NOT re-derived:
        # per-table rates from the trace-time record, totals cross-checked
        # against the registry counters the instrumented steps incremented
        live_b = dict(sparse_tr.exchange_bytes_per_step)
        live_b["total"] = sum(live_b.values())
        snap = sparse_tr.telemetry.snapshot()
        n_steps = snap["counters"]["trainer_steps_total"]
        counted = (snap["counters"].get(
                       "trainer_sparse_exchange_bytes_total", 0)
                   + snap["counters"].get(
                       "trainer_sparse_rs_bytes_total", 0)
                   + snap["counters"].get(
                       "trainer_dense_ring_bytes_total", 0))
        assert counted == live_b["total"] * n_steps, (counted, live_b, n_steps)

        sweep.append({
            "vocab": vocab,
            "global_batch": BATCH,
            "touched_rows": touched,
            "density": round(touched["w"] / vocab, 6),
            "padded_ids_per_member": {"w": k_w, "embed": k_e},
            "bytes_per_step_per_member": {
                "live_exchange": live_b,
                "dense_ring_counterfactual": dense_b,
                "sparse_exchange_int8": {
                    "total": sparse_exchange_bytes(N_DEV, k_w, 1, 8)
                    + sparse_exchange_bytes(N_DEV, k_e, DIM, 8)},
            },
            "registry_counters": {
                k: v for k, v in snap["counters"].items()
                if "bytes" in k or k == "trainer_steps_total"
            },
            "reduction_x": round(dense_b["total"] / live_b["total"], 2),
            "exchange_policy": dict(sparse_tr.exchange_policy),
            "examples_per_sec": {
                "sparse_exchange": round(ex_s_sparse, 1),
                "dense_psum": round(ex_s_dense, 1),
            },
            "max_loss_diff_vs_dense_psum": float(
                np.max(np.abs(np.asarray(l_sparse) - np.asarray(l_dense)))),
        })
        print(f"vocab=2^{vocab.bit_length() - 1}: "
              f"live {live_b['total']:,} B/step vs dense "
              f"{dense_b['total']:,} B/step ({sweep[-1]['reduction_x']}x), "
              f"{ex_s_sparse:,.0f} vs {ex_s_dense:,.0f} ex/s, "
              f"policy={sweep[-1]['exchange_policy']}", file=sys.stderr,
              flush=True)

    # v2: the reduce-scatter variant across (density x world_size), plus
    # one live rs-picked trainer cell
    grid, crossover = rs_grid(rng)
    trainer_rs = rs_trainer_cell(rng, steps=steps)

    # v3 (ISSUE 10): the hierarchical two-level exchange — the
    # (local-replicas x world) wire-bytes grid through a real in-process
    # reduce rendezvous, one live 2-host threaded trainer cell, and the
    # bandwidth-aware cost model's picks at representative link ratios
    hgrid = hier_grid(rng)
    codec_cell = hier_codec_grid(rng)
    stream_cells, stripe_cell = hier_stream_grid(rng)
    trainer_hier = hier_trainer_cell(rng, steps=steps)
    from lightctr_tpu.dist import LinkBandwidth

    hier_cost = []
    for ici_bps, dcn_bps in ((4e9, 2.5e8), (4e9, 4e9), (4e9, 4e10)):
        bw = LinkBandwidth(ici_bps, dcn_bps, "synthetic")
        algo, b = pick_exchange_algo(
            16, 2048, 4096, 16, local_n=8, bw=bw)
        hier_cost.append({
            "ici_bps": ici_bps, "dcn_bps": dcn_bps,
            "regime": "vocab=4096 k=2048 dim=16, 2 hosts x 8 replicas",
            "pick": algo, "bytes": b,
        })
        # the streaming terms (ISSUE 16): striped shards multiply the
        # effective DCN rate, overlap hides the push leg under the local
        # merge — same regime, re-priced
        algo_s, b_s = pick_exchange_algo(
            16, 2048, 4096, 16, local_n=8, bw=bw, stripes=2,
            overlap_push=True)
        hier_cost.append({
            "ici_bps": ici_bps, "dcn_bps": dcn_bps,
            "regime": "vocab=4096 k=2048 dim=16, 2 hosts x 8 replicas, "
                      "2 stripes + overlapped push",
            "pick": algo_s, "bytes": b_s,
        })
    # acceptance: rs bytes roughly FLAT in world size at fixed density
    # (the allgather's grow ~(n-1)), and the pick takes rs past the
    # modeled crossover
    for density in {c["density"] for c in grid}:
        ds = sorted((c for c in grid if c["density"] == density),
                    key=lambda c: c["world_size"])
        rs_growth = (ds[-1]["bytes_per_step_per_member"]["sparse_rs"]
                     / ds[0]["bytes_per_step_per_member"]["sparse_rs"])
        ag_growth = (ds[-1]["bytes_per_step_per_member"]["sparse_allgather"]
                     / ds[0]["bytes_per_step_per_member"]["sparse_allgather"])
        # rs never grows faster than the allgather; in the regime where it
        # WINS (overlap saturates the per-owner union) it is roughly flat
        assert rs_growth <= ag_growth, (density, rs_growth, ag_growth)
        if any(c["pick"] == "sparse_rs" for c in ds):
            assert rs_growth < 3.0 < ag_growth, (
                density, rs_growth, ag_growth,
            )
    assert any(c["pick"] == "sparse_rs" for c in grid), (
        "the grid must cover the rs-winning regime"
    )

    # live kernel-dispatch cell (ISSUE 9): which implementation of the
    # registered kernels the trainer cells above ACTUALLY ran, read from
    # the same trainer_kernel_path_total{phase,impl} counters a production
    # scrape sees (the dispatch counts to the process default registry at
    # trace time) — off-TPU this records their XLA form honestly.
    from lightctr_tpu import obs as obs_mod
    from lightctr_tpu.ops import sparse_kernels
    from tools.metrics_report import summarize_kernels

    kernel_cell = summarize_kernels(obs_mod.default_registry().snapshot())
    kernel_cell["resolved"] = {
        name: sparse_kernels.resolve_impl(name)
        for name in sorted(sparse_kernels.KERNELS)
    }
    kernel_cell["note"] = (
        "dispatch counts from the live trainer cells above (once per "
        "traced program per kernel); 'resolved' is the pick of each of "
        "the registered kernels on THIS platform — pallas only on a real "
        "TPU, so a CPU run records the XLA form instead of faking a fused win"
    )

    criteo_like = sweep[-1]
    report = {
        "metric": "sparse_exchange_bytes_reduction_at_criteo_density",
        "value": criteo_like["reduction_x"],
        "unit": "x fewer bytes/step/member vs dense ring",
        "platform": jax.devices()[0].platform,
        "topology": f"{N_DEV}-member data-parallel mesh "
                    "(xla_force_host_platform_device_count)",
        "model": f"widedeep vocab-sweep, dim={DIM}, batch={BATCH}, "
                 f"{N_FIELDS} fields",
        "note": "live bytes come from the trainer's obs-registry telemetry "
                "(trainer_*_bytes_total counters / exchange_bytes_per_step); "
                "sparse bytes are constant in vocab (they scale with the "
                "batch's touched rows); dense bytes are linear in vocab. "
                "examples/s on the CPU host mesh understates the win: XLA's "
                "CPU backend does not honor donation, so both trainers pay "
                "an O(vocab) table copy per step (sparse_trainer.py "
                "platform note).",
        "sweep": sweep,
        "rs_grid": {
            "note": "v2 reduce-scatter variant (owner-partitioned, "
                    "ppermute ring + merged-shard all_gather) vs the "
                    "allgather exchange across density x world_size; "
                    "bytes derive from the static payload shapes each "
                    "collective ships (same helpers as the trainer's "
                    "live counters); per cell the three-way trace-time "
                    "pick (pick_exchange_algo) is asserted equal to the "
                    "measured byte winner; rs bytes stay roughly flat "
                    "in world size at fixed density while allgather "
                    "bytes grow ~(n-1)x.",
            "cells": grid,
            "crossover": crossover,
        },
        "rs_trainer_cell": trainer_rs,
        "hier_grid": {
            "note": "hierarchical two-level exchange (ISSUE 10): fixed "
                    "per-host batch split across R local replicas, merged "
                    "in-jit over the local mesh, ONE merged payload per "
                    "host through the socket reduce rendezvous (2 shards, "
                    "owner-partitioned uid % n).  Measured wire bytes "
                    "(client socket counters) stay flat (+-10% asserted) "
                    "as R doubles; the per-replica-push counterfactual — "
                    "today's PS wire, every replica shipping its own rows "
                    "— grows ~linearly in R.",
            "cells": hgrid,
            "codec": {
                "note": "compressed DCN wire (ISSUE 13): the identical "
                        "merged payloads under the fp32 per-table wire "
                        "(PR 10) vs the q8_ef quantile-coded EF wire "
                        "with grouped shared-id frames — measured socket "
                        "bytes, >=4x asserted; the shared-id-stream "
                        "saving (grouping alone) reported separately, "
                        "and both EF carries' residual mass shown as "
                        "sub-bucket noise per value.",
                "cell": codec_cell,
            },
            "streaming": {
                "note": "streaming rendezvous (ISSUE 16): barrier vs "
                        "streaming A/B under a paced wire standing in "
                        "for a constrained DCN (LIGHTCTR_LINK_BW "
                        "regime).  The streaming arm dispatches chunked "
                        "pushes, overlaps the compute leg under the "
                        "in-flight transmissions and commits before the "
                        "pull; >=1.5x step speedup asserted.  The shard "
                        "peak-round-bytes column shows the streaming "
                        "accumulator flat (+-10% asserted) as n_hosts "
                        "doubles while the barrier buffer grows; the "
                        "stripe subcell shows the commit wall shrinking "
                        "with the shard count (aggregate paced "
                        "bandwidth scales with stripes).",
                "cells": stream_cells,
                "stripe_scaling": stripe_cell,
            },
        },
        "hier_trainer_cell": trainer_hier,
        "hier_cost_model": {
            "note": "pick_exchange_algo's two-fabric form at synthetic "
                    "link speeds (LIGHTCTR_LINK_BW overrides in "
                    "production; a startup probe measures otherwise): a "
                    "slow DCN aggregates before the slow link (hier), a "
                    "DCN an order faster than the ICI hands the pick "
                    "back to the flat single-fabric collective.",
            "cells": hier_cost,
        },
        "kernel_dispatch": kernel_cell,
    }
    print(json.dumps({k: v for k, v in report.items() if k != "sweep"},
                     indent=1))
    assert criteo_like["reduction_x"] >= 10.0, (
        "sparse exchange must beat the dense ring >=10x at Criteo-like "
        f"density, got {criteo_like['reduction_x']}x"
    )
    assert criteo_like["max_loss_diff_vs_dense_psum"] < 1e-4, criteo_like
    if out:
        with open(out, "w") as f:
            json.dump(report, f, indent=1)
    return report


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--out", default="SPARSE_RING_BENCH.json")
    args = ap.parse_args()
    run(steps=args.steps, out=args.out)


if __name__ == "__main__":
    main()
