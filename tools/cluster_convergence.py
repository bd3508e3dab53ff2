"""Composed full-cluster convergence: PS service + N streaming workers +
heartbeat kill/readmit — in ONE launcher.

The reference's deployment story is master + PS + workers as separate
communicating processes (``/root/reference/build.sh:24-26``, master control
plane ``distribut/master.h:146-262``, 4-node benchmark
``benchmark/4_node_ps.png``).  The repo proved every piece separately
(network PS service, heartbeat unroute/readmit, per-process disk shards,
SSP convergence); this tool proves the TOPOLOGY:

  1. spawns the PS as its own process — slot-contiguous store behind the
     socket service, with a HeartbeatMonitor wired to routing
     (dead -> unroute, returning beat -> readmit);
  2. spawns N worker processes; each streams ITS OWN strided shard from the
     libffm file on disk (``iter_libffm_batches(process_index=w)``), trains
     Wide&Deep via wire-coded pull/push, and heartbeats over a second PS
     connection (liveness rides the network, master.h:202);
  3. SIGKILLs one worker mid-run, observes the monitor declare it dead and
     the PS refuse its route (rejected counters), relaunches it, observes
     readmission, and lets the cluster converge;
  4. evaluates the PS-trained model against a single-process run of the
     same schedule and emits ``CLUSTER_CONVERGENCE.json``.

Run:  python -m tools.cluster_convergence [--workers 4] [--epochs 30]
Without ``--data`` and without the reference mounted, a learnable synthetic
libffm file is generated (``lightctr_tpu.data.synth``).

Host-side tool: the launcher pins the CPU platform
(``utils.devicecheck.pin_cpu_platform``) before it starts a worker, the
workers inherit the pin, and nothing here touches an accelerator — a chip
belongs to one process at a time, so a launcher that held it would starve
its own children.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import signal
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.ps_convergence import (  # noqa: E402
    DENSE_BASE,
    _dense_chunks,
    _dense_template,
    _flatten_dense,
    _pull_rows_retry,
    _push_rows,
    _unflatten_dense,
)

# demo-speed liveness (the reference's production constants are 5s/10s/20s,
# master.h:202; ratios preserved)
BEAT_PERIOD_S = 0.25
STALE_AFTER_S = 1.0
DEAD_AFTER_S = 2.0


def resolve_data(data_arg, workdir):
    """--data > $LIGHTCTR_DATA > reference file if mounted > synthetic.
    The synthetic fallback pins the demo's original shape (2000 rows x 10
    fields over a 4096 vocab) so artifacts stay comparable across rounds."""
    from lightctr_tpu.data import synth

    if data_arg:
        return data_arg
    env = os.environ.get("LIGHTCTR_DATA")
    if env:
        return env
    if os.path.exists(synth.REFERENCE_SPARSE):
        return synth.REFERENCE_SPARSE
    return synth.write_synthetic_libffm(
        os.path.join(workdir, "synthetic_train.libffm"),
        n_rows=2000, n_fields=10, vocab=4096,
    )


# ---------------------------------------------------------------------------
# PS process


def _shard_proc(conn, shard_index, dim, n_workers, updater, lr, staleness,
                seed, port=0):
    """One PS shard process (the reference's paramserver binary): serves
    keys and OBEYS routing — the master decides (network.h:148-151).
    Beats to the master (id ``SHARD_ID_BASE + shard_index``) once the
    launcher sends the master address over the pipe; a relaunched shard
    binds its predecessor's ``port`` so worker clients reconnect to the
    address they already hold.

    Shutdown rides the per-process PIPE (any message or launcher-side
    close), NOT a shared mp.Event: this role gets SIGKILLed mid-run by the
    failure drill, and a kill landing inside Event.wait()'s lock window
    would poison the shared semaphore for every later set()."""
    from lightctr_tpu.dist.master import SHARD_ID_BASE
    from lightctr_tpu.dist.ps_server import ParamServerService
    from lightctr_tpu.embed.async_ps import AsyncParamServer

    ps = AsyncParamServer(
        dim=dim, updater=updater, learning_rate=lr, n_workers=n_workers,
        staleness_threshold=staleness, seed=seed,
    )
    svc = ParamServerService(ps, port=port)
    conn.send(svc.address)
    try:
        msg = conn.recv()  # master address, once the master is up
    except EOFError:
        msg = "stop"
    if msg == "stop":  # startup aborted before the master came up
        svc.close()
        return
    stop_beat = threading.Event()
    beat_t = threading.Thread(
        target=_beat_loop,
        args=(tuple(msg), SHARD_ID_BASE + shard_index, stop_beat),
        daemon=True,
    )
    beat_t.start()
    try:
        conn.recv()  # blocks until the launcher says stop (or dies: EOF)
    except EOFError:
        pass
    stop_beat.set()
    svc.close()


def _master_proc(conn, shard_addresses):
    """The master role (master.h:146-262): owns the heartbeat monitor,
    broadcasts unroute/readmit decisions to every shard.  Pipe-based stop,
    same rationale as _shard_proc."""
    from lightctr_tpu.dist.master import MasterService

    m = MasterService(
        [tuple(a) for a in shard_addresses],
        stale_after_s=STALE_AFTER_S, dead_after_s=DEAD_AFTER_S,
        period_s=BEAT_PERIOD_S,
    )
    conn.send(m.address)
    try:
        conn.recv()
    except EOFError:
        pass
    m.close()


# ---------------------------------------------------------------------------
# worker process


def _beat_loop(address, worker_id, stop):
    """Heartbeat thread: its OWN connection (PSClient is not thread-safe),
    so a long pull can never starve liveness.  Reconnects on failure — a
    single transient beat error must not silence liveness forever (for a
    shard that would read as a death and trigger a destructive
    relaunch+restore of a healthy store)."""
    from lightctr_tpu.dist.ps_server import PSClient

    client = None
    while not stop.wait(BEAT_PERIOD_S):
        try:
            if client is None:
                client = PSClient(address, 1)
            client.beat(worker_id)
        except (ConnectionError, OSError, RuntimeError):
            if client is not None:
                try:
                    client.close()
                except OSError:
                    pass
                client = None
    if client is not None:
        try:
            client.close()
        except OSError:
            pass


def _cluster_worker(worker_id, n_workers, shard_addresses, master_address,
                    data_path, meta, cfg, out_dir, start_epoch, throttle_s):
    from lightctr_tpu.utils.devicecheck import pin_cpu_platform

    pin_cpu_platform(1)

    import jax
    import jax.numpy as jnp

    from lightctr_tpu.data.streaming import iter_libffm_batches
    from lightctr_tpu.dist.ps_server import make_client
    from lightctr_tpu.models import widedeep
    from lightctr_tpu.ops import losses as losses_lib

    D = cfg["factor_dim"]
    row_dim = 1 + D
    B = cfg["batch_size"]
    template = {k: tuple(v) for k, v in cfg["dense_template"]}
    dense_len = sum(int(np.prod(s)) for s in template.values())
    feature_cnt = meta["feature_cnt"]
    field_cnt = meta["field_cnt"]
    max_nnz = meta["max_nnz"]

    ps = make_client(shard_addresses, row_dim,
                     partition=cfg.get("partition", "modulo"))
    stop_beat = threading.Event()
    beat_t = threading.Thread(
        target=_beat_loop, args=(master_address, worker_id, stop_beat),
        daemon=True,
    )
    beat_t.start()

    U_w = B * max_nnz
    U_e = B * field_cnt

    @jax.jit
    def grads_fn(wide_rows, embed_rows, fc1, fc2, batch):
        def loss(wr, er, f1, f2):
            params = {"w": wr, "embed": er, "fc1": f1, "fc2": f2}
            z = widedeep.logits(params, batch)
            return losses_lib.logistic_loss(
                z, batch["labels"], reduction="mean"
            )

        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(
            wide_rows, embed_rows, fc1, fc2
        )

    n_dense = (dense_len + row_dim - 1) // row_dim
    dense_keys = DENSE_BASE + np.arange(n_dense, dtype=np.int64)
    curve = []
    for epoch in range(start_epoch, cfg["epochs"]):
        ep_losses = []
        # re-stream THIS worker's strided shard from disk each epoch
        for mb in iter_libffm_batches(
            data_path, B, max_nnz, feature_cnt=feature_cnt,
            field_cnt=field_cnt, process_index=worker_id,
            process_count=n_workers,
        ):
            rep, rep_mask = widedeep.field_representatives(
                mb["fids"], mb["fields"], mb["mask"], field_cnt
            )
            uw = np.unique(mb["fids"].reshape(-1))
            ue = np.unique(rep.reshape(-1))
            uw_pad = np.pad(uw, (0, U_w - len(uw)), mode="edge")
            ue_pad = np.pad(ue, (0, U_e - len(ue)), mode="edge")

            sparse_keys = np.union1d(uw, ue)
            all_keys = np.concatenate([sparse_keys, dense_keys])
            rows = _pull_rows_retry(ps, all_keys, epoch, worker_id,
                                    max_wait_s=60.0)

            iw = np.searchsorted(sparse_keys, uw_pad)
            ie = np.searchsorted(sparse_keys, ue_pad)
            dvec = rows[len(sparse_keys):].reshape(-1)[:dense_len]
            mlp = _unflatten_dense(dvec, template)

            batch = {
                "fids": np.searchsorted(uw, mb["fids"]).astype(np.int32),
                "rep_fids": np.searchsorted(ue, rep).astype(np.int32),
                "vals": mb["vals"],
                "mask": mb["mask"],
                "rep_mask": rep_mask,
                "labels": mb["labels"],
            }
            loss, (g_w, g_e, g_fc1, g_fc2) = grads_fn(
                jnp.asarray(rows[iw, 0]), jnp.asarray(rows[ie, 1:]),
                jax.tree_util.tree_map(jnp.asarray, mlp["fc1"]),
                jax.tree_util.tree_map(jnp.asarray, mlp["fc2"]),
                {k: jnp.asarray(v) for k, v in batch.items()},
            )
            ep_losses.append(float(loss))

            g_w, g_e = np.asarray(g_w), np.asarray(g_e)
            G = np.zeros((len(all_keys), row_dim), np.float32)
            G[iw[: len(uw)], 0] = g_w[: len(uw)]
            G[ie[: len(ue)], 1:] = g_e[: len(ue)]
            g_dense = _flatten_dense({"fc1": g_fc1, "fc2": g_fc2})
            pad = n_dense * row_dim - dense_len
            G[len(sparse_keys):] = np.pad(g_dense, (0, pad)).reshape(
                n_dense, row_dim
            )
            _push_rows(ps, worker_id, all_keys, G, epoch)
            if throttle_s:
                time.sleep(throttle_s)
        curve.append(float(np.mean(ep_losses)) if ep_losses else None)

    suffix = "" if start_epoch == 0 else f"_from{start_epoch}"
    with open(os.path.join(out_dir, f"worker_{worker_id}{suffix}.json"),
              "w") as f:
        json.dump({
            "worker": worker_id,
            "start_epoch": start_epoch,
            "loss_curve": curve,
            "withheld_pulls": ps.withheld_pulls,
            "dropped_pushes": ps.dropped_pushes,
        }, f)
    stop_beat.set()
    beat_t.join(timeout=2.0)
    from lightctr_tpu.dist.ps_server import PSClient

    fin = PSClient(tuple(master_address), 1)
    fin.farewell(worker_id)  # FIN to the MASTER: deliberate exit != death
    fin.close()
    ps.close()


# ---------------------------------------------------------------------------
# launcher


def run(data_path=None, n_workers=4, epochs=30, batch_size=50, factor_dim=8,
        lr=0.1, updater="adagrad", staleness=10, seed=0, workdir=None,
        kill_worker=1, throttle=None, ps_shards=1, kill_shard=None,
        partition="modulo", snapshot_period_s=0.5,
        out="CLUSTER_CONVERGENCE.json"):
    """throttle: optional {worker_id: seconds-per-batch} skew injection.
    ps_shards: number of PS shard processes; partition: key->shard policy
    ("modulo" | consistent-hash "ring").  kill_shard: SIGKILL that PS
    shard mid-run — master detects via shard heartbeats, the launcher
    relaunches it on the same port and restores the backup agent's latest
    snapshot (the reference's PS has NO disk backup, paramserver.h:309;
    this composes the failover path that exceeds it), worker clients
    reconnect and the cluster converges."""
    import tempfile

    import jax

    from lightctr_tpu import TrainConfig
    from lightctr_tpu.data import load_libffm
    from lightctr_tpu.models import widedeep
    from lightctr_tpu.models.ctr_trainer import CTRTrainer
    from lightctr_tpu.ops import metrics as metrics_lib
    from lightctr_tpu.ops.activations import sigmoid

    workdir = workdir or tempfile.mkdtemp(prefix="cluster_")
    data_path = resolve_data(data_path, workdir)

    # one metadata pass (feature/field counts, eval payload); workers
    # stream the same file from disk themselves
    ds = load_libffm(data_path)
    feature_cnt, field_cnt = ds.feature_cnt, ds.field_cnt
    max_nnz = ds.max_nnz
    rep, rep_mask = widedeep.field_representatives(
        ds.fids, ds.fields, ds.mask, field_cnt
    )
    payload = {k: np.asarray(v)
               for k, v in widedeep.make_batch(ds, rep, rep_mask).items()}
    meta = {"feature_cnt": feature_cnt, "field_cnt": field_cnt,
            "max_nnz": max_nnz}

    D = factor_dim
    row_dim = 1 + D
    params0 = widedeep.init(jax.random.PRNGKey(seed), feature_cnt,
                            field_cnt, D)
    template = _dense_template(params0)
    dense_vec = _flatten_dense(params0)
    n_chunks = (len(dense_vec) + row_dim - 1) // row_dim

    cfg = {
        "factor_dim": D, "batch_size": batch_size, "epochs": epochs,
        "lr": lr, "updater": updater, "staleness": staleness, "seed": seed,
        "partition": partition,
        "dense_template": [(k, list(v)) for k, v in template.items()],
    }

    ctx = mp.get_context("spawn")
    events = []

    def mark(kind, **kw):
        ev = {"t": round(time.time() - t0, 2), "event": kind, **kw}
        events.append(ev)
        print(f"[cluster] {ev}", file=sys.stderr, flush=True)

    # -- 1. the three-role control/data plane: N PS shard processes, then
    # one MASTER process owning the heartbeat monitor (master.h topology).
    # Role shutdown is per-process pipes (see _shard_proc docstring).
    t0 = time.time()
    role_procs, addresses = [], []
    shard_procs, shard_pipes = {}, {}
    master_pipe = None

    def spawn_shard(s, port=0):
        parent_conn, child_conn = ctx.Pipe()
        p = ctx.Process(
            target=_shard_proc,
            args=(child_conn, s, row_dim, n_workers, updater, lr,
                  staleness, seed + s, port),
        )
        p.start()
        if not parent_conn.poll(60):
            raise RuntimeError("PS shard failed to start within 60s")
        addr = list(parent_conn.recv())
        shard_procs[s] = p
        shard_pipes[s] = parent_conn
        return addr

    def stop_roles():
        for conn in [master_pipe, *shard_pipes.values()]:
            if conn is None:
                continue
            try:
                conn.send("stop")
            except (OSError, BrokenPipeError):
                pass  # already dead (e.g. the drill's victim)

    try:
        for s in range(ps_shards):
            addresses.append(spawn_shard(s))
        parent_conn, child_conn = ctx.Pipe()
        master_proc = ctx.Process(
            target=_master_proc, args=(child_conn, addresses)
        )
        master_proc.start()
        role_procs.append(master_proc)
        if not parent_conn.poll(60):
            raise RuntimeError("master failed to start within 60s")
        master_address = list(parent_conn.recv())
        master_pipe = parent_conn
        # shards learn the master address and start beating to it
        for s in range(ps_shards):
            shard_pipes[s].send(master_address)
    except Exception:
        stop_roles()
        for p in [*role_procs, *shard_procs.values()]:
            p.join(timeout=5)
            if p.is_alive():
                p.terminate()
        raise
    mark("ps_up", shards=addresses)
    mark("master_up", address=master_address)

    admin = None
    procs = {}

    from lightctr_tpu.dist.ps_server import make_client

    throttle = throttle or {}

    def spawn_worker(w, start_epoch=0):
        p = ctx.Process(
            target=_cluster_worker,
            args=(w, n_workers, addresses, master_address, data_path, meta,
                  cfg, workdir, start_epoch, float(throttle.get(w, 0.0))),
        )
        p.start()
        return p

    def wait_until(cond, what, watch=(), timeout_s=120.0, sleep_s=0.1):
        """Poll ``cond``; fail loudly on timeout or if a watched child dies
        (a crashed worker/PS must not hang the launcher forever)."""
        deadline = time.time() + timeout_s
        while not cond():
            for p in watch:
                if not p.is_alive() and p.exitcode not in (0, None):
                    raise RuntimeError(
                        f"{what}: child pid {p.pid} died "
                        f"(exitcode {p.exitcode})"
                    )
            if time.time() > deadline:
                raise TimeoutError(f"timed out waiting for {what}")
            time.sleep(sleep_s)

    def agg_stats():
        """Aggregate shard stats (single shard -> dict; sharded -> list).
        A down shard's slot is an explicit {"down": True, ...} record —
        aggregate over the survivors."""
        s = admin.stats()
        if isinstance(s, dict):
            return s
        live = [x for x in s if x is not None and not x.get("down")]
        if not live:
            raise ConnectionError("no PS shard reachable")
        return {
            "last_epoch_version": max(x["last_epoch_version"] for x in live),
            "staleness": max(x["staleness"] for x in live),
            "unrouted": sorted({w for x in live for w in x["unrouted"]}),
            "withheld_pulls": sum(x["withheld_pulls"] for x in live),
            "dropped_pushes": sum(x["dropped_pushes"] for x in live),
            "rejected_pulls": sum(x["rejected_pulls"] for x in live),
            "rejected_pushes": sum(x["rejected_pushes"] for x in live),
            "n_keys": sum(x["n_keys"] for x in live),
            "down_shards": [i for i, x in enumerate(s)
                            if x is None or x.get("down")],
            "per_shard": s,
        }

    _liveness_client = {"c": None}

    def master_liveness():
        """The master's view of every beating node (STATS liveness map).
        One persistent admin connection, reconnected on failure — the
        drill's 10Hz polls must not churn a connection per call."""
        from lightctr_tpu.dist.ps_server import PSClient

        try:
            if _liveness_client["c"] is None:
                _liveness_client["c"] = PSClient(tuple(master_address), 1)
            return _liveness_client["c"].stats().get("liveness", {})
        except (ConnectionError, OSError, RuntimeError):
            if _liveness_client["c"] is not None:
                try:
                    _liveness_client["c"].close()
                except OSError:
                    pass
                _liveness_client["c"] = None
            return {}  # poll loops retry

    def shard_status(s):
        from lightctr_tpu.dist.master import SHARD_ID_BASE

        return master_liveness().get(str(SHARD_ID_BASE + s))

    report_fail = None
    backup_stop = threading.Event()
    backup_thread = None
    backups = {}  # shard -> {"keys", "rows", "t"} latest good snapshot
    try:
        admin = make_client(addresses, row_dim, partition=partition)
        # master syncInitializer: deterministic start for every worker
        w0 = np.asarray(params0["w"])
        e0 = np.asarray(params0["embed"])
        rows0 = np.concatenate([w0[:, None], e0], axis=1).astype(np.float32)
        admin.preload_arrays(np.arange(feature_cnt, dtype=np.int64), rows0)
        chunks = _dense_chunks(dense_vec, row_dim)
        ck = np.array(sorted(chunks), np.int64)
        admin.preload_arrays(ck, np.stack([chunks[int(k)] for k in ck]))

        if kill_shard is not None:
            # -- backup agent: the ops-plane loop that gives the PS the
            # disk-backup story the reference lacks (paramserver.h:309's
            # TODO): periodically SNAPSHOT every shard over the admin op;
            # the latest good copy seeds a relaunched shard's restore.
            backup_client = make_client(addresses, row_dim,
                                        partition=partition)

            def backup_loop():
                while not backup_stop.wait(snapshot_period_s):
                    for s in range(ps_shards):
                        try:
                            k, r = backup_client.snapshot_shard(s)
                            backups[s] = {"keys": k, "rows": r,
                                          "t": time.time()}
                        except (ConnectionError, OSError, RuntimeError):
                            pass  # shard down: keep the last good copy
                backup_client.close()

            backup_thread = threading.Thread(target=backup_loop, daemon=True)
            backup_thread.start()

        procs.update({w: spawn_worker(w) for w in range(n_workers)})
        mark("workers_up", n=n_workers)

        if kill_worker is not None:
            # -- 3. mid-run failure injection: SIGKILL, observe unroute
            # (rejected counters / unrouted set), relaunch, observe readmit
            target_epoch = max(2, epochs // 4)
            wait_until(
                lambda: agg_stats()["last_epoch_version"] >= target_epoch,
                f"epoch ledger to reach {target_epoch}",
                watch=[*role_procs, *shard_procs.values(), *procs.values()],
                sleep_s=0.2,
            )
            victim = procs[kill_worker]
            os.kill(victim.pid, signal.SIGKILL)
            victim.join()
            mark("worker_killed", worker=kill_worker)

            wait_until(
                lambda: kill_worker in agg_stats()["unrouted"],
                f"heartbeat to unroute worker {kill_worker}",
                watch=[*role_procs, *shard_procs.values()],
            )
            s = agg_stats()
            mark("unrouted_observed", worker=kill_worker,
                 stats={k: s[k] for k in
                        ("rejected_pulls", "rejected_pushes", "unrouted")})

            resume_epoch = min(s["last_epoch_version"] + 1, epochs - 1)
            procs[kill_worker] = spawn_worker(
                kill_worker, start_epoch=resume_epoch
            )
            mark("worker_relaunched", worker=kill_worker,
                 start_epoch=resume_epoch)

            wait_until(
                lambda: kill_worker not in agg_stats()["unrouted"],
                f"readmission of worker {kill_worker}",
                watch=[*role_procs, procs[kill_worker]],
            )
            mark("readmitted_observed", worker=kill_worker)

        if kill_shard is not None:
            # -- 3b. PS-SHARD failure drill: kill a shard, master detects
            # via shard heartbeats, relaunch on the same port, restore the
            # backup agent's latest snapshot, workers reconnect and resume.
            # (The reference master monitors every registered node incl.
            # PS, master.h:202-262; PS disk backup is its acknowledged gap,
            # paramserver.h:309 — this composes the path that closes it.)
            survivors = [p for s, p in shard_procs.items() if s != kill_shard]
            shard_kill_epoch = min(
                max(agg_stats()["last_epoch_version"] + 2, epochs // 2),
                epochs - 5,
            )
            wait_until(
                lambda: agg_stats()["last_epoch_version"]
                >= shard_kill_epoch,
                f"epoch ledger to reach {shard_kill_epoch} (shard drill)",
                watch=[*role_procs, *shard_procs.values(), *procs.values()],
                sleep_s=0.2,
            )
            wait_until(
                lambda: kill_shard in backups,
                "backup agent to capture the victim shard",
                watch=[*role_procs, *shard_procs.values()],
            )
            victim = shard_procs[kill_shard]
            os.kill(victim.pid, signal.SIGKILL)
            victim.join()
            mark("ps_killed", shard=kill_shard,
                 address=addresses[kill_shard])

            wait_until(
                lambda: shard_status(kill_shard) == "dead",
                f"master to declare shard {kill_shard} dead",
                watch=[*role_procs, *survivors],
            )
            mark("ps_dead_detected", shard=kill_shard,
                 liveness=master_liveness())

            # relaunch on the SAME port (worker clients reconnect to the
            # address they already hold), then restore the newest backup
            addr = spawn_shard(kill_shard, port=addresses[kill_shard][1])
            assert tuple(addr) == tuple(addresses[kill_shard])
            shard_pipes[kill_shard].send(master_address)
            snap = backups[kill_shard]
            for attempt in range(5):
                try:
                    admin.preload_arrays(snap["keys"], snap["rows"])
                    break
                except (ConnectionError, OSError):
                    # first attempt may ride the pre-kill broken socket;
                    # _ensure reconnects on the next one
                    if attempt == 4:
                        raise
                    time.sleep(0.2)
            mark("ps_restored", shard=kill_shard,
                 restored_keys=int(len(snap["keys"])),
                 backup_age_s=round(time.time() - snap["t"], 2))

            wait_until(
                lambda: shard_status(kill_shard) == "alive",
                f"master to see shard {kill_shard} return",
                watch=[*role_procs, *shard_procs.values(), *procs.values()],
            )
            mark("ps_recovered_observed", shard=kill_shard)

        for w, p in procs.items():
            p.join()
            if p.exitcode != 0:
                report_fail = f"worker {w} exited with {p.exitcode}"
                raise RuntimeError(report_fail)
        wall = time.time() - t0
        mark("workers_done")

        final_stats = agg_stats()

        # -- 4. PS-trained model vs single-process baseline
        _, w_fin = admin.pull_arrays(
            np.arange(feature_cnt, dtype=np.int64),
            worker_epoch=final_stats["last_epoch_version"],
        )
        _, d_fin = admin.pull_arrays(
            ck, worker_epoch=final_stats["last_epoch_version"]
        )
        dvec = d_fin.reshape(-1)[: len(dense_vec)]
        ps_params = {
            "w": w_fin[:, 0], "embed": w_fin[:, 1:],
            **_unflatten_dense(dvec, template),
        }

        import jax.numpy as jnp

        def eval_params(params):
            z = widedeep.logits(
                jax.tree_util.tree_map(jnp.asarray, params),
                {k: jnp.asarray(v) for k, v in payload.items()},
            )
            probs = sigmoid(z)
            labels = jnp.asarray(payload["labels"])
            return {
                "logloss": float(metrics_lib.logloss(probs, labels)),
                "accuracy": float(metrics_lib.accuracy(
                    (probs > 0.5).astype(jnp.int32), labels.astype(jnp.int32)
                )),
                "auc": float(metrics_lib.auc_histogram(
                    probs, labels.astype(jnp.int32)
                )),
            }

        # baseline optimizer matches the PS updater family: the sgd/dcasgd/
        # dcasgda runs compare against plain SGD (DCASGD IS compensated SGD,
        # paramserver.h:252-300); adagrad against the trainer default
        from lightctr_tpu import optim as optim_lib

        baseline_tx = (
            None if updater == "adagrad" else optim_lib.sgd(lr)
        )
        tr = CTRTrainer(params0, widedeep.logits,
                        TrainConfig(learning_rate=lr, seed=seed),
                        optimizer=baseline_tx)
        tr.fit(payload, epochs=epochs, batch_size=batch_size)

        worker_reports = []
        for fn in sorted(os.listdir(workdir)):
            if fn.startswith("worker_") and fn.endswith(".json"):
                with open(os.path.join(workdir, fn)) as f:
                    worker_reports.append(json.load(f))

        ev_ps = eval_params(ps_params)
        ev_single = eval_params(tr.params)
        report = {
            "config": {
                "n_workers": n_workers, "epochs": epochs,
                "batch_size": batch_size, "factor_dim": D, "lr": lr,
                "updater": updater, "staleness": staleness,
                "data": data_path, "rows": int(len(payload["labels"])),
                "feature_cnt": int(feature_cnt),
                "killed_worker": kill_worker,
                "killed_shard": kill_shard,
                "partition": partition,
                "snapshot_period_s": snapshot_period_s,
                "ps_shards": ps_shards,
                "throttle": {str(k): v for k, v in throttle.items()},
                "heartbeat": {"period_s": BEAT_PERIOD_S,
                              "stale_s": STALE_AFTER_S,
                              "dead_s": DEAD_AFTER_S},
            },
            "timeline": events,
            "wall_time_s": round(wall, 2),
            "ps_stats": final_stats,
            "workers": worker_reports,
            "final_ps": ev_ps,
            "final_single": ev_single,
            "parity": {k: round(abs(ev_ps[k] - ev_single[k]), 5)
                       for k in ev_ps},
        }
        if out:
            with open(out, "w") as f:
                json.dump(report, f, indent=1)
        return report
    finally:
        backup_stop.set()
        if backup_thread is not None:
            backup_thread.join(timeout=5)
        if _liveness_client["c"] is not None:
            try:
                _liveness_client["c"].close()
            except OSError:
                pass
        if admin is not None:
            admin.close()
        stop_roles()
        for p in [*role_procs, *shard_procs.values()]:
            p.join(timeout=10)
            if p.is_alive():
                p.terminate()
        for p in procs.values():
            if p.is_alive():
                p.terminate()


def main():
    from lightctr_tpu.utils.devicecheck import pin_cpu_platform

    pin_cpu_platform(1)

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--data", default=None)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=30)
    ap.add_argument("--batch-size", type=int, default=50)
    ap.add_argument("--factor-dim", type=int, default=8)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--updater", default="adagrad")
    ap.add_argument("--staleness", type=int, default=10)
    ap.add_argument("--kill-worker", type=int, default=1)
    ap.add_argument("--ps-shards", type=int, default=1)
    ap.add_argument("--kill-shard", type=int, default=None,
                    help="SIGKILL this PS shard mid-run; master detects, "
                    "launcher relaunches + restores latest snapshot")
    ap.add_argument("--partition", default="modulo",
                    choices=("modulo", "ring"),
                    help="key->shard routing policy (dist/partition.py)")
    ap.add_argument("--no-kill", action="store_true")
    ap.add_argument("--out", default="CLUSTER_CONVERGENCE.json")
    args = ap.parse_args()

    report = run(
        data_path=args.data, n_workers=args.workers, epochs=args.epochs,
        batch_size=args.batch_size, factor_dim=args.factor_dim, lr=args.lr,
        updater=args.updater, staleness=args.staleness,
        kill_worker=None if args.no_kill else args.kill_worker,
        ps_shards=args.ps_shards, kill_shard=args.kill_shard,
        partition=args.partition, out=args.out,
    )
    print(json.dumps({
        "timeline": report["timeline"],
        "final_ps": report["final_ps"],
        "final_single": report["final_single"],
        "parity": report["parity"],
        "wall_time_s": report["wall_time_s"],
    }))


if __name__ == "__main__":
    main()
