"""Chaos harness: kill/-STOP/partition PS shards and workers mid-run,
then PROVE the cluster acted (docs/ELASTICITY.md).

The reference survives node churn by design (``ConsistentHash`` rebalance +
heartbeat-driven membership, master.h:202-262) but has no harness that
demonstrates it; the repo's failover tests cover one transition each.
This tool composes the whole story under real process-level faults:

  1. spawns N PS-shard PROCESSES (each heartbeating to the master and
     writing crash-safe row snapshots on a checkpoint cadence), an
     elastic :class:`MasterService` in the harness process, and M
     training workers (threads, or processes for the worker-kill drill)
     driving a quadratic teaching task over the sharded PS — grad =
     (w - target) per embedding row, so convergence is measurable as MSE;
  2. mid-run, injects ONE fault: ``kill9`` (SIGKILL a shard), ``sigstop``
     (SIGSTOP, later SIGCONT — the wedged-then-healed case), ``partition``
     (the shard drops its socket but stays alive, later re-listens),
     ``kill_worker`` (SIGKILL a worker process, then a fresh worker
     joins), or ``join`` (a brand-new shard is admitted);
  3. asserts the act-on-failure contract: every key range is served by
     the surviving members (a full-vocab pull succeeds), migration
     checksums verify with zero row loss, the final MSE is within
     tolerance of an unperturbed run of the same schedule, and the
     flight recorder captured the episode (bundle readable via
     ``python -m tools.trace_report --flight``).

Run: ``python -m tools.chaos_harness [--scenario all] [--steps 30]``
Progress goes to stderr; stdout is the ``CHAOS_HARNESS.json`` artifact.

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
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from lightctr_tpu.ckpt import checkpoint as ckpt_mod  # noqa: E402
from lightctr_tpu.dist.elastic import shards_of_worker  # noqa: E402
from lightctr_tpu.dist.master import SHARD_ID_BASE, MasterService  # noqa: E402
from lightctr_tpu.dist.ps_server import PSClient, ShardedPSClient  # noqa: E402
from lightctr_tpu.obs import flight as obs_flight  # noqa: E402

# demo-speed liveness (production ratios 5s/10s/20s preserved, master.h:202)
BEAT_PERIOD_S = 0.1
STALE_AFTER_S = 0.4
DEAD_AFTER_S = 0.8
CKPT_PERIOD_S = 0.25

SCENARIOS = ("kill9", "sigstop", "partition", "kill_worker", "join")


def _log(msg: str) -> None:
    print(f"[chaos] {msg}", file=sys.stderr, flush=True)


def target_rows(vocab: int, dim: int, seed: int = 7) -> np.ndarray:
    """The teaching target every process derives identically."""
    return np.random.default_rng(seed).normal(
        size=(vocab, dim)).astype(np.float32)


# ---------------------------------------------------------------------------
# PS shard process


def _shard_main(conn, shard_id, dim, n_workers, staleness, seed, port,
                ckpt_dir, store_kind="flat", updater="sgd", hot_rows=0):
    """One PS shard process: serve + beat to the master + checkpoint rows
    AND optimizer accumulators on a cadence (the migration source if we
    die without a farewell).  ``store_kind="tiered"`` backs the shard with
    a :class:`TieredEmbeddingStore` (hot budget ``hot_rows``) so the drill
    proves zero row loss across ALL tiers; ``updater="adagrad"`` makes the
    accumulators meaningful, so the state-carrying migration is asserted
    on real optimizer state, not zeros.
    Control pipe: "partition" (drop the socket, stop beating, stay alive),
    "heal" (re-listen on the same port, resume beating), "stop"."""
    from lightctr_tpu.dist.ps_server import ParamServerService
    from lightctr_tpu.embed.async_ps import AsyncParamServer
    from lightctr_tpu.embed.tiered import TieredEmbeddingStore

    # sgd contracts (w - target) by (1 - lr) per pass — geometric
    # convergence; adagrad's decaying steps land within the same parity
    # tolerance over the drill's schedule (both runs share the updater)
    if store_kind == "tiered":
        tier_dir = os.path.join(ckpt_dir, f"tier_{shard_id}")
        os.makedirs(tier_dir, exist_ok=True)
        ps = TieredEmbeddingStore(
            dim=dim, hot_rows=max(1, int(hot_rows)),
            path=os.path.join(tier_dir, "store"), updater=updater,
            learning_rate=0.5, n_workers=n_workers,
            staleness_threshold=staleness, seed=seed,
        )
    else:
        ps = AsyncParamServer(dim=dim, updater=updater, learning_rate=0.5,
                              n_workers=n_workers,
                              staleness_threshold=staleness, seed=seed)
    svc = ParamServerService(ps, port=port)
    conn.send(svc.address)
    master_addr = conn.recv()
    port = svc.address[1]
    state = {"beating": True, "stop": False}

    def beat_loop():
        client = None
        while not state["stop"]:
            if state["beating"]:
                try:
                    if client is None:
                        client = PSClient(tuple(master_addr), 1, timeout=1.0)
                    client.beat(SHARD_ID_BASE + shard_id)
                except (ConnectionError, OSError, RuntimeError):
                    client = None
            time.sleep(BEAT_PERIOD_S)

    def ckpt_loop():
        step = 0
        d = os.path.join(ckpt_dir, f"shard_{shard_id}")
        while not state["stop"]:
            time.sleep(CKPT_PERIOD_S)
            step += 1
            try:
                # state-carrying snapshots: the rebalance migrates the
                # victim's Adagrad accumulators instead of resetting them
                k, r, a = ps.snapshot_state_arrays()
                ckpt_mod.save_arrays(d, step, k, r, accums=a)
                ckpt_mod.gc_array_snapshots(d, keep=3)
            except OSError:
                pass

    threading.Thread(target=beat_loop, daemon=True).start()
    threading.Thread(target=ckpt_loop, daemon=True).start()
    while True:
        try:
            msg = conn.recv()
        except EOFError:
            msg = "stop"
        if msg == "partition":
            # network partition: the process lives, its rows live, but
            # nothing reaches it — socket dropped, heartbeats stop
            state["beating"] = False
            svc.close()
            conn.send("partitioned")
        elif msg == "heal":
            svc = ParamServerService(ps, port=port)
            state["beating"] = True
            conn.send("healed")
        else:
            state["stop"] = True
            svc.close()
            return


# ---------------------------------------------------------------------------
# worker (thread-form and process-form share this loop)


def _worker_loop(wid, master_addr, addresses, dim, vocab, n_data_shards,
                 steps, progress, stop=None, seed=7):
    """Train rows toward the target over the sharded PS: pull my data
    shards' rows, push grad = (w - target).  Membership-epoch driven:
    every pass re-derives MY data shards from the routing table's
    (epoch, workers); pulls that fail (dead shard mid-rebalance) back
    off, refresh the route, and retry — the elastic contract is that
    they eventually succeed without restart."""
    tgt = target_rows(vocab, dim, seed)
    master = PSClient(tuple(master_addr), 1, timeout=2.0)
    client = ShardedPSClient(addresses, dim, partition="ring")
    client.attach_route_source(master.route)
    master.beat(wid)  # join the membership
    client.refresh_route()
    done = 0
    epoch = 0
    try:
        while done < steps and (stop is None or not stop.is_set()):
            master.beat(wid)
            table = client.routing
            if wid not in table.workers:
                client.refresh_route()
                time.sleep(BEAT_PERIOD_S / 2)
                continue
            mine = shards_of_worker(wid, table.workers, n_data_shards,
                                    table.epoch)
            for s in mine:
                keys = np.arange(vocab, dtype=np.int64)[s::n_data_shards]
                out = None
                for _ in range(200):  # bounded retry: outage is transient
                    if stop is not None and stop.is_set():
                        return done
                    out = client.pull_arrays(keys, worker_epoch=epoch,
                                             worker_id=wid)
                    if out is not None:
                        break
                    master.beat(wid)
                    time.sleep(0.05)
                if out is None:
                    continue  # shard still dark; next pass retries
                grad = out[1] - tgt[keys]
                client.push_arrays(wid, keys, grad, worker_epoch=epoch)
            epoch += 1
            done += 1
            progress[wid] = done
    finally:
        try:
            master.farewell(wid)
            master.close()
        except (ConnectionError, OSError, RuntimeError):
            pass
        client.close()
    return done


def _worker_main(wid, master_addr, addresses, dim, vocab, n_data_shards,
                 steps, progress):
    """Process entry for the worker-kill drill (progress: mp dict)."""
    _worker_loop(wid, master_addr, addresses, dim, vocab, n_data_shards,
                 steps, progress)


# ---------------------------------------------------------------------------
# scenario runner


class _Cluster:
    """Spawn/teardown of shards + master + workers for one scenario run."""

    def __init__(self, n_shards, n_workers, dim, vocab, staleness,
                 workdir, worker_procs=False, store_kind="flat",
                 updater="sgd", hot_rows=0):
        self.dim, self.vocab = dim, vocab
        self.n_workers = n_workers
        self.n_data_shards = 2 * n_workers
        self.staleness = staleness
        self.workdir = workdir
        self.store_kind = store_kind
        self.updater = updater
        self.hot_rows = hot_rows
        self.ckpt_dir = os.path.join(workdir, "ckpt")
        self.flight_dir = os.path.join(workdir, "flight")
        self.worker_procs = worker_procs
        self.ctx = mp.get_context("spawn")
        self.shards = []   # [(proc, pipe)]
        self.addresses = []
        # start every shard before waiting on any: spawn pays a full
        # interpreter + import per process, so serializing start/recv
        # would multiply the harness's startup by n_shards
        started = [self._start_shard(i) for i in range(n_shards)]
        for p, parent in started:
            self.addresses.append(parent.recv())
            self.shards.append((p, parent))
        obs_flight.install(self.flight_dir)
        self.master = MasterService(
            self.addresses, stale_after_s=STALE_AFTER_S,
            dead_after_s=DEAD_AFTER_S, period_s=BEAT_PERIOD_S / 2,
            shard_rpc_timeout_s=2.0, elastic=True, partition="ring",
            dim=dim, ckpt_dir=self.ckpt_dir, grace_factor=3.0,
        )
        for _, pipe in self.shards:
            pipe.send(list(self.master.address))
        self._mgr = self.ctx.Manager() if worker_procs else None
        self.progress = self._mgr.dict() if worker_procs else {}
        self.workers = []
        self.stop = threading.Event()

    def _start_shard(self, i, port=0):
        parent, child = self.ctx.Pipe()
        p = self.ctx.Process(
            target=_shard_main,
            args=(child, i, self.dim, self.n_workers, self.staleness,
                  100 + i, port, self.ckpt_dir, self.store_kind,
                  self.updater, self.hot_rows),
            daemon=True,
        )
        p.start()
        return p, parent

    def _spawn_shard(self, i, port=0):
        p, parent = self._start_shard(i, port)
        addr = parent.recv()
        if i < len(self.addresses):
            self.addresses[i] = addr
            self.shards[i] = (p, parent)
        else:
            self.addresses.append(addr)
            self.shards.append((p, parent))
        return addr

    def preload(self, rows):
        keys = np.arange(self.vocab, dtype=np.int64)
        c = ShardedPSClient(self.addresses, self.dim, partition="ring")
        c.preload_arrays(keys, rows)
        c.close()

    def start_workers(self, steps):
        for wid in range(self.n_workers):
            self._start_worker(wid, steps)

    def _start_worker(self, wid, steps):
        args = (wid, self.master.address, list(self.addresses), self.dim,
                self.vocab, self.n_data_shards, steps, self.progress)
        if self.worker_procs:
            w = self.ctx.Process(target=_worker_main, args=args, daemon=True)
        else:
            w = threading.Thread(target=_worker_loop,
                                 args=args + (self.stop,), daemon=True)
        w.start()
        self.workers.append((wid, w))
        return w

    def min_progress(self):
        vals = [self.progress.get(wid, 0) for wid, _ in self.workers]
        return min(vals) if vals else 0

    def wait_progress(self, at_least, timeout=30.0):
        deadline = time.monotonic() + timeout
        while self.min_progress() < at_least:
            if time.monotonic() > deadline:
                return False
            time.sleep(0.05)
        return True

    def join_workers(self, timeout):
        deadline = time.monotonic() + timeout
        for _, w in self.workers:
            w.join(max(0.1, deadline - time.monotonic()))
        return all(not w.is_alive() for _, w in self.workers)

    def eval_mse(self):
        """Full-vocab pull through a FRESH routed client: proves every
        key range is served by the surviving members, and measures how
        far the rows are from the teaching target."""
        keys = np.arange(self.vocab, dtype=np.int64)
        tgt = target_rows(self.vocab, self.dim)
        admin = PSClient(tuple(self.master.address), 1, timeout=2.0)
        c = ShardedPSClient(self.addresses, self.dim, partition="ring")
        c.attach_route_source(admin.route)
        c.refresh_route()
        out = None
        for _ in range(100):
            out = c.pull_arrays(keys, worker_epoch=0)
            if out is not None:
                break
            c.refresh_route()
            time.sleep(0.05)
        admin.close()
        c.close()
        if out is None:
            return None  # some range unserved: the assertion that fails
        return float(np.mean((out[1] - tgt) ** 2))

    def teardown(self):
        self.stop.set()
        for _, w in self.workers:
            if isinstance(w, threading.Thread):
                w.join(timeout=5.0)
            elif w.is_alive():
                w.terminate()
                w.join(timeout=5.0)
        self.master.close()
        for p, pipe in self.shards:
            if p.is_alive():
                try:
                    pipe.send("stop")
                except (BrokenPipeError, OSError):
                    pass
                p.join(timeout=3.0)
            if p.is_alive():
                p.kill()
                p.join(timeout=3.0)
        obs_flight.uninstall()


def _await_ckpt(ckpt_dir, shard, timeout=15.0):
    """Block until the shard has a non-empty row snapshot on disk: the
    zero-row-loss guarantee is relative to the checkpoint cadence, so the
    drill only fires once the mechanism it asserts is actually armed."""
    d = os.path.join(ckpt_dir, f"shard_{int(shard)}")
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out = ckpt_mod.load_latest_arrays(d)
        if out is not None and len(out[1]):
            return True
        time.sleep(0.05)
    return False


def _await_members(master, want, timeout=40.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if sorted(master.routing.members) == sorted(want) \
                and not master.routing.rebalancing:
            return True
        time.sleep(0.05)
    return False


def run_scenario(
    scenario: str,
    steps: int = 30,
    n_shards: int = 3,
    n_workers: int = 2,
    dim: int = 8,
    vocab: int = 1536,
    staleness: int = 50,
    workdir=None,
    keep_cluster=None,
    store: str = "flat",
    updater: str = "sgd",
    hot_rows: int = 0,
) -> dict:
    """Run one fault drill end to end; returns the assertion-ready report.
    ``keep_cluster``: optional list that receives the live _Cluster (tests
    poke at it mid-run via threads).  ``scenario == "none"`` is the
    unperturbed baseline.  ``store="tiered"`` backs every shard with a
    :class:`TieredEmbeddingStore` (hot budget ``hot_rows``, default
    vocab // 6 — small enough that the victim's rows really live across
    tiers); ``updater="adagrad"`` arms the accumulator-survival
    assertions."""
    workdir = workdir or tempfile.mkdtemp(prefix=f"chaos_{scenario}_")
    victim = n_shards - 1  # ring arcs exist for every shard; any works
    worker_procs = scenario == "kill_worker"
    if store == "tiered" and hot_rows <= 0:
        hot_rows = max(16, vocab // 6)
    cl = _Cluster(n_shards, n_workers, dim, vocab, staleness, workdir,
                  worker_procs=worker_procs, store_kind=store,
                  updater=updater, hot_rows=hot_rows)
    if keep_cluster is not None:
        keep_cluster.append(cl)
    report = {"scenario": scenario, "steps": steps, "n_shards": n_shards,
              "n_workers": n_workers, "vocab": vocab, "dim": dim,
              "store": store, "updater": updater}
    if store == "tiered":
        report["hot_rows"] = hot_rows
    try:
        cl.preload(target_rows(vocab, dim) * 0.0)  # start at zero rows
        t0 = time.monotonic()
        cl.start_workers(steps)
        if not cl.wait_progress(max(2, steps // 5), timeout=60.0):
            raise RuntimeError("workers never reached the fault point")

        members_after = list(range(n_shards))
        if scenario in ("kill9", "sigstop", "partition"):
            proc, pipe = cl.shards[victim]
            if not _await_ckpt(cl.ckpt_dir, victim):
                raise RuntimeError("victim shard never checkpointed")
            _log(f"{scenario}: injecting fault on shard {victim} "
                 f"(pid {proc.pid})")
            if scenario == "kill9":
                os.kill(proc.pid, signal.SIGKILL)
                members_after = [m for m in members_after if m != victim]
            elif scenario == "sigstop":
                os.kill(proc.pid, signal.SIGSTOP)
            else:
                pipe.send("partition")
                pipe.recv()
            # the detect->act loop: master declares the shard dead and
            # migrates its checkpointed rows to the ring successors
            drop = [m for m in range(n_shards) if m != victim]
            if not _await_members(cl.master, drop):
                raise RuntimeError("master never rebalanced the dead shard")
            report["dropped_epoch"] = cl.master.routing.epoch
            if scenario == "sigstop":
                os.kill(proc.pid, signal.SIGCONT)
            elif scenario == "partition":
                pipe.send("heal")
                pipe.recv()
            if scenario in ("sigstop", "partition"):
                # healed shard beats again -> recover -> join migration
                if not _await_members(cl.master, members_after):
                    raise RuntimeError("healed shard never rejoined")
        elif scenario == "kill_worker":
            wid, w = cl.workers[-1]
            _log(f"kill_worker: SIGKILL worker {wid} (pid {w.pid})")
            os.kill(w.pid, signal.SIGKILL)
            w.join(timeout=5.0)
            # a FRESH worker joins under a new id and picks up the epoch's
            # shard map (the dead worker's data shards re-deal to it and
            # the survivors once the master declares the death)
            new_wid = cl.n_workers
            cl.n_workers += 1
            cl._start_worker(new_wid, steps)
            deadline = time.monotonic() + 20.0
            while wid in cl.master.routing.workers:
                if time.monotonic() > deadline:
                    raise RuntimeError("dead worker never left the epoch")
                time.sleep(0.05)
            report["workers_after"] = list(cl.master.routing.workers)
        elif scenario == "join":
            addr = cl._spawn_shard(n_shards)
            cl.shards[-1][1].send(list(cl.master.address))
            sid = cl.master.admit_shard(addr)
            members_after = list(range(n_shards)) + [sid]
            if not _await_members(cl.master, members_after):
                raise RuntimeError("admitted shard never became a member")
        elif scenario != "none":
            raise ValueError(f"unknown scenario {scenario!r}")

        ok = cl.join_workers(timeout=120.0)
        report["wall_s"] = round(time.monotonic() - t0, 3)
        report["workers_finished"] = bool(ok)
        report["final_members"] = list(cl.master.routing.members)
        report["final_epoch"] = cl.master.routing.epoch
        report["migrations"] = [
            {k: v for k, v in m.items() if k != "src_fnv"}
            for m in cl.master.migrations
        ]
        report["migrations_verified"] = all(
            m.get("verified") for m in cl.master.migrations
        )
        report["migrated_rows"] = int(sum(
            m.get("n", 0) for m in cl.master.migrations))
        if scenario == "kill9":
            # zero row loss: everything the dead shard's last checkpoint
            # held was landed (count + checksum verified per range) — for
            # a tiered victim the snapshot walks ALL THREE tiers, so this
            # asserts nothing fell between hot, warm, and cold
            src = ckpt_mod.load_latest_state(
                os.path.join(cl.ckpt_dir, f"shard_{victim}"))
            report["dead_shard_ckpt_rows"] = 0 if src is None else len(src[1])
            drop_recs = [
                m for m in cl.master.migrations
                if m.get("reason") == "shard_death" and m.get("verified")]
            drop_rows = sum(m.get("n", 0) for m in drop_recs)
            report["zero_row_loss"] = (
                src is not None and drop_rows == len(src[1]))
            # accumulator survival (PR 6 follow-up): every death range rode
            # MSG_MIGRATE_STATE (read-back checksum over rows AND accums),
            # and the checkpointed accumulators were real training state
            report["accums_migrated"] = bool(drop_recs) and all(
                m.get("accums") for m in drop_recs)
            report["dead_shard_ckpt_accums_nonzero"] = bool(
                src is not None and src[3] is not None
                and float(np.abs(src[3]).sum()) > 0.0)
        mse = cl.eval_mse()
        report["all_ranges_served"] = mse is not None
        report["mse"] = mse
        # flight recorder: the rebalance episode dumps a bundle at act
        # time; prove it is readable through the postmortem tool
        bundles = sorted(
            os.path.join(cl.flight_dir, f)
            for f in os.listdir(cl.flight_dir)
            if f.startswith("flight-") and f.endswith(".jsonl")
        ) if os.path.isdir(cl.flight_dir) else []
        report["flight_bundles"] = bundles
        if bundles and scenario != "none":
            # prove the episode is readable through the postmortem tool...
            from tools.trace_report import summarize_flight

            summary = summarize_flight(bundles[-1])
            report["flight_reason"] = summary.get("reason")
            report["flight_event_kinds"] = (
                summary.get("event_ring", {}).get("by_kind", {}))
            # ...and that the failover story is actually IN the bundle
            from lightctr_tpu.obs import read_jsonl

            report["flight_actions"] = sorted({
                r["record"].get("action")
                for r in read_jsonl(bundles[-1])
                if r.get("kind") == "flight_event"
                and r.get("record", {}).get("kind") == "failover"
            } - {None})
        return report
    finally:
        cl.teardown()


def parity(report: dict, baseline: dict, tol: float = 5e-3) -> dict:
    """Convergence parity vs the unperturbed run: both runs' final MSE
    under tolerance AND their gap small — churn cost bounded, not just
    'it eventually trains'."""
    m, b = report.get("mse"), baseline.get("mse")
    out = {
        "mse": m, "baseline_mse": b, "tol": tol,
        "parity": (m is not None and b is not None
                   and m < tol and abs(m - b) < tol),
    }
    return out


def main(argv=None):
    from lightctr_tpu.utils.devicecheck import pin_cpu_platform

    pin_cpu_platform(1)

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenario", default="all",
                    help=f"one of {SCENARIOS + ('all', 'none')}")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--shards", type=int, default=3)
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--vocab", type=int, default=1536)
    ap.add_argument("--dim", type=int, default=8)
    ap.add_argument("--out", default="CHAOS_HARNESS.json",
                    help="also write the artifact here ('-' = stdout only)")
    ap.add_argument("--store", default="flat", choices=("flat", "tiered"),
                    help="shard store backing every scenario run")
    ap.add_argument("--updater", default="sgd", choices=("sgd", "adagrad"))
    ap.add_argument("--skip-tiered-cell", action="store_true",
                    help="skip the extra tiered-victim adagrad kill9 cell "
                         "appended to the 'all' matrix")
    args = ap.parse_args(argv)

    names = SCENARIOS if args.scenario == "all" else (args.scenario,)
    kw = dict(steps=args.steps, n_shards=args.shards, n_workers=args.workers,
              vocab=args.vocab, dim=args.dim, store=args.store,
              updater=args.updater)
    _log("running unperturbed baseline")
    baseline = run_scenario("none", **kw)
    results = {"baseline": baseline, "scenarios": {}}
    failed = False

    def run_cell(cell_name, scenario_name, cell_kw, cell_baseline,
                 extra_ok=()):
        nonlocal failed
        _log(f"running scenario {cell_name}")
        rep = run_scenario(scenario_name, **cell_kw)
        rep["parity"] = parity(rep, cell_baseline)
        ok = (rep.get("workers_finished") and rep.get("all_ranges_served")
              and rep.get("migrations_verified")
              and rep["parity"]["parity"]
              and all(rep.get(k) for k in extra_ok))
        rep["ok"] = bool(ok)
        failed = failed or not ok
        results["scenarios"][cell_name] = rep
        _log(f"{cell_name}: ok={ok} mse={rep.get('mse')} "
             f"epoch={rep.get('final_epoch')} "
             f"migrated={rep.get('migrated_rows')}")

    for name in names:
        run_cell(name, name, kw, baseline)
    if args.scenario == "all" and args.store == "flat" \
            and not args.skip_tiered_cell:
        # the tiered-victim cell (docs/TIERED_STORE.md): a tiered adagrad
        # shard is SIGKILLed — zero row loss across all three tiers vs its
        # last checkpoint, and the accumulators ride the migration
        tkw = dict(kw, store="tiered", updater="adagrad")
        _log("running tiered-store baseline")
        tbase = run_scenario("none", **tkw)
        results["baseline_tiered"] = tbase
        run_cell("kill9_tiered", "kill9", tkw, tbase,
                 extra_ok=("zero_row_loss", "accums_migrated",
                           "dead_shard_ckpt_accums_nonzero"))
    results["ok"] = not failed
    if args.out and args.out != "-":
        with open(args.out, "w") as f:
            f.write(json.dumps(results, indent=2, sort_keys=True) + "\n")
    print(json.dumps(results, indent=2, sort_keys=True))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
