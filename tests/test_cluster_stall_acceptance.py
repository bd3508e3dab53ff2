"""ISSUE 14 acceptance: the cluster step-observability plane over REAL
processes (the tests/test_hier_exchange.py shape).

Two spawned hosts train through a spawned reduce rendezvous:

  1. one host sleeps mid-round — the shard's per-round arrival timeline
     and the host-labeled ``hier_round_wait_seconds`` histogram name it,
     and ``/stragglerz`` (the rollup + straggler attributor over the
     shard's scraped stats) ranks it first;
  2. SIGSTOP of the rendezvous shard trips the step stall watchdog on
     EVERY host: a ``stall:process:exchange`` flight bundle lands at
     stall time (readable via ``trace_report --flight``) and both hosts'
     ``/healthz`` go 503;
  3. SIGCONT recovers both hosts to 200 within one completed step.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap
import time
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from lightctr_tpu.dist.ps_server import PSClient
from lightctr_tpu.obs import exporter as exporter_mod
from lightctr_tpu.obs import labeled
from lightctr_tpu.obs.cluster import ClusterRollup, attribute_stragglers

REPO_ROOT = str(Path(__file__).resolve().parents[1])

_SHARD = textwrap.dedent(
    """
    import os, sys, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    from lightctr_tpu.dist.hier import SparseReduceShard

    port_file = sys.argv[1]
    shard = SparseReduceShard(n_hosts=2)
    with open(port_file + ".tmp", "w") as f:
        f.write(str(shard.address[1]))
    os.replace(port_file + ".tmp", port_file)
    while True:
        time.sleep(3600)
    """
)

_WORKER = textwrap.dedent(
    """
    import itertools, os, sys, time
    host_id, port, run_dir = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["LIGHTCTR_TELEMETRY"] = "1"
    os.environ["LIGHTCTR_STALL"] = "1"
    os.environ["LIGHTCTR_STALL_MIN_S"] = "1.0"
    os.environ["LIGHTCTR_STALL_FACTOR"] = "4"
    os.environ["LIGHTCTR_OPS_PORT"] = "0"
    os.environ["LIGHTCTR_FLIGHT"] = os.path.join(
        run_dir, "flight_%d" % host_id)
    from lightctr_tpu.utils.devicecheck import pin_cpu_platform
    pin_cpu_platform(2)
    import numpy as np
    import jax
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.core.mesh import MeshSpec, make_mesh
    from lightctr_tpu.dist.hier import HierExchangeClient
    from lightctr_tpu.models import fm
    from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer
    from lightctr_tpu.obs import exporter

    ops_port = exporter.installed().address[1]
    pf = os.path.join(run_dir, "ops_port_%d" % host_id)
    with open(pf + ".tmp", "w") as f:
        f.write(str(ops_port))
    os.replace(pf + ".tmp", pf)

    rng = np.random.default_rng(host_id)
    fids = rng.integers(1, 256, size=(64, 4)).astype(np.int32)
    batch = {
        "fids": fids, "fields": np.zeros_like(fids),
        "vals": np.ones((64, 4), np.float32),
        "mask": np.ones((64, 4), np.float32),
        "labels": (np.arange(64) % 2).astype(np.float32),
    }
    params = fm.init(jax.random.PRNGKey(0), 256, 4)
    client = HierExchangeClient(
        [("127.0.0.1", port)], host_id=host_id, n_hosts=2,
        pull_timeout_s=300.0)
    tr = SparseTableCTRTrainer(
        params, fm.logits, TrainConfig(learning_rate=0.1),
        sparse_tables={"w": ["fids"], "v": ["fids"]},
        fused_fn=fm.logits_with_l2,
        mesh=make_mesh(MeshSpec(data=2)), hier_exchange=client)
    assert tr.stepwatch is not None  # LIGHTCTR_STALL armed it
    # the test's SIGSTOP phase must re-dump inside the default 60s
    # flight rate limit (an idle-wait trip may already have dumped)
    tr.stepwatch.flight_min_interval_s = 1.0

    go = os.path.join(run_dir, "go")
    marker = os.path.join(run_dir, "phase_a_%d" % host_id)
    for step in itertools.count():
        if host_id == 1 and step in (8, 9):
            time.sleep(0.4)  # the mid-round sleeper
        tr.train_step(batch)
        if step == 11:
            open(marker, "w").close()
            while not os.path.exists(go):
                time.sleep(0.05)
    """
)


def _wait_file(path, timeout_s, what):
    deadline = time.monotonic() + timeout_s
    while not os.path.exists(path):
        if time.monotonic() > deadline:
            raise TimeoutError(f"{what} never appeared at {path}")
        time.sleep(0.05)
    return path


def _healthz_code(port):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=2) as resp:
            return resp.status
    except urllib.error.HTTPError as e:
        return e.code


def _wait_healthz(ports, want, timeout_s, what):
    deadline = time.monotonic() + timeout_s
    last = {}
    while time.monotonic() < deadline:
        last = {p: _healthz_code(p) for p in ports}
        if all(c == want for c in last.values()):
            return
        time.sleep(0.2)
    raise AssertionError(f"{what}: wanted {want} on all of {last}")


def _stall_bundles(flight_dir):
    """reasons of every stall bundle in a worker's flight dir."""
    out = []
    for p in sorted(Path(flight_dir).glob("flight-*.jsonl")):
        try:
            head = p.read_text().splitlines()[0]
            reason = json.loads(head).get("reason", "")
        except (OSError, ValueError, IndexError):
            continue
        if reason.startswith("stall:"):
            out.append((str(p), reason))
    return out


def test_two_host_straggler_named_and_stall_watchdog_round_trip(tmp_path):
    run_dir = tmp_path
    shard_script = run_dir / "shard.py"
    shard_script.write_text(_SHARD)
    worker_script = run_dir / "worker.py"
    worker_script.write_text(_WORKER)

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("LIGHTCTR_TRACE", None)
    env["PYTHONPATH"] = REPO_ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")

    procs = []
    srv = None
    try:
        shard_proc = subprocess.Popen(
            [sys.executable, str(shard_script),
             str(run_dir / "shard_port")],
            stdout=subprocess.DEVNULL, stderr=subprocess.PIPE, text=True,
            env=env, cwd=REPO_ROOT)
        procs.append(shard_proc)
        _wait_file(str(run_dir / "shard_port"), 60, "shard port")
        port = int((run_dir / "shard_port").read_text())

        for hid in (0, 1):
            procs.append(subprocess.Popen(
                [sys.executable, str(worker_script), str(hid), str(port),
                 str(run_dir)],
                stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
                text=True, env=env, cwd=REPO_ROOT))

        for hid in (0, 1):
            _wait_file(str(run_dir / f"phase_a_{hid}"), 240,
                       f"worker {hid} phase A marker")
        ops_ports = [
            int((run_dir / f"ops_port_{hid}").read_text())
            for hid in (0, 1)
        ]

        # -- 1. the sleeper is NAMED by the shard's arrival timeline ------
        scraper = PSClient(("127.0.0.1", port), dim=1, timeout=10.0)
        st = scraper.stats()
        scraper.close()
        hists = st["telemetry"]["histograms"]
        h0 = hists[labeled("hier_round_wait_seconds", host="0")]
        h1 = hists[labeled("hier_round_wait_seconds", host="1")]
        # host 1 slept 0.4s before its push on two rounds' worth of
        # tables; its cumulative wait must dwarf host 0's
        assert h1["sum"] > h0["sum"] + 0.5, (h0["sum"], h1["sum"])
        slept = [r for r in st["arrivals"]
                 if r["arrivals"].get("1", 0.0) >= 0.25]
        assert slept, st["arrivals"]
        assert {r["epoch"] for r in slept} <= {8, 9}
        assert all(r["wait_s"] == r["arrivals"]["1"] for r in slept)

        # ...and /stragglerz (rollup + attributor over the scraped stats,
        # served over a real ops endpoint) ranks it first
        rollup = ClusterRollup()
        rollup.update("rendezvous_0", st)
        exporter_mod.register_json_route(
            "/stragglerz",
            lambda: attribute_stragglers(rollup.members()))
        srv = exporter_mod.OpsServer(port=0)
        with urllib.request.urlopen(
                f"http://{srv.address[0]}:{srv.address[1]}/stragglerz",
                timeout=5) as resp:
            verdict = json.loads(resp.read())
        assert verdict["verdict"]["slowest_host"] == "1"
        assert verdict["hosts"][0]["host"] == "1"

        # -- 2. SIGSTOP the rendezvous: every host's watchdog trips -------
        (run_dir / "go").write_text("")
        # both workers step again -> healthy (recovers any idle-wait trip)
        _wait_healthz(ops_ports, 200, 60, "post-go recovery")
        os.kill(shard_proc.pid, signal.SIGSTOP)
        try:
            _wait_healthz(ops_ports, 503, 90,
                          "stall escalation under SIGSTOP")
            # the at-stall-time bundle names the wedged phase by name:
            # the step is stuck in the EXCHANGE, and the bundle landed
            # while it still was
            deadline = time.monotonic() + 30
            needed = {0: False, 1: False}
            while not all(needed.values()) and time.monotonic() < deadline:
                for hid in (0, 1):
                    needed[hid] = any(
                        r == "stall:process:exchange" for _, r in
                        _stall_bundles(run_dir / f"flight_{hid}"))
                time.sleep(0.2)
            assert all(needed.values()), {
                hid: _stall_bundles(run_dir / f"flight_{hid}")
                for hid in (0, 1)}
            # the bundle reads back through the standard postmortem tool
            from tools.trace_report import summarize_flight
            bundle = [p for p, r in _stall_bundles(run_dir / "flight_0")
                      if r == "stall:process:exchange"][0]
            report = summarize_flight(bundle)
            assert report["reason"] == "stall:process:exchange"
            stall_detail = report["health"]["process"]["detectors"]["stall"]
            assert stall_detail["status"] in ("degraded", "unhealthy")
            assert stall_detail["detail"]["phase"] == "exchange"
        finally:
            os.kill(shard_proc.pid, signal.SIGCONT)

        # -- 3. clean recovery on SIGCONT ---------------------------------
        _wait_healthz(ops_ports, 200, 90, "recovery after SIGCONT")
    finally:
        if srv is not None:
            exporter_mod.unregister_json_route("/stragglerz")
            srv.close()
        stderrs = []
        for p in procs:
            if p.poll() is None:
                # workers loop forever by design; SIGCONT any stopped
                # shard first so the kill lands
                try:
                    os.kill(p.pid, signal.SIGCONT)
                except OSError:
                    pass
                p.kill()
            try:
                _, err = p.communicate(timeout=30)
                stderrs.append(err[-2000:] if err else "")
            except subprocess.TimeoutExpired:
                stderrs.append("<no stderr: communicate timed out>")
    # no worker may have CRASHED before the kill (a crash would have
    # broken the rendezvous and shown up as a timeout above — this is
    # the readable breadcrumb when it does)
    for p, err in zip(procs, stderrs):
        assert p.returncode is not None, err
