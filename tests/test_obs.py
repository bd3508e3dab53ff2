"""Unified telemetry layer: registry, event log, wire-level aggregation,
trainer instrumentation, overhead guard, and the no-bare-print lint."""

import ast
import json
import os
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from lightctr_tpu import obs

LIB_ROOT = Path(__file__).resolve().parents[1] / "lightctr_tpu"


# -- registry ---------------------------------------------------------------


def test_counters_gauges_histograms_roundtrip():
    r = obs.MetricsRegistry()
    r.inc("a_total")
    r.inc("a_total", 5)
    r.gauge_set("depth", 3)
    r.observe("lat_seconds", 0.003)
    r.observe("lat_seconds", 0.3)
    s = r.snapshot()
    assert s["counters"]["a_total"] == 6
    assert s["gauges"]["depth"] == 3
    h = s["histograms"]["lat_seconds"]
    assert h["count"] == 2 and abs(h["sum"] - 0.303) < 1e-9
    assert sum(h["counts"]) == 2
    # snapshots are wire-ready: plain JSON types end to end
    json.dumps(s)


def test_snapshot_reset_is_atomic_with_read():
    r = obs.MetricsRegistry()
    r.inc("c", 7)
    r.observe("h", 0.1)
    first = r.snapshot(reset=True)
    assert first["counters"]["c"] == 7
    second = r.snapshot()
    assert "c" not in second["counters"]
    assert "h" not in second["histograms"]


def test_registry_thread_safe_increments():
    r = obs.MetricsRegistry()

    def hammer():
        for _ in range(1000):
            r.inc("n_total")
            r.observe("h", 0.001)

    threads = [threading.Thread(target=hammer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    s = r.snapshot()
    assert s["counters"]["n_total"] == 8000
    assert s["histograms"]["h"]["count"] == 8000


def test_merge_snapshots_sums_everything():
    a, b = obs.MetricsRegistry(), obs.MetricsRegistry()
    a.inc("c", 2)
    b.inc("c", 3)
    b.inc("only_b")
    a.observe("h", 0.01)
    b.observe("h", 10.0)
    merged = obs.merge_snapshots([a.snapshot(), b.snapshot(), {}])
    assert merged["counters"]["c"] == 5
    assert merged["counters"]["only_b"] == 1
    assert merged["histograms"]["h"]["count"] == 2


def test_merge_rejects_mismatched_buckets():
    a, b = obs.MetricsRegistry(), obs.MetricsRegistry()
    a.observe("h", 1.0, buckets=(1.0, 2.0))
    b.observe("h", 1.0, buckets=(5.0,))
    with pytest.raises(ValueError):
        obs.merge_snapshots([a.snapshot(), b.snapshot()])


def test_histogram_quantile_interpolates():
    r = obs.MetricsRegistry()
    for v in np.linspace(0.0, 1.0, 101):
        r.observe("h", float(v), buckets=(0.25, 0.5, 0.75, 1.0))
    h = r.snapshot()["histograms"]["h"]
    assert abs(obs.histogram_quantile(h, 0.5) - 0.5) < 0.05
    assert obs.histogram_quantile(h, 0.0) <= obs.histogram_quantile(h, 1.0)
    empty = {"le": [1.0], "counts": [0, 0], "sum": 0.0, "count": 0}
    assert obs.histogram_quantile(empty, 0.99) == 0.0


def test_render_prometheus_format():
    r = obs.MetricsRegistry()
    r.inc("reqs_total", 4)
    r.inc(obs.labeled("ops_total", op="pull"), 2)
    r.gauge_set("depth", 1)
    r.observe(obs.labeled("lat_seconds", op="pull"), 0.2, buckets=(0.1, 1.0))
    text = obs.render_prometheus(r.snapshot(), prefix="lightctr_")
    assert "# TYPE lightctr_reqs_total counter" in text
    assert "lightctr_reqs_total 4" in text
    assert 'lightctr_ops_total{op="pull"} 2' in text
    assert "# TYPE lightctr_depth gauge" in text
    # histogram renders the cumulative bucket/sum/count triple with the
    # baked-in labels merged alongside le
    assert 'lightctr_lat_seconds_bucket{op="pull",le="+Inf"} 1' in text
    assert 'lightctr_lat_seconds_count{op="pull"} 1' in text


# -- event log --------------------------------------------------------------


def test_event_log_ring_is_bounded():
    log = obs.EventLog(capacity=10)
    for i in range(25):
        log.emit("step", step=i)
    recs = log.records()
    assert len(recs) == 10
    assert recs[0]["step"] == 15 and recs[-1]["step"] == 24  # oldest dropped
    assert log.dropped == 15 and log.emitted == 25
    assert all(r["v"] == obs.SCHEMA_VERSION for r in recs)


def test_event_log_flushes_jsonl(tmp_path):
    path = str(tmp_path / "run.jsonl")
    log = obs.EventLog(path=path, capacity=100, flush_every=4)
    for i in range(10):
        log.emit("step", step=i, loss=0.1 * i)
    # flush_every=4 -> two automatic flushes so far; close drains the rest
    log.close()
    recs = obs.read_jsonl(path)
    assert [r["step"] for r in recs] == list(range(10))
    assert all(r["kind"] == "step" and "ts" in r for r in recs)
    assert log.dropped == 0


def test_event_log_flush_failure_never_raises(tmp_path):
    """Telemetry I/O failure must not kill the emitting (training) thread:
    the flush swallows the OSError, counts it, and keeps ring semantics."""
    gone = tmp_path / "subdir"
    gone.mkdir()
    path = str(gone / "run.jsonl")
    log = obs.EventLog(path=path, capacity=8, flush_every=4)
    gone.rmdir()  # directory vanishes before the first flush
    for i in range(30):
        log.emit("step", step=i)  # would raise without containment
    assert log.flush_errors >= 1
    assert len(log.records()) <= 8  # fell back to the bounded ring
    assert log.dropped > 0


def test_ensure_console_logging_attaches_once():
    import logging

    root = logging.getLogger()
    lib_log = logging.getLogger("lightctr_tpu")
    old_root = list(root.handlers)
    old_handlers, old_level = list(lib_log.handlers), lib_log.level
    root.handlers.clear()  # simulate a fresh interpreter (pytest adds some)
    lib_log.handlers.clear()
    try:
        obs.ensure_console_logging()
        obs.ensure_console_logging()  # idempotent
        assert len(lib_log.handlers) == 1
        assert lib_log.isEnabledFor(logging.INFO)
        # an application's own config wins: with root handlers present the
        # helper must not attach anything
        lib_log.handlers.clear()
        root.addHandler(logging.NullHandler())
        obs.ensure_console_logging()
        assert lib_log.handlers == []
    finally:
        root.handlers[:] = old_root
        lib_log.handlers[:] = old_handlers
        lib_log.setLevel(old_level)


def test_default_event_log_respects_gate(tmp_path):
    obs.configure_event_log()
    try:
        with obs.override(False):
            obs.emit_event("step", step=1)
        assert obs.get_event_log().records() == []
        obs.emit_event("step", step=2)
        assert len(obs.get_event_log().records()) == 1
    finally:
        obs.configure_event_log()


# -- PS wire-level stats ----------------------------------------------------


def test_stats_wire_op_carries_registry_snapshot(rng):
    from lightctr_tpu.dist.ps_server import ParamServerService, PSClient
    from lightctr_tpu.embed.async_ps import AsyncParamServer

    ps = AsyncParamServer(dim=4, n_workers=1, seed=0)
    svc = ParamServerService(ps)
    client = PSClient(svc.address, 4)
    try:
        keys = np.arange(32, dtype=np.int64)
        client.pull_arrays(keys, worker_epoch=0, worker_id=0)
        client.push_arrays(0, keys, np.ones((32, 4), np.float32),
                           worker_epoch=0)
        st = client.stats()
        telem = st["telemetry"]
        c = telem["counters"]
        assert c[obs.labeled("ps_requests_total", op="pull")] == 1
        assert c[obs.labeled("ps_requests_total", op="push")] == 1
        assert c["ps_store_pulled_keys_total"] == 32
        assert c["ps_bytes_received_total"] > 0
        assert c["ps_bytes_sent_total"] > 0
        h = telem["histograms"][obs.labeled("ps_op_seconds", op="pull")]
        assert h["count"] == 1
        # the snapshot renders straight to Prometheus text
        assert "ps_requests_total" in obs.render_prometheus(telem)
    finally:
        client.close()
        svc.close()


def test_store_stats_expose_pending_and_drift():
    from lightctr_tpu.embed.async_ps import AsyncParamServer

    ps = AsyncParamServer(dim=2, n_workers=1, seed=0)
    st = ps.stats()
    assert st["pending_depth"] == 0 and st["key_cache_drift"] == 0
    assert st["key_cache_builds"] == 0 and st["key_cache_merges"] == 0
    # first big pull allocates via the dict path (empty store); the second
    # takes the vectorized path and builds the sorted snapshot; later small
    # allocations queue against it
    ps.pull_batch(np.arange(5000, dtype=np.int64), worker_epoch=0)
    ps.pull_batch(np.arange(5000, dtype=np.int64), worker_epoch=0)
    assert ps.stats()["key_cache_builds"] == 1
    ps.pull_batch(np.arange(5000, 5100, dtype=np.int64), worker_epoch=0)
    st = ps.stats()
    assert st["pending_depth"] >= 1
    assert st["key_cache_drift"] == 100


def test_async_ps_pending_stays_bounded_under_merge_rule():
    """PR 1's merge rule: _pending folds into the snapshot once drift
    passes max(4096, cache/8) — so the queue depth (and drift) stay bounded
    no matter how many small allocations arrive post-snapshot."""
    from lightctr_tpu.embed.async_ps import AsyncParamServer

    ps = AsyncParamServer(dim=1, n_workers=1, seed=0)
    ps.pull_batch(np.arange(8192, dtype=np.int64), worker_epoch=0)  # alloc
    ps.pull_batch(np.arange(8192, dtype=np.int64), worker_epoch=0)  # build
    max_depth = 0
    key = 8192
    for _ in range(300):
        ks = np.arange(key, key + 64, dtype=np.int64)
        key += 64
        ps.pull_batch(ks, worker_epoch=0)
        st = ps.stats()
        bound = max(4096, (st["n_keys"] - st["key_cache_drift"]) // 8)
        assert st["key_cache_drift"] <= bound + 64, st
        max_depth = max(max_depth, st["pending_depth"])
    st = ps.stats()
    assert st["key_cache_merges"] >= 1  # the rule actually fired
    # 300 allocations of 64 keys would queue 300 deep without the rule
    assert max_depth <= (bound // 64) + 2


def test_two_process_cluster_aggregates_over_stats_op(tmp_path):
    """Acceptance: a 2-PROCESS PS run surfaces cluster-wide metrics through
    the stats wire op — each OS process serves its own shard + registry,
    the client merges the per-shard telemetry snapshots."""
    import subprocess
    import sys
    import textwrap

    from lightctr_tpu.dist.ps_server import ShardedPSClient

    server = textwrap.dedent(
        """
        import sys
        sys.path.insert(0, %r)
        from lightctr_tpu.embed.async_ps import AsyncParamServer
        from lightctr_tpu.dist.ps_server import ParamServerService
        ps = AsyncParamServer(dim=4, n_workers=2, seed=int(sys.argv[1]))
        svc = ParamServerService(ps)
        print("ADDR", svc.address[0], svc.address[1], flush=True)
        sys.stdin.read()   # serve until the parent closes our stdin
        svc.close()
        """
    ) % str(LIB_ROOT.parent)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [
        subprocess.Popen([sys.executable, "-c", server, str(i)],
                         stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                         text=True, env=env)
        for i in range(2)
    ]
    client = None
    try:
        addrs = []
        for p in procs:
            line = p.stdout.readline().split()
            assert line[0] == "ADDR", line
            addrs.append((line[1], int(line[2])))
        client = ShardedPSClient(addrs, 4)
        keys = np.arange(100, dtype=np.int64)  # 50 keys per modulo shard
        client.pull_arrays(keys, worker_epoch=0, worker_id=0)
        client.push_arrays(0, keys, np.ones((100, 4), np.float32),
                           worker_epoch=0)
        per_shard = client.stats()
        assert all(not s["down"] for s in per_shard)
        for s in per_shard:
            assert s["telemetry"]["counters"][
                obs.labeled("ps_requests_total", op="push")] == 1
        merged = obs.merge_snapshots([s["telemetry"] for s in per_shard
                                      if not s.get("down")])
        c = merged["counters"]
        # cluster-wide: both shards' pulls/pushes summed
        assert c[obs.labeled("ps_requests_total", op="pull")] == 2
        assert c[obs.labeled("ps_requests_total", op="push")] == 2
        assert c["ps_store_pulled_keys_total"] == 100
        assert c["ps_store_pushed_keys_total"] == 100
        assert merged["histograms"][
            obs.labeled("ps_op_seconds", op="push")]["count"] == 2
    finally:
        if client is not None:
            client.close()
        for p in procs:
            try:
                p.stdin.close()
            except OSError:
                pass
            p.wait(timeout=10)


# -- trainer instrumentation ------------------------------------------------


def _tiny_widedeep(vocab=4096, n_fields=4, dim=4, batch=64, seed=0):
    import jax

    from lightctr_tpu.models import widedeep

    rng = np.random.default_rng(seed)
    fids = rng.integers(0, vocab, size=(batch, n_fields)).astype(np.int32)
    fields = np.tile(np.arange(n_fields, dtype=np.int32), (batch, 1))
    mask = np.ones((batch, n_fields), np.float32)
    rep, rep_mask = widedeep.field_representatives(fids, fields, mask,
                                                   n_fields)
    batch_arrays = {
        "fids": fids, "fields": fields,
        "vals": np.ones((batch, n_fields), np.float32), "mask": mask,
        "labels": (rng.random(batch) > 0.5).astype(np.float32),
        "rep_fids": rep, "rep_mask": rep_mask,
    }
    params = widedeep.init(jax.random.PRNGKey(0), vocab, n_fields, dim)
    return params, batch_arrays


def test_hybrid_trainer_jsonl_reproduces_bench_byte_accounting(tmp_path):
    """Acceptance: a single-host hybrid run's per-step JSONL counters equal
    the byte accounting SPARSE_RING_BENCH.json is built from (both sides
    use dist.collectives.sparse_exchange_bytes on the same static shapes,
    so they can never disagree)."""
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.core.mesh import MeshSpec, make_mesh
    from lightctr_tpu.dist.collectives import sparse_exchange_bytes
    from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer

    n_dev = 8
    vocab, n_fields, dim, batch_n = 4096, 4, 4, 64
    params, batch = _tiny_widedeep(vocab, n_fields, dim, batch_n)
    mesh = make_mesh(MeshSpec(data=n_dev))
    tr = SparseTableCTRTrainer(
        params, __import__("lightctr_tpu.models.widedeep",
                           fromlist=["logits"]).logits,
        TrainConfig(learning_rate=0.05),
        sparse_tables={"w": ["fids"], "embed": ["rep_fids"]},
        mesh=mesh,
    )
    tr.telemetry = obs.MetricsRegistry()
    path = str(tmp_path / "run.jsonl")
    obs.configure_event_log(path=path, flush_every=1)
    try:
        for _ in range(3):
            tr.train_step(batch)
    finally:
        obs.get_event_log().flush()
        obs.configure_event_log()

    # the bench's accounting, from the same helpers on the same shapes
    k_w = batch["fids"].size // n_dev
    k_e = batch["rep_fids"].size // n_dev
    expect_sparse = (sparse_exchange_bytes(n_dev, k_w, 1)
                     + sparse_exchange_bytes(n_dev, k_e, dim))
    assert tr.exchange_policy == {"w": "sparse", "embed": "sparse"}

    steps = [r for r in obs.read_jsonl(path) if r["kind"] == "step"]
    assert len(steps) == 3
    for s in steps:
        assert s["sparse_exchange_bytes"] == expect_sparse
        assert s["dense_ring_bytes"] == 0
        assert s["exchange_policy"] == {"w": "sparse", "embed": "sparse"}
        assert s["examples"] == batch_n
        assert s["duration_s"] > 0
    # one exchange-decision event per table rode along
    decisions = [r for r in obs.read_jsonl(path) if r["kind"] == "exchange"]
    assert {d["table"] for d in decisions} == {"w", "embed"}
    # registry counters agree with the event-log per-step numbers
    c = tr.telemetry.snapshot()["counters"]
    assert c["trainer_steps_total"] == 3
    assert c["trainer_sparse_exchange_bytes_total"] == 3 * expect_sparse
    assert c["trainer_examples_total"] == 3 * batch_n


def test_trainer_telemetry_overhead_under_5_percent():
    """Tier-1 overhead guard: the instrumented step path must cost <5%
    wall time over the disabled path on CPU (min-of-reps to denoise).
    Covers the span-creation paths too: tracing is pinned to its default
    (rate 0), so the timed path includes every ``trace.enabled()`` guard
    the span instrumentation added — the acceptance bar for PR 3 is that
    those guards, not the spans, are what a disabled run pays for.

    PR 4 extends the bar to HEALTH MONITORING: the timed path carries a
    monitor with the full standard trainer detector set (NaN loss, loss
    spike, grad norm), so the per-step [loss, grad_norm] device fetch
    and the detector checks are inside the <5% budget — and the feed is
    asserted to have actually run (no passing by silently skipping).

    ISSUE 14 extends it again to the STEP STALL WATCHDOG: the timed path
    runs with an armed StepWatch (poll thread live, per-step
    step_completed feed), so the watchdog's hot-path cost — one lock +
    EWMA fold per step — is inside the same budget."""
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.models.ctr_trainer import CTRTrainer
    from lightctr_tpu.obs import health as health_mod
    from lightctr_tpu.obs import trace as trace_mod

    rng = np.random.default_rng(0)
    d = 256
    batch = {
        "x": rng.normal(size=(512, d)).astype(np.float32),
        "labels": (rng.random(512) > 0.5).astype(np.float32),
    }
    params = {"w": np.zeros((d,), np.float32)}
    tr = CTRTrainer(params, lambda p, b: b["x"] @ p["w"],
                    TrainConfig(learning_rate=0.1))
    hm = health_mod.HealthMonitor(component="overhead_guard",
                                  registry=obs.MetricsRegistry())
    health_mod.ensure_trainer_detectors(hm)
    tr.health = hm
    # the stall watchdog ARMED on the timed path (deadline far beyond
    # any sane step so it never trips into the measurement)
    sw = tr.arm_stepwatch(min_s=120.0, factor=1000.0,
                          registry=obs.MetricsRegistry())
    obs.configure_event_log()  # fresh in-memory ring (no disk writes)
    try:
        with trace_mod.override_rate(0.0):  # the documented default
            for _ in range(5):  # compile + warm both paths
                tr.train_step(batch)

            def run(n=60):
                t0 = time.perf_counter()
                for _ in range(n):
                    tr.train_step(batch)
                return time.perf_counter() - t0

            with obs.override(False):
                t_off = min(run() for _ in range(4))
            obs_before = hm.observations
            with obs.override(True):
                t_on = min(run() for _ in range(4))
            # the monitors were genuinely fed on the timed path (the
            # drain lags a bounded number of steps, never all of them)
            assert hm.observations - obs_before >= 4 * 60 - tr._HEALTH_MAX_LAG
            assert hm.status() == "ok"
            # ...and so was the armed watchdog, without ever tripping
            wst = sw.check()
            assert wst["steps"] >= 4 * 60 and not wst["stalled"]
    finally:
        sw.close()
        obs.configure_event_log()
        hm.close()
    # small absolute slack keeps the guard robust to scheduler noise while
    # still catching any real regression (a disk flush or sync per step
    # would blow far past this)
    assert t_on <= t_off * 1.05 + 0.005, (t_on, t_off)


# -- library hygiene --------------------------------------------------------


def test_no_bare_print_in_library_code():
    """Library code reports through obs/logging, never print().  cli/ is
    the user-facing surface and exempt (tools/ has its own rule below)."""
    offenders = []
    for path in sorted(LIB_ROOT.rglob("*.py")):
        rel = path.relative_to(LIB_ROOT)
        if rel.parts[0] == "cli":
            continue
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "print"):
                offenders.append(f"{rel}:{node.lineno}")
    assert not offenders, (
        "bare print() in library code (use logging or obs events): "
        + ", ".join(offenders)
    )


def test_no_bare_print_in_tools():
    """tools/ are CLIs whose stdout is a machine-readable artifact: a
    print there must either emit the artifact (first argument is a
    ``json.dumps(...)`` call) or explicitly say where it goes
    (``file=...`` — progress chatter belongs on stderr).  A bare print
    would interleave human text into the JSON stream a pipeline parses."""

    def _is_json_dumps(node) -> bool:
        return (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "dumps"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "json")

    tools_root = LIB_ROOT.parent / "tools"
    offenders = []
    for path in sorted(tools_root.glob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "print"):
                continue
            has_file = any(kw.arg == "file" for kw in node.keywords)
            artifact = bool(node.args) and _is_json_dumps(node.args[0])
            if not (has_file or artifact):
                offenders.append(f"{path.name}:{node.lineno}")
    assert not offenders, (
        "bare print() in tools/ (route progress to file=sys.stderr; only "
        "json.dumps artifacts may go to stdout): " + ", ".join(offenders)
    )


def test_every_ps_wire_op_has_a_latency_series_name():
    """Every ``MSG_*`` op the PS server dispatches must be in
    ``_OP_NAMES`` — the shared telemetry block records
    ``ps_op_seconds{op=...}`` under that name, so a new wire op missing
    here would hide as op="unknown" in every latency dashboard.
    (MSG_CLOSE terminates the connection before the telemetry block and
    is exempt.)"""
    from lightctr_tpu.dist import ps_server

    ops = {
        name: val for name, val in vars(ps_server).items()
        if name.startswith("MSG_") and isinstance(val, int)
    }
    missing = [
        name for name, val in sorted(ops.items())
        if val != ps_server.MSG_CLOSE and val not in ps_server._OP_NAMES
    ]
    assert not missing, (
        "PS wire ops without an _OP_NAMES entry (their latency would "
        "record as op=\"unknown\"): " + ", ".join(missing)
    )
    # and the flag bit can never collide with an op type
    from lightctr_tpu.dist import wire
    assert all(v < wire.TRACE_FLAG for v in ops.values())

    # the serving plane (serve/) and the online plane (online/) ride the
    # same framing and telemetry block: any MSG_* constant DEFINED there
    # (rather than imported from ps_server, the canonical op registry)
    # would dodge the vars() scan above — lint the ASTs so a wire op
    # assigned in either package can't ship dark either
    rogue = []
    for pkg in ("serve", "online"):
        for path in sorted((LIB_ROOT / pkg).glob("*.py")):
            tree = ast.parse(path.read_text(), filename=str(path))
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)
                        and node.targets[0].id.startswith("MSG_")
                        and isinstance(node.value, ast.Constant)
                        and isinstance(node.value.value, int)):
                    continue
                if node.value.value not in ps_server._OP_NAMES:
                    rogue.append(
                        f"{pkg}/{path.name}:{node.lineno} "
                        f"{node.targets[0].id}"
                    )
    assert not rogue, (
        "serve//online/ define MSG_* ops missing from ps_server._OP_NAMES "
        "(latency series would record as op=\"unknown\"): "
        + ", ".join(rogue)
    )


def test_every_health_detector_is_registered_and_series_declared():
    """No silent dark detectors: every ``*Detector`` class in obs/health.py
    AND obs/quality.py AND obs/resources.py AND obs/device.py (the
    quality, resource, and device planes register their detectors into
    the same ``KNOWN_DETECTORS`` at
    import) must declare literal ``name``/``signals`` class attributes
    and be listed in ``KNOWN_DETECTORS``; and every gauge/counter series
    obs/health.py writes (the first argument of each ``labeled(...)``
    call) must appear in ``HEALTH_SERIES`` — a detector whose metric is
    not declared there would never make it into dashboards or docs.
    (quality.py's series get the same treatment against
    ``QUALITY_SERIES`` in tests/test_quality.py, resources.py's against
    ``RESOURCE_SERIES`` in tests/test_resources.py, device.py's against
    ``DEVICE_SERIES`` in tests/test_device.py.)"""
    from lightctr_tpu.obs import device, health, quality, resources

    detectors = {}  # class name -> (module, detector name)
    for module, fname in ((health, "health.py"), (quality, "quality.py"),
                          (resources, "resources.py"),
                          (device, "device.py")):
        src = (LIB_ROOT / "obs" / fname).read_text()
        tree = ast.parse(src, filename=f"obs/{fname}")

        labeled_series = set()
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "labeled"
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                labeled_series.add(node.args[0].value)
            if not (isinstance(node, ast.ClassDef)
                    and node.name.endswith("Detector")
                    and node.name != "Detector"):
                continue
            attrs = {}
            for stmt in node.body:
                if (isinstance(stmt, ast.Assign)
                        and len(stmt.targets) == 1
                        and isinstance(stmt.targets[0], ast.Name)):
                    attrs[stmt.targets[0].id] = stmt.value
            assert isinstance(attrs.get("name"), ast.Constant) and \
                isinstance(attrs["name"].value, str) and \
                attrs["name"].value, \
                f"{node.name} must declare a literal class-level name"
            sig = attrs.get("signals")
            assert isinstance(sig, ast.Tuple) and sig.elts, \
                f"{node.name} must declare a non-empty literal signals tuple"
            detectors[node.name] = (module, attrs["name"].value)
        if module is health:
            # every series written is declared, nothing declared is dead
            assert labeled_series == set(health.HEALTH_SERIES), (
                labeled_series, set(health.HEALTH_SERIES))

    assert detectors, "no Detector subclasses found (lint is miswired)"
    names = {dname for _, dname in detectors.values()}
    assert len(names) == len(detectors), "duplicate detector names"
    # every subclass is in the registry, and vice versa
    assert names == set(health.KNOWN_DETECTORS), (
        names, set(health.KNOWN_DETECTORS))
    for cname, (module, dname) in detectors.items():
        assert health.KNOWN_DETECTORS[dname] is getattr(module, cname)

    # and a tripped detector really lights its gauge + transition counter
    reg = obs.MetricsRegistry()
    hm = health.HealthMonitor(component="lint", registry=reg)
    try:
        hm.add_detector(health.NaNLossDetector())
        hm.observe(loss=float("nan"))
        snap = reg.snapshot()
        assert snap["gauges"][obs.labeled(
            "health_status", component="lint", detector="nan_loss")] == 2
        assert snap["gauges"][obs.labeled(
            "health_component_status", component="lint")] == 2
        assert snap["counters"][obs.labeled(
            "health_transitions_total", component="lint",
            detector="nan_loss", to="unhealthy")] == 1
    finally:
        hm.close()


def test_every_tier_series_is_declared_and_emitted():
    """No dark tier counters: every ``tiered_*`` metric the tiered store
    EMITS (a literal first argument of a registry ``inc``/``gauge_set``/
    ``observe`` call, directly or through ``labeled(...)``) must be
    declared in ``embed.tiered.TIER_SERIES`` — and every declared series
    must actually be emitted (a stale declaration would document a metric
    that no longer exists).  A tier-transition counter can therefore
    never ship unregistered/undocumented."""
    from lightctr_tpu.embed import tiered

    src = (LIB_ROOT / "embed" / "tiered.py").read_text()
    tree = ast.parse(src, filename="embed/tiered.py")

    emitted = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("inc", "gauge_set", "observe")
                and node.args):
            continue
        arg = node.args[0]
        if (isinstance(arg, ast.Call) and isinstance(arg.func, ast.Name)
                and arg.func.id == "labeled" and arg.args):
            arg = arg.args[0]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str) \
                and arg.value.startswith("tiered_"):
            emitted.add(arg.value)

    declared = set(tiered.TIER_SERIES)
    assert emitted, "no tiered_* emissions found (lint is miswired)"
    undeclared = emitted - declared
    assert not undeclared, (
        "tiered_* series emitted but missing from TIER_SERIES "
        "(dark counters): " + ", ".join(sorted(undeclared))
    )
    dead = declared - emitted
    assert not dead, (
        "TIER_SERIES declares series the store never emits "
        "(stale declarations): " + ", ".join(sorted(dead))
    )
    assert len(tiered.TIER_SERIES) == len(declared), \
        "duplicate names in TIER_SERIES"


# -- tools/metrics_report ----------------------------------------------------


def test_metrics_report_prom_renders_golden_snapshot(tmp_path, capsys):
    """The ``--prom`` renderer must be exactly ``render_prometheus`` over
    the snapshot JSON — one exposition path, no drift."""
    import tools.metrics_report as metrics_report

    r = obs.MetricsRegistry()
    r.inc("reqs_total", 4)
    r.inc(obs.labeled("ops_total", op="pull"), 2)
    r.gauge_set("depth", 1)
    r.observe(obs.labeled("lat_seconds", op="pull"), 0.2, buckets=(0.1, 1.0))
    snap = r.snapshot()
    path = tmp_path / "snap.json"
    path.write_text(json.dumps(snap))

    assert metrics_report.main(["--prom", str(path)]) == 0
    out = capsys.readouterr().out
    assert out == obs.render_prometheus(snap, prefix="lightctr_")
    # spot-check the golden shape, so a silent render_prometheus change
    # still fails loudly here
    assert "# TYPE lightctr_reqs_total counter" in out
    assert 'lightctr_lat_seconds_bucket{op="pull",le="+Inf"} 1' in out


def test_metrics_report_tolerates_malformed_jsonl_lines(tmp_path):
    """A crash-truncated or corrupted event log must still summarize:
    read_jsonl skips undecodable lines by default (strict=True raises)."""
    import tools.metrics_report as metrics_report

    path = tmp_path / "run.jsonl"
    good1 = json.dumps({"v": 1, "ts": 1.0, "kind": "step",
                        "duration_s": 0.01, "examples": 8})
    good2 = json.dumps({"v": 1, "ts": 2.0, "kind": "epoch", "loss": 0.5})
    torn = '{"v": 1, "ts": 3.0, "kind": "step", "durat'  # torn tail
    path.write_text(good1 + "\n" + "{{{not json}}}\n" + good2 + "\n" + torn)

    recs = obs.read_jsonl(str(path))
    assert len(recs) == 2
    with pytest.raises(json.JSONDecodeError):
        obs.read_jsonl(str(path), strict=True)

    report = metrics_report.summarize(recs)
    assert report["events"] == 2
    assert report["by_kind"] == {"epoch": 1, "step": 1}
    assert report["steps"]["examples_total"] == 8


# -- fused kernel registry lints (ISSUE 9) ----------------------------------


def test_pallas_call_sites_route_through_kernel_registry():
    """Every ``pallas_call`` site in the tree must belong to a module that
    registers its kernel(s) in the ``ops.sparse_kernels`` registry — a
    direct call with no registered XLA form would crash CPU
    tier-1 the moment the dispatcher cannot gate it.  Module-level calls
    (executed at import) are banned outright."""
    import importlib

    from lightctr_tpu.ops import sparse_kernels as sk

    call_sites = {}
    for path in sorted(LIB_ROOT.rglob("*.py")):
        rel = path.relative_to(LIB_ROOT)
        tree = ast.parse(path.read_text(), filename=str(path))
        # no pallas_call outside any function body (import-time execution)
        toplevel = {
            id(n) for fn in ast.walk(tree)
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
            for n in ast.walk(fn)
        }
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                mod = "lightctr_tpu." + str(rel)[:-3].replace("/", ".")
                call_sites.setdefault(mod, []).append(node.lineno)
                assert id(node) in toplevel, (
                    f"{rel}:{node.lineno}: module-level pallas_call")
    assert call_sites, "lint is vacuous: no pallas_call sites found"
    # import every module holding a call site (registration happens at
    # import), then demand its pallas impls are registered
    for mod in call_sites:
        importlib.import_module(mod)
    registered_modules = {kd.pallas.__module__ for kd in sk.KERNELS.values()}
    unrouted = {m: lines for m, lines in call_sites.items()
                if m not in registered_modules}
    assert not unrouted, (
        "pallas_call sites outside the kernel registry (register the "
        f"kernel + its XLA form in ops.sparse_kernels): {unrouted}"
    )


def test_every_registered_kernel_declares_its_xla_form():
    """Registry contract: both impls callable, the pallas impl accepts
    ``interpret=`` (the CPU parity path), the phase is declared, and the
    kernels with two implementations are present."""
    import inspect

    import lightctr_tpu.nn.flash_attention    # noqa: F401 (self-registers)
    from lightctr_tpu.ops import sparse_kernels as sk

    assert {"quantize_pack", "quantize_pack_ef",
            "flash_attention"} == set(sk.KERNELS)
    for name, kd in sk.KERNELS.items():
        assert kd.phase in sk.KERNEL_PHASES, name
        assert callable(kd.reference), f"{name}: no XLA form"
        assert callable(kd.pallas), f"{name}: no pallas impl"
        assert "interpret" in inspect.signature(kd.pallas).parameters, (
            f"{name}: pallas impl must accept interpret=")


def test_metrics_report_kernels_section(tmp_path, capsys, monkeypatch):
    """--kernels parses trainer_kernel_path_total{phase,impl} out of a
    registry snapshot: per-phase impl counts plus the fused-active flag
    (which implementation actually ran — measured, not assumed)."""
    import tools.metrics_report as metrics_report
    from lightctr_tpu.ops import sparse_kernels as sk

    reg = obs.MetricsRegistry()
    monkeypatch.setattr(obs, "default_registry", lambda: reg)
    monkeypatch.setattr(sk.obs, "default_registry", lambda: reg)
    import jax.numpy as jnp
    from lightctr_tpu.ops import quantize
    x = jnp.linspace(-1.0, 1.0, 8)
    for bits in (8, 16):
        sk.quantize_pack(quantize.build_table(-1.0, 1.0, bits=bits), x)
    path = tmp_path / "snap.json"
    path.write_text(json.dumps(reg.snapshot()))
    assert metrics_report.main(["--kernels", str(path)]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["phases"] == {"pack": {"xla": 2}}
    assert report["dispatches_by_impl"] == {"xla": 2}
    assert report["fused_active"] is False


@pytest.mark.parametrize("sharded", [False, True], ids=["table", "shards"])
def test_metrics_report_kernels_apply_section(sharded, tmp_path, capsys):
    """--kernels' ``apply`` section: live rows over the slots of the rung
    taken, per table — and per row shard, beside the table's sums, on a
    snapshot whose two counters carry the ``shard`` label."""
    import tools.metrics_report as metrics_report

    reg = obs.MetricsRegistry()
    per = {"0": (22_870, 29_952), "1": (22_523, 29_952)} if sharded \
        else {None: (45_393, 49_920)}
    for shard, (live, slots) in per.items():
        labels = dict(table="embed", **({"shard": shard} if sharded else {}))
        reg.inc(obs.labeled("trainer_apply_live_rows_total", **labels), live)
        reg.inc(obs.labeled("trainer_apply_slots_total", **labels), slots)
    # where the counts came from: the step's health vector, or the host
    reg.inc(obs.labeled("trainer_health_signals_total", source="device"), 511)
    if sharded:
        reg.inc(obs.labeled("trainer_health_signals_total", source="host"), 2)
    path = tmp_path / "snap.json"
    path.write_text(json.dumps(reg.snapshot()))
    assert metrics_report.main(["--kernels", str(path)]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["health_signals"] == (
        {"device": 511, "host": 2} if sharded else {"device": 511})
    entry = report["apply"]["embed"]
    if not sharded:
        assert entry == {"live_rows": 45_393, "slots": 49_920,
                         "live_share": 0.9093}
        return
    assert entry["shards"] == {
        "0": {"live_rows": 22_870, "slots": 29_952, "live_share": 0.7636},
        "1": {"live_rows": 22_523, "slots": 29_952, "live_share": 0.752}}
    assert (entry["live_rows"], entry["slots"], entry["live_share"]) == (
        45_393, 59_904, 0.7578)


# -- exchange telemetry lints + report (ISSUE 10) ----------------------------


def test_every_exchange_series_is_declared_and_emitted():
    """No dark exchange counters: every ``trainer_*`` metric the sparse
    trainer EMITS (a literal first argument of a registry
    ``inc``/``gauge_set``/``observe`` call, directly or through
    ``labeled(...)``/``obs.labeled(...)``) must be declared in
    ``models.sparse_trainer.EXCHANGE_SERIES`` — and every declared series
    must actually be emitted.  The hierarchical per-hop counters
    (``trainer_hier_wire/local_bytes_total``) can therefore never ship
    unregistered or go stale."""
    from lightctr_tpu.models import sparse_trainer

    src = (LIB_ROOT / "models" / "sparse_trainer.py").read_text()
    tree = ast.parse(src, filename="models/sparse_trainer.py")

    emitted = set()
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("inc", "gauge_set", "observe")
                and node.args):
            continue
        arg = node.args[0]
        if isinstance(arg, ast.Call) and arg.args and (
                (isinstance(arg.func, ast.Name)
                 and arg.func.id == "labeled")
                or (isinstance(arg.func, ast.Attribute)
                    and arg.func.attr == "labeled")):
            arg = arg.args[0]
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str) \
                and arg.value.startswith("trainer_"):
            emitted.add(arg.value)

    declared = set(sparse_trainer.EXCHANGE_SERIES)
    assert emitted, "no trainer_* emissions found (lint is miswired)"
    undeclared = emitted - declared
    assert not undeclared, (
        "trainer_* series emitted but missing from EXCHANGE_SERIES "
        "(dark counters): " + ", ".join(sorted(undeclared))
    )
    dead = declared - emitted
    assert not dead, (
        "EXCHANGE_SERIES declares series the trainer never emits "
        "(stale declarations): " + ", ".join(sorted(dead))
    )
    assert len(sparse_trainer.EXCHANGE_SERIES) == len(declared), \
        "duplicate names in EXCHANGE_SERIES"


def test_every_round_cluster_stall_series_is_declared_and_emitted():
    """The ISSUE-14 observability planes follow the same no-dark-series
    contract as EXCHANGE_SERIES/HEALTH_SERIES: every ``hier_round_*`` or
    ``hier_stripe_*`` series dist/hier.py emits must be declared in
    ``HIER_ROUND_SERIES``, every ``cluster_*`` in obs/cluster.py in
    ``CLUSTER_SERIES``, every ``stall_*`` in obs/stepwatch.py in
    ``STALL_SERIES`` — and every declaration must be emitted (both
    directions, no duplicates).  A case's prefix may be a TUPLE of
    prefixes — one declaration tuple can own several series families in
    one module (the ISSUE-16 stripe counters live beside the round
    series)."""
    from lightctr_tpu.dist import hier
    from lightctr_tpu.obs import cluster as cluster_mod
    from lightctr_tpu.obs import stepwatch as stepwatch_mod

    cases = [
        (LIB_ROOT / "dist" / "hier.py", ("hier_round_", "hier_stripe_"),
         hier.HIER_ROUND_SERIES, "HIER_ROUND_SERIES"),
        (LIB_ROOT / "obs" / "cluster.py", "cluster_",
         cluster_mod.CLUSTER_SERIES, "CLUSTER_SERIES"),
        (LIB_ROOT / "obs" / "stepwatch.py", "stall_",
         stepwatch_mod.STALL_SERIES, "STALL_SERIES"),
    ]
    for path, prefix, series, decl_name in cases:
        tree = ast.parse(path.read_text(), filename=str(path))
        emitted = set()
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("inc", "gauge_set", "observe")
                    and node.args):
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Call) and arg.args and (
                    (isinstance(arg.func, ast.Name)
                     and arg.func.id == "labeled")
                    or (isinstance(arg.func, ast.Attribute)
                        and arg.func.attr == "labeled")):
                arg = arg.args[0]
            if isinstance(arg, ast.Constant) \
                    and isinstance(arg.value, str) \
                    and arg.value.startswith(prefix):
                emitted.add(arg.value)
        declared = set(series)
        assert emitted, f"no {prefix}* emissions in {path.name} " \
                        "(lint is miswired)"
        assert emitted == declared, (
            f"{path.name} {prefix}* emissions != {decl_name}: "
            f"dark={sorted(emitted - declared)} "
            f"stale={sorted(declared - emitted)}"
        )
        assert len(series) == len(declared), \
            f"duplicate names in {decl_name}"


def test_metrics_report_exchange_section(tmp_path, capsys):
    """--exchange parses the per-table algo/byte series — the
    hierarchical algo and its per-hop local/wire split included — out of
    a registry snapshot."""
    import tools.metrics_report as metrics_report

    reg = obs.MetricsRegistry()
    reg.inc(obs.labeled("trainer_exchange_algo_total",
                        table="v", algo="hier"), 3)
    reg.inc(obs.labeled("trainer_exchange_algo_total",
                        table="w", algo="sparse_rs"), 3)
    reg.inc(obs.labeled("trainer_exchange_bytes_total",
                        table="v", policy="hier"), 3000)
    reg.inc("trainer_hier_wire_bytes_total", 3000)
    reg.inc("trainer_hier_local_bytes_total", 12000)
    reg.inc("trainer_sparse_rs_bytes_total", 900)
    reg.inc("trainer_rs_fallback_total", 1)
    # wire-codec honesty counters (ISSUE 13)
    reg.inc("trainer_hier_wire_packed_bytes_total", 1000)
    reg.inc("trainer_hier_wire_fp32_bytes_total", 4500)
    reg.inc("trainer_hier_wire_id_saved_bytes_total", 250)
    reg.gauge_set("trainer_hier_wire_ef_mass", 0.125)
    # streaming rendezvous counters (ISSUE 16)
    reg.inc("trainer_hier_chunk_pushes_total", 24)
    reg.inc("trainer_hier_chunk_rows_total", 600)
    reg.inc("trainer_hier_chunk_capacity_rows_total", 768)
    reg.inc("trainer_hier_overlap_push_seconds_total", 2.0)
    reg.inc("trainer_hier_overlap_blocked_seconds_total", 0.5)
    path = tmp_path / "snap.json"
    path.write_text(json.dumps(reg.snapshot()))
    assert metrics_report.main(["--exchange", str(path)]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["tables"]["v"]["algo_steps"] == {"hier": 3}
    assert report["tables"]["w"]["algo_steps"] == {"sparse_rs": 3}
    assert report["tables"]["v"]["bytes"] == {"hier": 3000}
    assert report["bytes_by_algo"]["hier_wire"] == 3000
    assert report["bytes_by_algo"]["hier_local"] == 12000
    assert report["bytes_by_algo"]["sparse_rs"] == 900
    assert report["rs_fallback_steps"] == 1
    assert report["hier_active"] is True
    assert report["hier_local_to_wire_x"] == 4.0
    codec = report["wire_codec"]
    assert codec["packed_bytes"] == 1000
    assert codec["fp32_equiv_bytes"] == 4500
    assert codec["compression_x"] == 4.5
    assert codec["shared_id_saved_bytes"] == 250
    assert codec["shared_id_dedup_x"] == 1.25
    assert codec["ef_residual_mass"] == 0.125
    # the streaming section: chunk fill = rows / window capacity, overlap
    # ratio = the share of the push wall hidden under compute
    streaming = report["streaming"]
    assert streaming["chunk_pushes"] == 24
    assert streaming["chunk_rows"] == 600
    assert streaming["chunk_fill"] == round(600 / 768, 3)
    assert streaming["push_seconds"] == 2.0
    assert streaming["blocked_seconds"] == 0.5
    assert streaming["overlap_ratio"] == 0.75


# -- online plane telemetry lints + report (ISSUE 11) ------------------------


def test_every_online_series_is_declared_and_emitted():
    """No dark online counters: every ``online_*`` / ``serve_freshness_*``
    metric the online plane EMITS (a literal first argument of a registry
    ``inc``/``gauge_set``/``observe`` call, directly or through
    ``labeled(...)``) — across every module of ``lightctr_tpu/online/`` —
    must be declared in ``online.ONLINE_SERIES``, and every declared
    series must actually be emitted.  A freshness gauge or swap counter
    can therefore never ship unregistered or go stale."""
    from lightctr_tpu import online

    emitted = set()
    for path in sorted((LIB_ROOT / "online").glob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("inc", "gauge_set", "observe")
                    and node.args):
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Call) and arg.args and (
                    (isinstance(arg.func, ast.Name)
                     and arg.func.id == "labeled")
                    or (isinstance(arg.func, ast.Attribute)
                        and arg.func.attr == "labeled")):
                arg = arg.args[0]
            if isinstance(arg, ast.Constant) \
                    and isinstance(arg.value, str) \
                    and (arg.value.startswith("online_")
                         or arg.value.startswith("serve_freshness_")):
                emitted.add(arg.value)

    declared = set(online.ONLINE_SERIES)
    assert emitted, "no online emissions found (lint is miswired)"
    undeclared = emitted - declared
    assert not undeclared, (
        "online series emitted but missing from ONLINE_SERIES "
        "(dark counters): " + ", ".join(sorted(undeclared))
    )
    dead = declared - emitted
    assert not dead, (
        "ONLINE_SERIES declares series the plane never emits "
        "(stale declarations): " + ", ".join(sorted(dead))
    )
    assert len(online.ONLINE_SERIES) == len(declared), \
        "duplicate names in ONLINE_SERIES"


def test_metrics_report_online_section(tmp_path, capsys):
    """--online parses the freshness / swap / trainer series out of a
    registry snapshot: deltas applied vs dropped-to-full-refresh (by
    reason), apply-age percentiles, swap attempts/refusals, trainer
    step+export counters — the golden shape the online dashboards read."""
    import tools.metrics_report as metrics_report

    reg = obs.MetricsRegistry()
    reg.inc("serve_freshness_polls_total", 20)
    reg.inc("serve_freshness_deltas_applied_total", 12)
    reg.inc("serve_freshness_rows_dropped_total", 34)
    reg.inc(obs.labeled("serve_freshness_full_refresh_total",
                        reason="floor"), 2)
    reg.inc(obs.labeled("serve_freshness_full_refresh_total",
                        reason="down"), 1)
    reg.gauge_set("serve_freshness_age_seconds", 0.25)
    for age in (0.01, 0.02, 0.4):
        reg.observe("serve_freshness_apply_age_seconds", age)
    reg.inc("online_swap_attempts_total", 3)
    reg.inc("online_swap_accepted_total", 1)
    reg.inc(obs.labeled("online_swap_refused_total", reason="parity"), 1)
    reg.inc(obs.labeled("online_swap_refused_total", reason="load"), 1)
    reg.gauge_set("online_swap_shadow_diff", 0.8)
    reg.inc("online_steps_total", 100)
    reg.inc("online_examples_total", 6400)
    reg.inc("online_exports_total", 5)
    reg.gauge_set("online_loss", 0.31)
    path = tmp_path / "snap.json"
    path.write_text(json.dumps(reg.snapshot()))
    assert metrics_report.main(["--online", str(path)]) == 0
    report = json.loads(capsys.readouterr().out)
    fresh = report["freshness"]
    assert fresh["polls"] == 20
    assert fresh["deltas_applied"] == 12
    assert fresh["rows_dropped"] == 34
    assert fresh["full_refreshes"] == {
        "total": 3, "by_reason": {"floor": 2, "down": 1}}
    assert fresh["age_s"] == 0.25
    assert fresh["apply_age"]["count"] == 3
    assert fresh["apply_age"]["p99_ms"] > fresh["apply_age"]["p50_ms"]
    swap = report["swap"]
    assert swap["attempts"] == 3 and swap["accepted"] == 1
    assert swap["refused"] == {
        "total": 2, "by_reason": {"parity": 1, "load": 1}}
    assert swap["last_shadow_diff"] == 0.8
    trainer = report["trainer"]
    assert trainer["steps"] == 100 and trainer["examples"] == 6400
    assert trainer["exports"] == 5 and trainer["last_loss"] == 0.31

    # a trainer-only snapshot (no freshness/swap series at all) must
    # omit those sections entirely, not render them zeroed
    reg2 = obs.MetricsRegistry()
    reg2.inc("online_steps_total", 3)
    path2 = tmp_path / "snap2.json"
    path2.write_text(json.dumps(reg2.snapshot()))
    assert metrics_report.main(["--online", str(path2)]) == 0
    report2 = json.loads(capsys.readouterr().out)
    assert "freshness" not in report2 and "swap" not in report2
    assert report2["trainer"]["steps"] == 3


# -- compiled data plane telemetry lints + report (ISSUE 20) ------------------


def test_every_ingest_series_is_declared_and_emitted():
    """No dark ingest counters: every ``ingest_*`` metric the data plane
    EMITS (a literal first argument of a registry
    ``inc``/``gauge_set``/``observe`` call, directly or through
    ``labeled(...)``) — across every module of ``lightctr_tpu/data/`` —
    must be declared in ``ingest.INGEST_SERIES``, and every declared
    series must actually be emitted.  A shard-cache counter or the
    overlap honesty gauge can therefore never ship unregistered or go
    stale."""
    from lightctr_tpu.data import ingest

    emitted = set()
    for path in sorted((LIB_ROOT / "data").glob("*.py")):
        tree = ast.parse(path.read_text(), filename=str(path))
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in ("inc", "gauge_set", "observe")
                    and node.args):
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Call) and arg.args and (
                    (isinstance(arg.func, ast.Name)
                     and arg.func.id == "labeled")
                    or (isinstance(arg.func, ast.Attribute)
                        and arg.func.attr == "labeled")):
                arg = arg.args[0]
            if isinstance(arg, ast.Constant) \
                    and isinstance(arg.value, str) \
                    and arg.value.startswith("ingest_"):
                emitted.add(arg.value)

    declared = set(ingest.INGEST_SERIES)
    assert emitted, "no ingest emissions found (lint is miswired)"
    undeclared = emitted - declared
    assert not undeclared, (
        "ingest series emitted but missing from INGEST_SERIES "
        "(dark counters): " + ", ".join(sorted(undeclared))
    )
    dead = declared - emitted
    assert not dead, (
        "INGEST_SERIES declares series the plane never emits "
        "(stale declarations): " + ", ".join(sorted(dead))
    )
    assert len(ingest.INGEST_SERIES) == len(declared), \
        "duplicate names in INGEST_SERIES"


def test_metrics_report_ingest_section(tmp_path, capsys):
    """--ingest parses the shard-cache and prefetch series out of a
    registry snapshot: compile/hit/recovery and rows/bytes counters, the
    prefetch delivered/ready counts, the overlap honesty gauge,
    consumer-wait percentiles, and the queue's depth/capacity face."""
    import tools.metrics_report as metrics_report

    reg = obs.MetricsRegistry()
    reg.inc("ingest_shard_compiles_total", 2)
    reg.inc("ingest_shard_cache_hits_total", 5)
    reg.inc("ingest_shard_recoveries_total", 1)
    reg.inc("ingest_shard_rows_total", 100000)
    reg.inc("ingest_shard_bytes_total", 1 << 20)
    reg.inc("ingest_replay_blocks_total", 25)
    reg.inc("ingest_prefetch_batches_total", 40)
    reg.inc("ingest_prefetch_ready_total", 36)
    reg.gauge_set("ingest_overlap_ratio", 0.9)
    for w in (0.0, 0.001, 0.01):
        reg.observe("ingest_wait_seconds", w)
    reg.gauge_set('resource_queue_depth{queue="ingest_prefetch"}', 3)
    reg.gauge_set('resource_queue_capacity{queue="ingest_prefetch"}', 4)
    path = tmp_path / "snap.json"
    path.write_text(json.dumps(reg.snapshot()))
    assert metrics_report.main(["--ingest", str(path)]) == 0
    report = json.loads(capsys.readouterr().out)
    cache = report["shard_cache"]
    assert cache["compiles"] == 2 and cache["cache_hits"] == 5
    assert cache["recoveries"] == 1
    assert cache["rows_written"] == 100000
    assert cache["bytes_written"] == 1 << 20
    assert cache["blocks_replayed"] == 25
    pre = report["prefetch"]
    assert pre["batches"] == 40 and pre["ready"] == 36
    assert pre["overlap_ratio"] == 0.9
    assert pre["wait"]["count"] == 3
    assert pre["queue"] == {"depth": 3, "capacity": 4, "fill": 0.75}

    # a compile-only snapshot (no prefetch series) must omit the
    # prefetch section entirely, not render it zeroed
    reg2 = obs.MetricsRegistry()
    reg2.inc("ingest_shard_compiles_total")
    path2 = tmp_path / "snap2.json"
    path2.write_text(json.dumps(reg2.snapshot()))
    assert metrics_report.main(["--ingest", str(path2)]) == 0
    report2 = json.loads(capsys.readouterr().out)
    assert "prefetch" not in report2
    assert report2["shard_cache"]["compiles"] == 1
