"""Health plane: detectors, hysteresis state machine, HTTP ops endpoints,
cluster verdict aggregation, master degraded-before-dead, and the
anomaly -> flight-dump path."""

import json
import os
import time
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np
import pytest

from lightctr_tpu import obs
from lightctr_tpu.obs import exporter, flight, health

LIB_ROOT = Path(__file__).resolve().parents[1] / "lightctr_tpu"


def _monitor(**kw):
    kw.setdefault("registry", obs.MetricsRegistry())
    kw.setdefault("flight_min_interval_s", 0.0)
    return health.HealthMonitor(**kw)


def _get(url, timeout=5.0):
    """(status_code, parsed_json_or_text) tolerating HTTP error codes."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            body = r.read()
            code = r.status
    except urllib.error.HTTPError as e:
        body = e.read()
        code = e.code
    try:
        return code, json.loads(body)
    except json.JSONDecodeError:
        return code, body.decode()


# -- detectors ---------------------------------------------------------------


def test_nan_loss_trips_in_one_observation():
    hm = _monitor(component="t_nan")
    try:
        hm.add_detector(health.NaNLossDetector())
        hm.observe(loss=0.5)
        assert hm.status() == health.OK
        hm.observe(loss=float("nan"))  # trip_after=1: conclusive on sight
        assert hm.status() == health.UNHEALTHY
        v = hm.verdict()
        assert v["detectors"]["nan_loss"]["status"] == health.UNHEALTHY
        hm.observe(loss=float("inf"))
        assert hm.status() == health.UNHEALTHY
    finally:
        hm.close()


def test_loss_spike_zscore_flags_divergence():
    det = health.LossSpikeDetector(z_threshold=6.0, warmup=10)
    rng = np.random.default_rng(0)
    for _ in range(30):
        st, _ = det.check({"loss": 0.5 + 0.01 * rng.standard_normal()})
        assert st == health.OK
    st, detail = det.check({"loss": 5.0})  # far outside the EWMA band
    assert st == health.UNHEALTHY and detail["z"] > 12
    # the spike was NOT absorbed: the baseline still flags it next step
    st, _ = det.check({"loss": 5.0})
    assert st != health.OK
    # and a NaN is left to the NaN detector, never poisoning the EWMA
    st, detail = det.check({"loss": float("nan")})
    assert st == health.OK and detail == {"skipped": "non-finite"}


def test_grad_norm_explosion_and_nonfinite():
    det = health.GradNormDetector(explode_ratio=50.0, warmup=5)
    for _ in range(10):
        assert det.check({"grad_norm": 1.0})[0] == health.OK
    assert det.check({"grad_norm": 100.0})[0] == health.DEGRADED
    assert det.check({"grad_norm": 1e5})[0] == health.UNHEALTHY
    assert det.check({"grad_norm": float("nan")})[0] == health.UNHEALTHY
    det2 = health.GradNormDetector(abs_limit=10.0, warmup=0)
    assert det2.check({"grad_norm": 11.0})[0] == health.UNHEALTHY


def test_table_skew_dead_and_hot_tables():
    det = health.TableSkewDetector(hot_density=0.05, dead_unique=1)
    ok = {"t": {"unique": 500, "ids": 1000, "vocab": 4096}}
    assert det.check({"table_touch": ok})[0] == health.OK
    hot = {"t": {"unique": 10, "ids": 1000, "vocab": 4096}}
    st, detail = det.check({"table_touch": hot})
    assert st == health.DEGRADED and detail["t"]["why"] == "hot"
    dead = {"t": {"unique": 1, "ids": 1000, "vocab": 4096}}
    st, detail = det.check({"table_touch": dead})
    assert st == health.UNHEALTHY and detail["t"]["why"] == "dead"
    # worst table wins
    st, detail = det.check({"table_touch": {**ok, "u": dead["t"]}})
    assert st == health.UNHEALTHY and "u" in detail and "t" not in detail


def test_staleness_slo_breach():
    det = health.StalenessDetector(slo=10, hard_factor=2.0)
    assert det.check({"staleness": 3})[0] == health.OK
    assert det.check({"staleness": 15})[0] == health.DEGRADED
    assert det.check({"staleness": 25})[0] == health.UNHEALTHY


def test_heartbeat_gap_detector():
    det = health.HeartbeatGapDetector()
    assert det.check({"peers": {"stale": [], "dead": []}})[0] == health.OK
    assert det.check(
        {"peers": {"stale": ["3"], "dead": []}})[0] == health.DEGRADED
    st, detail = det.check({"peers": {"stale": [], "dead": ["3"]}})
    assert st == health.UNHEALTHY and detail["dead"] == ["3"]


# -- state machine -----------------------------------------------------------


def test_hysteresis_no_flap_on_one_bad_step():
    hm = _monitor(component="t_hyst", trip_after=2, recover_after=3)
    try:
        hm.add_detector(health.StalenessDetector(slo=10))
        hm.observe(staleness=0)
        hm.observe(staleness=15)  # one bad observation: no transition
        assert hm.status() == health.OK
        hm.observe(staleness=0)   # streak broken
        hm.observe(staleness=15)
        assert hm.status() == health.OK
        hm.observe(staleness=15)  # second consecutive: latch
        assert hm.status() == health.DEGRADED
        # recovery needs recover_after consecutive good observations
        hm.observe(staleness=0)
        hm.observe(staleness=0)
        assert hm.status() == health.DEGRADED
        hm.observe(staleness=0)
        assert hm.status() == health.OK
    finally:
        hm.close()


def test_recovery_steps_down_through_worst_seen_in_streak():
    hm = _monitor(component="t_steps", trip_after=1, recover_after=2)
    try:
        hm.add_detector(health.StalenessDetector(slo=10, hard_factor=2.0))
        hm.observe(staleness=30)
        assert hm.status() == health.UNHEALTHY
        # improvement streak contains a DEGRADED sample: land there, not OK
        hm.observe(staleness=15)
        hm.observe(staleness=0)
        assert hm.status() == health.DEGRADED
        hm.observe(staleness=0)
        hm.observe(staleness=0)
        assert hm.status() == health.OK
    finally:
        hm.close()


def test_transitions_emit_events_and_gauges():
    obs.configure_event_log()
    hm = _monitor(component="t_emit", trip_after=1)
    try:
        hm.add_detector(health.NaNLossDetector())
        # both gauges are seeded at OK before any transition: "0" means
        # healthy, absence means not monitored
        snap = hm.registry.snapshot()
        assert snap["gauges"][obs.labeled(
            "health_component_status", component="t_emit")] == 0
        assert snap["gauges"][obs.labeled(
            "health_status", component="t_emit", detector="nan_loss")] == 0
        hm.observe(loss=float("nan"))
        recs = [r for r in obs.get_event_log().records()
                if r["kind"] == "health"]
        dets = {r["detector"] for r in recs}
        assert dets == {"nan_loss", "aggregate"}
        for r in recs:
            assert r["component"] == "t_emit"
            assert r["status"] == health.UNHEALTHY
            assert r["prev"] == health.OK
        snap = hm.registry.snapshot()
        assert snap["gauges"][obs.labeled(
            "health_status", component="t_emit",
            detector="nan_loss")] == health.SEVERITY[health.UNHEALTHY]
    finally:
        hm.close()
        obs.configure_event_log()


def test_monitor_disabled_by_gate_and_env_switch():
    hm = _monitor(component="t_gate", trip_after=1)
    try:
        hm.add_detector(health.NaNLossDetector())
        with obs.override(False):  # LIGHTCTR_TELEMETRY=0 hard-disables
            hm.observe(loss=float("nan"))
        assert hm.status() == health.OK and hm.observations == 0
        with health.override(False):  # LIGHTCTR_HEALTH=0 too
            hm.observe(loss=float("nan"))
            assert not hm.wants("loss")  # producers skip building signals
        assert hm.status() == health.OK
        hm.observe(loss=float("nan"))
        assert hm.status() == health.UNHEALTHY
    finally:
        hm.close()


def test_detector_exception_is_contained():
    class BrokenDetector(health.Detector):
        name = "broken"
        signals = ("loss",)

        def check(self, signals):
            raise RuntimeError("detector bug")

    hm = _monitor(component="t_broken", trip_after=1)
    try:
        hm.add_detector(BrokenDetector())
        hm.add_detector(health.NaNLossDetector())
        hm.observe(loss=float("nan"))  # must not raise, others still run
        assert hm.status() == health.UNHEALTHY
    finally:
        hm.close()


# -- exporter ----------------------------------------------------------------


def test_exporter_serves_all_endpoints(tmp_path):
    reg = obs.default_registry()
    reg.inc("exporter_test_total", 3)
    srv = exporter.OpsServer(port=0)
    host, port = srv.address
    base = f"http://{host}:{port}"
    try:
        code, text = _get(base + "/metrics")
        assert code == 200
        assert "lightctr_exporter_test_total 3" in text

        code, varz = _get(base + "/varz")
        assert code == 200
        assert varz["pid"] == os.getpid()
        assert "default" in varz["registries"]
        assert "status" in varz["health"]

        code, tracez = _get(base + "/tracez?n=5")
        assert code == 200
        assert isinstance(tracez["spans"], list)
        code, tracez = _get(base + "/tracez?n=0")
        assert code == 200 and tracez["spans"] == []  # not the whole ring

        code, body = _get(base + "/nope")
        assert code == 404

        # GET /flightz is not a trigger
        code, body = _get(base + "/flightz")
        assert code == 405

        # POST on an UNARMED process must refuse, not litter the cwd
        req = urllib.request.Request(base + "/flightz", data=b"",
                                     method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=5.0)
        assert ei.value.code == 409

        # POST /flightz writes a bundle into the armed flight dir
        flight.install(str(tmp_path), catch_signals=False)
        req = urllib.request.Request(base + "/flightz", data=b"",
                                     method="POST")
        with urllib.request.urlopen(req, timeout=5.0) as r:
            out = json.loads(r.read())
        assert os.path.exists(out["bundle"])
        recs = obs.read_jsonl(out["bundle"])
        assert recs[0]["reason"] == "ops:flightz"
    finally:
        flight.uninstall()
        srv.close()


def test_healthz_flips_503_on_unhealthy_component():
    srv = exporter.OpsServer(port=0)
    hm = _monitor(component="t_healthz", trip_after=1)
    base = "http://%s:%d" % srv.address
    try:
        hm.add_detector(health.NaNLossDetector())
        code, body = _get(base + "/healthz")
        assert code in (200, 503)  # other suites may share the process
        if code == 200:
            assert body["status"] in (health.OK, health.DEGRADED)
        hm.observe(loss=float("nan"))
        code, body = _get(base + "/healthz")
        assert code == 503
        assert body["status"] == health.UNHEALTHY
        comp = body["components"]["t_healthz"]
        assert comp["detectors"]["nan_loss"]["status"] == health.UNHEALTHY
    finally:
        hm.close()
        srv.close()
    # once the sick monitor is gone the aggregate recovers
    assert flight.health_verdicts().get("t_healthz") is None


def test_exporter_env_arming_and_telemetry_hard_disable(monkeypatch):
    exporter.uninstall()
    monkeypatch.setenv("LIGHTCTR_OPS_PORT", "0")
    with obs.override(False):
        exporter.maybe_install_from_env()
        assert exporter.installed() is None  # telemetry off wins
    exporter.maybe_install_from_env()
    srv = exporter.installed()
    try:
        assert srv is not None
        code, _ = _get("http://%s:%d/varz" % srv.address)
        assert code == 200
    finally:
        exporter.uninstall()
    monkeypatch.setenv("LIGHTCTR_OPS_PORT", "not-a-port")
    exporter.maybe_install_from_env()
    assert exporter.installed() is None


# -- flight integration ------------------------------------------------------


def test_concurrent_dumps_coalesce_not_interleave(tmp_path):
    """The shared re-entrancy guard: a dump triggered while another is
    mid-write returns None (counted) instead of queueing or interleaving."""
    before = flight.coalesced_dumps()
    with flight._dump_lock:  # simulate a dump in progress
        assert flight.dump("second", dir=str(tmp_path)) is None
    assert flight.coalesced_dumps() == before + 1
    # and with the lock free a dump succeeds again
    path = flight.dump("after", dir=str(tmp_path))
    assert path is not None and os.path.exists(path)


def test_coalesced_anomaly_dump_is_retried_until_it_lands(tmp_path):
    """An anomaly dump that coalesced with a dump already in progress is
    owed, not lost: later observations retry it while the verdict stays
    past the flight threshold."""
    t = [0.0]
    flight.install(str(tmp_path), catch_signals=False)
    hm = _monitor(component="t_retry", trip_after=1, clock=lambda: t[0])
    try:
        hm.add_detector(health.NaNLossDetector())
        with flight._dump_lock:  # a signal dump is mid-write
            hm.observe(loss=float("nan"))
        assert hm.status() == health.UNHEALTHY
        assert not list(tmp_path.glob("flight-*.jsonl"))
        t[0] = 2.0  # past the attempt backoff; no new transition needed
        hm.observe(loss=float("nan"))
        bundles = list(tmp_path.glob("flight-*.jsonl"))
        assert len(bundles) == 1
        assert obs.read_jsonl(str(bundles[0]))[0]["reason"] == \
            "health:t_retry:nan_loss"
        t[0] = 4.0  # the debt is paid: no further dumps
        hm.observe(loss=float("nan"))
        assert len(list(tmp_path.glob("flight-*.jsonl"))) == 1
    finally:
        hm.close()
        flight.uninstall()


def test_nan_loss_triggers_flight_dump_end_to_end(tmp_path):
    """Acceptance: a NaN loss flips the verdict within one recorded step
    and writes a flight bundle — which tools/trace_report.py --flight
    reads back with the health section naming the tripped detector."""
    import tools.trace_report as trace_report
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.models.ctr_trainer import CTRTrainer

    flight.install(str(tmp_path), catch_signals=False)
    hm = _monitor(component="t_anomaly", trip_after=2)
    health.ensure_trainer_detectors(hm)
    obs.configure_event_log()
    try:
        rng = np.random.default_rng(0)
        d = 8
        batch = {"x": rng.normal(size=(32, d)).astype(np.float32),
                 "labels": (rng.random(32) > 0.5).astype(np.float32)}
        tr = CTRTrainer({"w": np.zeros((d,), np.float32)},
                        lambda p, b: b["x"] @ p["w"],
                        TrainConfig(learning_rate=0.1))
        tr.health = hm
        for _ in range(3):
            tr.train_step(batch)
        tr.flush_health()
        assert hm.status() == health.OK
        assert not list(tmp_path.glob("flight-*.jsonl"))

        tr.train_step(dict(batch, labels=np.full(32, np.nan, np.float32)))
        tr.flush_health()  # drain the queued scalar without another step
        assert hm.status() == health.UNHEALTHY

        bundles = list(tmp_path.glob("flight-*.jsonl"))
        assert len(bundles) == 1  # rate-limited/coalesced, not spammed
        report = trace_report.summarize_flight(str(bundles[0]))
        assert report["reason"] == "health:t_anomaly:nan_loss"
        hsec = report["health"]["t_anomaly"]
        assert hsec["status"] == health.UNHEALTHY
        assert hsec["detectors"]["nan_loss"]["status"] == health.UNHEALTHY
        # the health events made it into the bundle's event ring too
        snap = hm.registry.snapshot()
        assert snap["counters"][obs.labeled(
            "health_flight_dumps_total", component="t_anomaly")] == 1
    finally:
        obs.configure_event_log()
        hm.close()
        flight.uninstall()


def test_metrics_report_health_summarizes_dir(tmp_path, capsys):
    import tools.metrics_report as metrics_report

    path = str(tmp_path / "events.jsonl")
    obs.configure_event_log(path=path, flush_every=1)
    hm = _monitor(component="t_report", trip_after=1, recover_after=1)
    try:
        hm.add_detector(health.StalenessDetector(slo=10))
        hm.observe(staleness=15)
        hm.observe(staleness=0)
    finally:
        obs.get_event_log().flush()
        obs.configure_event_log()
        hm.close()

    assert metrics_report.main(["--health", str(tmp_path)]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["transitions"] == 4  # degraded + ok, detector + aggregate
    assert report["final"]["t_report"]["status"] == health.OK
    assert report["final"]["t_report"]["detectors"]["staleness"] == health.OK
    first = report["timeline"][0]
    assert first["from"] == health.OK and first["to"] == health.DEGRADED
    # the plain summarize() integrates the same section
    recs = obs.read_jsonl(path)
    assert metrics_report.summarize(recs)["health"]["transitions"] == 4


# -- trainer table-skew feed -------------------------------------------------


def test_sparse_trainer_feeds_table_touch_and_flags_dead_table():
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.models import widedeep
    from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer
    import jax

    vocab, n_fields, dim, batch_n = 512, 4, 4, 32
    rng = np.random.default_rng(0)
    fids = rng.integers(0, vocab, size=(batch_n, n_fields)).astype(np.int32)
    fields = np.tile(np.arange(n_fields, dtype=np.int32), (batch_n, 1))
    mask = np.ones((batch_n, n_fields), np.float32)
    rep, rep_mask = widedeep.field_representatives(fids, fields, mask,
                                                   n_fields)
    batch = {
        "fids": fids, "fields": fields,
        "vals": np.ones((batch_n, n_fields), np.float32), "mask": mask,
        "labels": (rng.random(batch_n) > 0.5).astype(np.float32),
        "rep_fids": rep, "rep_mask": rep_mask,
    }
    params = widedeep.init(jax.random.PRNGKey(0), vocab, n_fields, dim)
    tr = SparseTableCTRTrainer(
        params, widedeep.logits, TrainConfig(learning_rate=0.05),
        sparse_tables={"w": ["fids"], "embed": ["rep_fids"]},
    )
    hm = _monitor(component="t_skew", trip_after=2)
    tr.health = hm
    health.ensure_trainer_detectors(hm, tables=True)
    try:
        for _ in range(3):
            tr.train_step(batch)
        tr.flush_health()
        assert hm.status() == health.OK

        # a dead feature pipeline: every id identical -> table_skew trips
        dead = dict(batch, fids=np.zeros_like(fids),
                    rep_fids=np.zeros_like(rep))
        for _ in range(2):  # trip_after=2
            tr.train_step(dead)
        tr.flush_health()  # the counts ride the step's health vector
        v = hm.verdict()
        assert v["detectors"]["table_skew"]["status"] == health.UNHEALTHY
        detail = v["detectors"]["table_skew"]["detail"]
        assert detail["w"]["why"] == "dead" and detail["w"]["unique"] == 1
    finally:
        hm.close()


# -- PS / cluster ------------------------------------------------------------


def test_stats_wire_op_carries_health_verdict_and_staleness_trips():
    from lightctr_tpu.dist.ps_server import ParamServerService, PSClient
    from lightctr_tpu.embed.async_ps import AsyncParamServer

    ps = AsyncParamServer(dim=2, n_workers=4, seed=0,
                          staleness_threshold=2)
    svc = ParamServerService(ps)
    client = PSClient(svc.address, 2)
    try:
        keys = np.arange(8, dtype=np.int64)
        g = np.ones((8, 2), np.float32)
        client.push_arrays(0, keys, g, worker_epoch=0)
        st = client.stats()
        assert st["health"]["status"] == health.OK
        assert "staleness" in st["health"]["detectors"]
        # drive the SSP ledger far past the SLO: worker 1 races ahead
        # while worker 0 stays at epoch 0 -> staleness > 2*slo
        for epoch in range(1, 12):
            client.push_arrays(1, keys, g, worker_epoch=epoch)
        client.push_arrays(0, keys, g, worker_epoch=0)
        client.push_arrays(0, keys, g, worker_epoch=0)
        st = client.stats()
        assert st["staleness"] > 4
        assert st["health"]["status"] == health.UNHEALTHY
        assert st["health"]["detectors"]["staleness"]["status"] == \
            health.UNHEALTHY
    finally:
        client.close()
        svc.close()
    assert flight.health_verdicts().get(svc._flight_name) is None


def test_cluster_health_degrades_on_down_shard_unhealthy_when_all_down():
    from lightctr_tpu.dist.ps_server import ParamServerService, ShardedPSClient
    from lightctr_tpu.embed.async_ps import AsyncParamServer

    svcs = [ParamServerService(AsyncParamServer(dim=2, n_workers=1, seed=i))
            for i in range(2)]
    client = ShardedPSClient([s.address for s in svcs], 2)
    try:
        ch = client.cluster_health()
        assert ch["status"] == health.OK and ch["down_shards"] == 0
        assert len(ch["shards"]) == 2
        assert all("detectors" in s for s in ch["shards"])

        svcs[1].close()  # one shard down: degraded, never a crash
        ch = client.cluster_health()
        assert ch["status"] == health.DEGRADED
        assert ch["down_shards"] == 1
        assert ch["shards"][1]["down"] is True

        svcs[0].close()  # whole cluster down: unhealthy
        ch = client.cluster_health()
        assert ch["status"] == health.UNHEALTHY
        assert ch["down_shards"] == 2
    finally:
        client.close()
        for s in svcs:
            s.close()


# -- heartbeat degraded stage ------------------------------------------------


def test_heartbeat_monitor_fires_on_stale_once_per_episode():
    from lightctr_tpu.dist.bootstrap import HeartbeatMonitor

    t = [0.0]
    events = []
    mon = HeartbeatMonitor(
        clock=lambda: t[0], stale_after_s=1.0, dead_after_s=3.0,
        on_stale=lambda w: events.append(("stale", w)),
        on_dead=lambda w: events.append(("dead", w)),
        on_recover=lambda w: events.append(("recover", w)),
        on_stale_clear=lambda w: events.append(("stale_clear", w)),
    )
    mon.beat("7")
    t[0] = 1.5
    assert mon.check()["7"] == "stale"
    mon.check()  # same episode: no second stale event
    assert events == [("stale", "7")]
    mon.beat("7")  # returning beat clears the stage AND notifies
    assert events == [("stale", "7"), ("stale_clear", "7")]
    t[0] = 2.0
    assert mon.check()["7"] == "alive"
    t[0] = 3.2  # second silence episode: a fresh stale event fires
    assert mon.check()["7"] == "stale"
    t[0] = 5.5
    assert mon.check()["7"] == "dead"  # death supersedes: no stale_clear
    assert events == [("stale", "7"), ("stale_clear", "7"),
                      ("stale", "7"), ("dead", "7")]
    assert mon.stale_workers() == set()
    mon.beat("7")
    assert events[-1] == ("recover", "7")


def test_master_marks_shard_degraded_before_dead(tmp_path):
    """The failover-hardening satellite: k missed heartbeats -> DEGRADED
    (counted + evented + master health degraded) BEFORE the dead line."""
    from lightctr_tpu.dist.master import SHARD_ID_BASE, MasterService
    from lightctr_tpu.dist.ps_server import ParamServerService, PSClient
    from lightctr_tpu.embed.async_ps import AsyncParamServer

    obs.configure_event_log()
    svc = ParamServerService(AsyncParamServer(dim=2, n_workers=1, seed=0))
    master = MasterService(
        [svc.address], period_s=0.05, degraded_after_missed=2,
        dead_after_s=0.6,
    )
    beat_client = PSClient(master.address, 1)
    try:
        assert master.monitor.stale_after_s == pytest.approx(0.1)
        beat_client.beat(SHARD_ID_BASE + 0)
        time.sleep(0.02)
        assert master.health.status() == health.OK
        # stop beating: degraded must precede dead
        deadline = time.monotonic() + 5.0
        while master.health.status() == health.OK \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        first = master.health.status()
        assert first == health.DEGRADED
        c = master.registry.snapshot()["counters"]
        assert c[obs.labeled("master_degraded_total", kind="shard")] >= 1
        assert "master_shard_deaths_total" not in c

        # a degraded shard that resumes beating WITHOUT dying recovers
        # the verdict (the stale_clear path — no binary cliff both ways)
        beat_client.beat(SHARD_ID_BASE + 0)
        while master.health.status() != health.OK \
                and time.monotonic() < deadline:
            time.sleep(0.01)
            beat_client.beat(SHARD_ID_BASE + 0)
        assert master.health.status() == health.OK
        assert "master_shard_deaths_total" not in \
            master.registry.snapshot()["counters"]

        # now fall silent for good: degraded again, then the dead line
        while master.health.status() != health.UNHEALTHY \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert master.health.status() == health.UNHEALTHY
        c = master.registry.snapshot()["counters"]
        assert c["master_shard_deaths_total"] >= 1

        actions = [r["action"] for r in obs.get_event_log().records()
                   if r["kind"] == "failover"]
        assert "shard_degraded" in actions and "shard_dead" in actions
        assert actions.index("shard_degraded") < actions.index("shard_dead")

        # the returning shard recovers the verdict
        beat_client.beat(SHARD_ID_BASE + 0)
        while master.health.status() != health.OK \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        assert master.health.status() == health.OK
    finally:
        beat_client.close()
        master.close()
        svc.close()
        obs.configure_event_log()


# -- 2-process acceptance ----------------------------------------------------


def test_two_process_ps_serves_metrics_and_healthz():
    """Acceptance: a 2-process PS run with LIGHTCTR_OPS_PORT set serves
    /metrics and /healthz on BOTH processes (port 0 auto-assign)."""
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
        from lightctr_tpu.obs import exporter
        ps = AsyncParamServer(dim=4, n_workers=2, seed=int(sys.argv[1]))
        svc = ParamServerService(ps)
        ops = exporter.installed()   # armed by LIGHTCTR_OPS_PORT at import
        assert ops is not None, "exporter did not arm from the env"
        print("ADDR", svc.address[0], svc.address[1],
              ops.address[0], ops.address[1], flush=True)
        sys.stdin.read()
        svc.close()
        """
    ) % str(LIB_ROOT.parent)
    env = dict(os.environ, JAX_PLATFORMS="cpu", LIGHTCTR_OPS_PORT="0")
    procs = [
        subprocess.Popen([sys.executable, "-c", server, str(i)],
                         stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                         text=True, env=env)
        for i in range(2)
    ]
    client = None
    try:
        addrs, ops_addrs = [], []
        for p in procs:
            line = p.stdout.readline().split()
            assert line[0] == "ADDR", line
            addrs.append((line[1], int(line[2])))
            ops_addrs.append((line[3], int(line[4])))
        client = ShardedPSClient(addrs, 4)
        keys = np.arange(100, dtype=np.int64)
        client.pull_arrays(keys, worker_epoch=0, worker_id=0)
        client.push_arrays(0, keys, np.ones((100, 4), np.float32),
                           worker_epoch=0)
        for host, port in ops_addrs:
            code, text = _get(f"http://{host}:{port}/metrics")
            assert code == 200
            # the shard's store registry is merged into the exposition
            assert 'lightctr_ps_requests_total{op="push"} 1' in text
            code, body = _get(f"http://{host}:{port}/healthz")
            assert code == 200
            assert body["status"] == health.OK
            assert any(c.startswith("ps_shard_")
                       for c in body["components"])
        # the wire-level verdict aggregation sees both shards too
        assert client.cluster_health()["status"] == health.OK
    finally:
        if client is not None:
            client.close()
        for p in procs:
            try:
                p.stdin.close()
            except OSError:
                pass
            p.wait(timeout=10)
