"""The one span layer (obs/trace.py): a root is taken by the sampling rate
OR while a JAX profiler session records; every span is then in the ring and
a ``TraceAnnotation`` on the profiler's host timeline; the training step,
the ingest producer and the scorer's loop carry spans at each boundary; and
``trace_report --stalls`` names what held the scorer thread."""

import glob
import json
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from lightctr_tpu import TrainConfig, obs, serve
from lightctr_tpu.data import ingest
from lightctr_tpu.models import fm, widedeep
from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer
from lightctr_tpu.obs import trace

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

TRAIN_SPANS = ("trainer/step", "trainer/input", "trainer/exec",
               "trainer/record")
INGEST_SPANS = ("ingest/produce", "ingest/put_wait", "ingest/get_wait")


@pytest.fixture
def clean_ring():
    trace.configure()
    with obs.override(True):
        yield
    trace.configure()


def _wd_batches(n_batches=4, vocab=512, n_fields=4, batch_n=32):
    rng = np.random.default_rng(0)
    out = []
    for _ in range(n_batches):
        fids = rng.integers(0, vocab, size=(batch_n, n_fields)).astype(np.int32)
        fields = np.tile(np.arange(n_fields, dtype=np.int32), (batch_n, 1))
        mask = np.ones((batch_n, n_fields), np.float32)
        rep, rep_mask = widedeep.field_representatives(fids, fields, mask,
                                                       n_fields)
        out.append({
            "fids": fids, "fields": fields,
            "vals": np.ones((batch_n, n_fields), np.float32), "mask": mask,
            "labels": (rng.random(batch_n) > 0.5).astype(np.float32),
            "rep_fids": rep, "rep_mask": rep_mask,
        })
    return out


def _wd_trainer(vocab=512, n_fields=4, dim=4):
    params = widedeep.init(jax.random.PRNGKey(0), vocab, n_fields, dim)
    return SparseTableCTRTrainer(
        params, widedeep.logits, TrainConfig(learning_rate=0.05),
        sparse_tables={"w": ["fids"], "embed": ["rep_fids"]},
    )


def _three_steps(tr, batches):
    stream = ingest.prefetch_batches(iter(batches), depth=2,
                                     registry=obs.MetricsRegistry())
    try:
        for _ in range(3):
            loss = tr.train_step(next(stream))
        jax.block_until_ready(loss)
    finally:
        stream.close()


# -- (a) a profiler session throws the switch --------------------------------


def test_profiler_session_puts_the_spans_in_the_ring_and_in_the_xplane(
        clean_ring, tmp_path):
    from benchmarks.harness import xplane

    tr = _wd_trainer()
    batches = _wd_batches()
    tr.train_step(batches[0])            # compiled before the session
    assert trace.finished() == []        # rate 0, no session: nothing
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert trace.enabled() and trace.profiling()
        _three_steps(tr, batches[1:])
    finally:
        jax.profiler.stop_trace()
    assert not trace.enabled()
    ring = trace.finished()
    names = {r["name"] for r in ring}
    assert set(TRAIN_SPANS + INGEST_SPANS) <= names
    steps = [r for r in ring if r["name"] == "trainer/step"]
    assert [r["attrs"]["step"] for r in steps] == [2, 3, 4]
    by_id = {r["span"]: r for r in ring}
    for r in ring:
        if r["name"] in TRAIN_SPANS[1:]:
            assert by_id[r["parent"]]["name"] == "trainer/step"
        if r["name"] in INGEST_SPANS + TRAIN_SPANS[:1]:
            assert "parent" not in r     # roots of their threads
    worker_tids = {r["tid"] for r in ring if r["name"] == "ingest/produce"}
    assert worker_tids and threading.get_ident() not in worker_tids

    # the same spans are host-plane events of the same names, and nest in
    # time as the ring's parents say
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    planes = xplane.read_planes(path, lambda p: p.startswith("/host:"),
                                lambda p, ln: True)
    events = {}      # name -> [(start, end)] in order of start
    for lines in planes.values():
        for evs in lines.values():
            for name, _, start, dur in evs:
                if name in names:
                    events.setdefault(name, []).append((start, start + dur))
    assert set(TRAIN_SPANS + INGEST_SPANS) <= set(events)
    for evs in events.values():
        evs.sort()
    order = {}       # ring record -> its event: k-th of its name, by start
    for name in TRAIN_SPANS:
        recs = sorted((r for r in ring if r["name"] == name),
                      key=lambda r: r["start_ns"])
        assert len(recs) == len(events[name]) == 3
        for r, ev in zip(recs, events[name]):
            order[r["span"]] = ev
    for sid, (start, end) in order.items():
        parent = by_id[sid].get("parent")
        if parent is not None:
            p_start, p_end = order[parent]
            assert p_start <= start and end <= p_end
    assert len(events["ingest/produce"]) == \
        sum(r["name"] == "ingest/produce" for r in ring)


def test_without_a_session_at_rate_zero_nothing_is_recorded(clean_ring):
    assert not trace.enabled() and not trace.profiling()
    assert trace.span("anything", k=1) is trace.span("else") is trace._NULL
    tr = _wd_trainer()
    _three_steps(tr, _wd_batches()[:3])
    assert trace.finished() == []
    assert trace._ctx.stack == []


def test_gate_off_wins_over_a_recording_session(clean_ring, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with obs.override(False):
            assert trace.span("gated") is trace._NULL
        with trace.span("open"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert [r["name"] for r in trace.finished()] == ["open"]


def test_children_keep_the_roots_decision_when_the_session_ends(
        clean_ring, tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    with trace.span("root"):
        jax.profiler.stop_trace()
        with trace.span("child"):        # session over, rate 0: inherited
            pass
    with trace.span("later-root"):
        pass
    assert [r["name"] for r in trace.finished()] == ["child", "root"]


# -- the record ----------------------------------------------------------------


def test_a_record_keeps_todays_keys_and_adds_the_ns_clock(clean_ring):
    with trace.override_rate(1.0):
        t0 = time.time_ns()
        with pytest.raises(KeyError):
            with trace.span("outer", step=3) as sp:
                sp.set(rows=7)
                with trace.span("inner"):
                    time.sleep(0.01)
                raise KeyError("x")
    inner, outer = trace.finished()
    assert set(outer) == {"kind", "v", "trace", "span", "name", "ts", "pid",
                          "tid", "attrs", "dur_s", "start_ns", "end_ns",
                          "error"}
    assert set(inner) == set(outer) - {"attrs", "error"} | {"parent"}
    assert outer["attrs"] == {"step": 3, "rows": 7}
    assert outer["error"] == "KeyError" and outer["pid"] == os.getpid()
    assert inner["parent"] == outer["span"] and len(outer["span"]) == 16
    assert t0 <= outer["start_ns"] <= inner["start_ns"]
    assert inner["end_ns"] <= outer["end_ns"] <= time.time_ns()
    assert inner["end_ns"] - inner["start_ns"] >= 10_000_000
    for r in (inner, outer):
        assert r["dur_s"] == pytest.approx(
            (r["end_ns"] - r["start_ns"]) / 1e9, abs=1e-9)
        assert r["ts"] == pytest.approx(r["start_ns"] / 1e9, abs=1e-6)
    json.dumps(trace.finished())


def test_record_keeps_an_interval_that_two_threads_share(clean_ring):
    got = {}

    def owner():
        with trace.span("request"):
            got["ctx"], got["t0"] = trace.current_context(), time.time_ns()

    with trace.override_rate(1.0):
        t = threading.Thread(target=owner)
        t.start()
        t.join()
        trace.record("wait", got["t0"], got["t0"] + 5_000_000, got["ctx"],
                     batch="b1")
        trace.record("dropped", 1, 2, None)      # no recorded trace there
    req, wait = trace.finished()
    assert wait["name"] == "wait" and wait["parent"] == req["span"]
    assert wait["trace"] == req["trace"] and wait["attrs"] == {"batch": "b1"}
    assert wait["dur_s"] == pytest.approx(0.005)
    assert wait["tid"] == threading.get_ident() != req["tid"]


def test_obs_trace_takes_no_jax_into_a_process_that_has_none():
    """PS shards, the master and load generators are JAX-free: the span
    layer records there without loading it (and finds no annotation)."""
    code = (
        "import sys, importlib.util\n"
        "import types\n"
        "pkg = types.ModuleType('lightctr_tpu'); pkg.__path__ = [%r]\n"
        "sys.modules['lightctr_tpu'] = pkg\n"
        "obs = types.ModuleType('lightctr_tpu.obs'); obs.__path__ = [%r]\n"
        "sys.modules['lightctr_tpu.obs'] = obs\n"
        "from lightctr_tpu.obs import trace\n"
        "trace.set_rate(1.0)\n"
        "with trace.span('a'):\n"
        "    pass\n"
        "assert [r['name'] for r in trace.finished()] == ['a']\n"
        "assert not trace.profiling() and 'jax' not in sys.modules\n"
        "print('ok')\n"
    ) % (os.path.join(REPO_ROOT, "lightctr_tpu"),
         os.path.join(REPO_ROOT, "lightctr_tpu", "obs"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_the_trainer_step_has_one_instrumented_body():
    from lightctr_tpu.models.ctr_trainer import CTRTrainer

    assert not hasattr(CTRTrainer, "_train_step_traced")


def test_health_fetch_is_a_child_of_record(clean_ring):
    from lightctr_tpu.models.ctr_trainer import CTRTrainer
    from lightctr_tpu.obs import health

    rng = np.random.default_rng(0)
    batch = {"x": rng.normal(size=(32, 8)).astype(np.float32),
             "labels": (rng.random(32) > 0.5).astype(np.float32)}
    tr = CTRTrainer({"w": np.zeros((8,), np.float32)},
                    lambda p, b: b["x"] @ p["w"], TrainConfig(learning_rate=0.1))
    hm = health.HealthMonitor(component="span_layer_t")
    health.ensure_trainer_detectors(hm)
    tr.health = hm
    try:
        with trace.override_rate(1.0), health.override(True):
            for _ in range(tr._HEALTH_MAX_LAG + 2):
                tr.train_step(batch)
            tr.flush_health()
    finally:
        hm.close()
    ring = trace.finished()
    by_id = {r["span"]: r for r in ring}
    fetches = [r for r in ring if r["name"] == "trainer/health_fetch"]
    assert fetches
    under = {by_id[r["parent"]]["name"] for r in fetches if "parent" in r}
    assert under == {"trainer/record"}
    observes = [r for r in ring if r["name"] == "health/observe"]
    assert observes and all(
        by_id[r["parent"]]["name"] == "trainer/record"
        for r in observes if "parent" in r)


# -- (b) the scorer ------------------------------------------------------------

F, K = 256, 8
REQUEST_SPANS = ("serve/decode", "serve/queue_wait", "serve/reply")
CYCLE_SPANS = ("serve/collect_idle", "serve/collect_fill", "serve/batch")
BATCH_CHILDREN = ("serve/telemetry", "serve/shed_scan", "serve/concat",
                  "serve/score", "serve/scatter")


def _requests(srv, n_requests, n_threads=4):
    answered = []

    def one(i):
        cli = serve.PredictClient(srv.address)
        rng = np.random.default_rng(i)
        try:
            for _ in range(n_requests // n_threads):
                rows = int(rng.integers(1, 5))
                cli.predict({
                    "fids": rng.integers(1, F, size=(rows, 4)).astype(np.int32),
                    "vals": np.ones((rows, 4), np.float32)})
                answered.append(i)
        finally:
            cli.close()

    ts = [threading.Thread(target=one, args=(i,)) for i in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return len(answered)


@pytest.fixture
def server(clean_ring):
    model = serve.ServingModel("fm", fm.init(jax.random.PRNGKey(2), F, K))
    for rows in (1, 2, 4, 8, 16, 32):    # every padded shape, compiled now
        model.score({"fids": np.ones((rows, 4), np.int32),
                     "vals": np.ones((rows, 4), np.float32)})
    srv = serve.PredictionServer(
        model, max_batch=32, max_wait_us=2000, deadline_ms=60_000,
        slo_feed_every=2)
    try:
        yield srv
    finally:
        srv.close()


def _self_time(ring):
    own = {r["span"]: r["end_ns"] - r["start_ns"] for r in ring}
    for r in ring:
        if r.get("parent") in own and r["name"] != "serve/queue_wait":
            own[r["parent"]] -= r["end_ns"] - r["start_ns"]
    return own


def test_every_answered_request_and_every_cycle_of_the_scorer_has_its_spans(
        server):
    trace.configure(capacity=65536)
    with trace.override_rate(1.0):
        answered = _requests(server, 24)
        time.sleep(0.05)                 # the last cycle's tail spans
    ring = trace.finished()
    by_id = {r["span"]: r for r in ring}
    frames = [r for r in ring if r["name"] == "serve/predict_batch"
              or r["name"] == "serve/predict"]
    assert len(frames) == answered == 24
    score_of_batch = {r["parent"]: r for r in ring if r["name"] == "serve/score"}
    for frame in frames:
        kids = {r["name"]: r for r in ring if r.get("parent") == frame["span"]}
        assert set(REQUEST_SPANS) <= set(kids)
        assert {r["trace"] for r in kids.values()} == {frame["trace"]}
        # the client's span is the frame's parent: one trace, wire to reply
        assert by_id[frame["parent"]]["name"] == "serve_client/predict"
        wait = kids["serve/queue_wait"]
        batch = by_id[wait["attrs"]["batch"]]
        assert batch["name"] == "serve/batch"
        assert wait["end_ns"] <= score_of_batch[batch["span"]]["start_ns"]
        assert kids["serve/decode"]["end_ns"] <= wait["start_ns"] + 1_000_000
        assert wait["tid"] == batch["tid"] != frame["tid"]
    batches = [r for r in ring if r["name"] == "serve/batch"]
    assert sum(b["attrs"]["requests"] for b in batches) == 24
    for b in batches:
        assert b["attrs"]["rows"] <= b["attrs"]["padded_rows"] <= 32
        kids = [r["name"] for r in ring if r.get("parent") == b["span"]
                and r["name"] != "serve/queue_wait"]
        assert set(BATCH_CHILDREN) <= set(kids)
        assert "parent" not in b
    assert any(r["name"] == "serve/feed_slo" for r in ring)
    # the scorer thread's spans: roots follow one another, children nest
    (scorer,) = {b["tid"] for b in batches}
    mine = sorted((r for r in ring if r["tid"] == scorer
                   and r["name"] != "serve/queue_wait"),
                  key=lambda r: r["start_ns"])
    assert {r["name"] for r in mine if "parent" not in r} == set(CYCLE_SPANS)
    roots = [r for r in mine if "parent" not in r]
    for a, b in zip(roots, roots[1:]):
        assert a["end_ns"] <= b["start_ns"]
    for r in mine:
        if "parent" in r:
            p = by_id[r["parent"]]
            assert p["start_ns"] <= r["start_ns"] and r["end_ns"] <= p["end_ns"]
            sibs = [s for s in mine if s.get("parent") == r["parent"]]
            for a, b in zip(sibs, sibs[1:]):
                assert a["end_ns"] <= b["start_ns"]


def test_a_planted_stall_in_feed_slo_is_its_spans_self_time_and_named_by_stalls(
        server, tmp_path, monkeypatch):
    from tools import trace_report

    real = server._feed_slo
    planted = []

    def slow():
        if not planted:
            planted.append(time.time_ns())
            time.sleep(0.1)
        real()

    monkeypatch.setattr(server, "_feed_slo", slow)
    trace.configure(path=str(tmp_path / "trace-serve.jsonl"), capacity=65536,
                    flush_every=1)
    with trace.override_rate(1.0):
        _requests(server, 48)
        time.sleep(0.05)
    trace.flush()
    ring = trace.finished()
    own = _self_time(ring)
    slo = max((r for r in ring if r["name"] == "serve/feed_slo"),
              key=lambda r: own[r["span"]])
    assert own[slo["span"]] >= 95_000_000
    assert slo["start_ns"] <= planted[0] + 1_000_000
    # the requests that arrived meanwhile waited, and the report says for what
    report = trace_report.summarize_stalls(
        trace_report.load_spans([str(tmp_path / "trace-serve.jsonl")]), 60.0)
    assert report["stalls"] >= 1 and report["waits"] == 48
    assert next(iter(report["held_by"])) == "serve/feed_slo"
    worst = report["worst"][0]
    assert worst["held_by"] == "serve/feed_slo"
    assert worst["inside_ms"]["serve/feed_slo"] >= 0.5 * worst["wait_ms"]
    assert worst["longest_spans"][0]["name"] in ("serve/batch", "serve/feed_slo")
    out = subprocess.run(
        [sys.executable, "-m", "tools.trace_report",
         str(tmp_path / "trace-serve.jsonl"), "--stalls", "60"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr
    assert json.loads(out.stdout)["held_by"] == report["held_by"]


def test_stalls_count_what_no_span_covers():
    from tools import trace_report

    def rec(name, span, start_ms, end_ms, tid, parent=None, **attrs):
        r = {"kind": "span", "name": name, "span": span, "trace": "t",
             "pid": 1, "tid": tid, "start_ns": int(start_ms * 1e6),
             "end_ns": int(end_ms * 1e6), "ts": start_ms / 1e3,
             "dur_s": (end_ms - start_ms) / 1e3}
        if parent:
            r["parent"] = parent
        if attrs:
            r["attrs"] = attrs
        return r

    spans = [
        rec("serve/batch", "b1", 0, 30, tid=7),
        rec("serve/score", "s1", 5, 25, tid=7, parent="b1"),
        rec("serve/collect_idle", "c1", 80, 90, tid=7),
        rec("serve/predict", "p1", 0, 120, tid=9),      # another thread
        rec("serve/queue_wait", "w1", 10, 100, tid=7, parent="p1", batch="b2"),
        rec("serve/queue_wait", "w0", 0, 4, tid=7, parent="p1", batch="b1"),
    ]
    report = trace_report.summarize_stalls(spans, 50.0)
    assert report["waits"] == 2 and report["stalls"] == 1
    (stall,) = report["worst"]
    assert stall["wait_ms"] == 90.0
    assert stall["inside_ms"] == {"(no span)": 60.0, "serve/score": 15.0,
                                  "serve/collect_idle": 10.0,
                                  "serve/batch": 5.0}
    assert stall["held_by"] == "(no span)" and report["held_by"] == {"(no span)": 1}
