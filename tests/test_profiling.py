"""utils/profiling: no-op-safe annotate (obs.trace.span + named_scope) and
trace()'s trace_capture event (tests/test_span_layer.py holds what a
recording session does to spans)."""

import jax
import jax.numpy as jnp
import numpy as np

from lightctr_tpu import obs
from lightctr_tpu.obs import trace as obs_trace
from lightctr_tpu.utils import profiling
from lightctr_tpu.utils.profiling import annotate


def test_annotate_is_noop_safe_on_cpu():
    with annotate("region"):
        x = 1 + 1
    assert x == 2


def test_annotate_inside_jit_preserves_result():
    def f(x):
        with annotate("gather"):
            y = x * 2.0
        with annotate("apply"):
            return y + 1.0

    out = jax.jit(f)(jnp.float32(3.0))
    np.testing.assert_allclose(np.asarray(out), 7.0)


def test_annotate_nested():
    with annotate("outer"):
        with annotate("inner"):
            pass  # nesting must not raise (named_scope stacks)


def test_annotate_emits_spans_when_tracing_sampled():
    """annotate is the one-name-everywhere hook: when tracing is sampled
    it opens an obs span under the same name (wire trace == XLA trace)."""
    obs_trace.reset()
    with obs.override(True), obs_trace.override_rate(1.0):
        with annotate("phase/outer", step=3):
            with annotate("phase/inner"):
                pass
    spans = {s["name"]: s for s in obs_trace.finished()}
    assert set(spans) == {"phase/outer", "phase/inner"}
    assert spans["phase/inner"]["parent"] == spans["phase/outer"]["span"]
    assert spans["phase/outer"]["attrs"] == {"step": 3}
    obs_trace.reset()


def test_trace_emits_trace_capture_event(tmp_path):
    """Satellite: a profiler capture announces itself through the event
    log, so telemetry consumers can FIND the capture artifacts."""
    obs.configure_event_log()
    try:
        with obs.override(True):
            with profiling.trace(str(tmp_path / "profile"),
                                 create_perfetto_link=False):
                pass
        recs = [r for r in obs.get_event_log().records()
                if r["kind"] == "trace_capture"]
        assert len(recs) == 1
        assert recs[0]["log_dir"].endswith("profile")
        assert recs[0]["perfetto_link"] is False
    finally:
        obs.configure_event_log()


def test_trace_degrades_to_noop_without_jax_profiler(tmp_path, monkeypatch,
                                                     caplog):
    """Satellite: jax.profiler unavailable -> logged warning + no-op, and
    the trace_capture event records the degradation."""
    import builtins
    import logging

    real_import = builtins.__import__

    def no_jax(name, *a, **k):
        if name == "jax":
            raise ImportError("no jax here")
        return real_import(name, *a, **k)

    monkeypatch.setattr(builtins, "__import__", no_jax)
    obs.configure_event_log()
    try:
        with obs.override(True), caplog.at_level(
                logging.WARNING, logger="lightctr_tpu.utils.profiling"):
            with profiling.trace(str(tmp_path / "p")):
                ran = True
        assert ran
        assert any("no-op" in r.message for r in caplog.records)
        recs = [r for r in obs.get_event_log().records()
                if r["kind"] == "trace_capture"]
        assert recs and recs[0]["unavailable"] is True
    finally:
        obs.configure_event_log()


def test_trace_degrades_when_start_trace_refuses(tmp_path, monkeypatch,
                                                 caplog):
    """Satellite (device plane): an IMPORTABLE profiler whose backend
    refuses to start (double-start, unsupported platform) degrades the
    same way as an absent one — logged no-op, degradation recorded on
    the trace_capture event, no exception into the caller's step."""
    import logging

    def refuse(*a, **k):
        raise RuntimeError("already profiling")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    obs.configure_event_log()
    try:
        with obs.override(True), caplog.at_level(
                logging.WARNING, logger="lightctr_tpu.utils.profiling"):
            with profiling.trace(str(tmp_path / "p")):
                ran = True
        assert ran
        assert any("no-op" in r.message for r in caplog.records)
        recs = [r for r in obs.get_event_log().records()
                if r["kind"] == "trace_capture"]
        degraded = [r for r in recs if r.get("unavailable")]
        assert degraded and "already profiling" in degraded[0]["error"]
    finally:
        obs.configure_event_log()


def test_profiler_available_contract(monkeypatch):
    """profiler_available() is what POST /profilez checks before arming:
    (True, 'ok') with a working jax.profiler, (False, why) without —
    the refusal path must name its reason, never raise."""
    ok, why = profiling.profiler_available()
    assert ok is True and why == "ok"
    monkeypatch.setattr(jax.profiler, "start_trace", None)
    ok, why = profiling.profiler_available()
    assert ok is False and "start_trace" in why
