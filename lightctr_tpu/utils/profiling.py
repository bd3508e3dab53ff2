"""Profiling hooks.

Parity-plus for SURVEY.md §5 "tracing/profiling": the reference has wall-clock
counters (``clock_start``/``clock_cycles``, time.h:81-99) and DEBUG printf
tracing; on TPU the right tool is ``jax.profiler`` traces viewed in
Perfetto/TensorBoard.

``trace(dir)`` wraps a region (and emits a ``trace_capture`` event through
the obs event log so captures are discoverable from telemetry);
``wall_clock()`` reproduces the reference's train-wall-clock counter pair;
``annotate(name)`` tags a sub-region on EVERY timeline at once — the XLA
profiler's host track, the HLO metadata, and the obs span tracer
(obs/trace.py) — so a region carries the same name in a Perfetto device
trace and in a cross-process wire trace.
"""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Iterator, Optional

from lightctr_tpu.obs import events as _events
from lightctr_tpu.obs import trace as _trace

_LOG = logging.getLogger(__name__)


def profiler_available() -> "tuple[bool, str]":
    """Whether ``jax.profiler`` can be imported here: ``(ok, why)``.
    The device plane's ``POST /profilez`` checks this BEFORE arming so a
    capture request on a profiler-less worker is a clean 409, not a
    mid-step exception."""
    try:
        import jax

        profiler = jax.profiler
    except Exception as e:
        return False, f"jax.profiler unavailable: {e}"
    if not callable(getattr(profiler, "start_trace", None)):
        return False, "jax.profiler has no start_trace"
    return True, "ok"


@contextlib.contextmanager
def trace(log_dir: str, create_perfetto_link: bool = False) -> Iterator[None]:
    """jax.profiler trace around a region; view in TensorBoard/Perfetto.

    Emits a ``trace_capture`` event so the capture (and where it landed)
    shows up in the run's event log; degrades to a logged no-op when
    ``jax.profiler`` is unavailable — a CPU-only worker process asking for
    a profile must not crash, just not profile."""
    try:
        import jax

        profiler = jax.profiler
    except Exception:  # jax absent or profiler backend broken
        _LOG.warning(
            "jax.profiler unavailable: profiling.trace(%r) is a no-op",
            log_dir,
        )
        _events.emit("trace_capture", log_dir=str(log_dir),
                     perfetto_link=bool(create_perfetto_link),
                     unavailable=True)
        yield
        return
    _events.emit("trace_capture", log_dir=str(log_dir),
                 perfetto_link=bool(create_perfetto_link))
    try:
        profiler.start_trace(log_dir,
                             create_perfetto_link=create_perfetto_link)
    except Exception as e:
        # an importable profiler whose backend refuses to start (double
        # start, unsupported platform) degrades the same way as an absent
        # one: a logged no-op, never an exception into the caller's step
        _LOG.warning(
            "jax.profiler failed to start (%s): profiling.trace(%r) is a "
            "no-op", e, log_dir,
        )
        _events.emit("trace_capture", log_dir=str(log_dir),
                     perfetto_link=bool(create_perfetto_link),
                     unavailable=True, error=str(e))
        yield
        return
    try:
        yield
    finally:
        try:
            profiler.stop_trace()
        except Exception:
            _LOG.warning("jax.profiler failed to stop the trace",
                         exc_info=True)


class wall_clock:
    """clock_start/clock_cycles parity (time.h:81-99): seconds since start.
    As a context manager, the elapsed time freezes at block exit so a later
    ``cycles()`` reports the timed region, not everything since."""

    def __init__(self):
        self._t0: Optional[float] = None
        self._t1: Optional[float] = None

    def start(self) -> None:
        self._t0 = time.perf_counter()
        self._t1 = None

    def cycles(self) -> float:
        if self._t0 is None:
            raise RuntimeError("start() first")
        end = self._t1 if self._t1 is not None else time.perf_counter()
        return end - self._t0

    def __enter__(self) -> "wall_clock":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self._t1 = time.perf_counter()


@contextlib.contextmanager
def annotate(name: str, **attrs) -> Iterator[None]:
    """Named sub-region for traces: tags ALL timelines — the host timeline
    (``jax.profiler.TraceAnnotation``), the device/HLO metadata
    (``jax.named_scope``, so the region name survives into compiled-program
    profiles even though the body runs at trace time), and the obs span
    tracer (a span when tracing is sampled, ``attrs`` attached) — one name
    across XLA profiler traces and cross-process wire traces.

    No-op-safe: usable on CPU, inside ``jit`` tracing, and in processes
    where jax (or its profiler) is unavailable — instrumented library code
    must never crash because profiling isn't."""
    jstack = contextlib.ExitStack()
    try:
        import jax

        jstack.enter_context(jax.named_scope(name))
        jstack.enter_context(jax.profiler.TraceAnnotation(name))
    except Exception:
        # unwind whatever DID enter (a half-entered named_scope left open
        # would push jax's thread-local name stack one level forever)
        jstack.close()
        jstack = None
    try:
        with _trace.span(name, **attrs):
            yield
    finally:
        if jstack is not None:
            jstack.close()
