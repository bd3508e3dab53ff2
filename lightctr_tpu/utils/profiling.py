"""Profiling hooks.

Parity-plus for SURVEY.md §5 "tracing/profiling": the reference has wall-clock
counters (``clock_start``/``clock_cycles``, time.h:81-99) and DEBUG printf
tracing; on TPU the right tool is ``jax.profiler`` traces viewed in
Perfetto/TensorBoard.

``trace(dir)`` wraps a region in a profiler session (and emits a
``trace_capture`` event through the obs event log so captures are
discoverable from telemetry); while it records, every ``obs.trace.span``
of the program is an event on the profiler's host timeline, beside the
device's ops.  ``annotate(name)`` is ``obs.trace.span`` plus
``jax.named_scope``, for the regions whose body runs while ``jit`` traces
(the name then reaches the HLO metadata too).
"""

from __future__ import annotations

import contextlib
import logging
from typing import Iterator

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


@contextlib.contextmanager
def annotate(name: str, **attrs) -> Iterator[None]:
    """Named sub-region for code that (also) runs while ``jit`` traces:
    ``jax.named_scope`` puts the name into the HLO metadata, so it
    survives into compiled-program profiles as the device ops' scope even
    though the body runs at trace time; around it ``obs.trace.span`` does
    what it does for every span (the ring, and a ``TraceAnnotation`` on
    the profiler's host timeline while a session records).  Host-only
    regions call ``obs.trace.span`` directly.

    No-op-safe: usable on CPU, inside ``jit`` tracing, and in processes
    where jax is unavailable — instrumented library code must never crash
    because profiling isn't."""
    try:
        import jax

        scope = jax.named_scope(name)
    except Exception:
        scope = contextlib.nullcontext()
    with _trace.span(name, **attrs), scope:
        yield
