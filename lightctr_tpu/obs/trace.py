"""Causal span tracer: end-to-end timelines from trainer step to PS shard.

The registry (obs/registry.py) answers *how much*; this module answers
*why a step was slow*: every instrumented region is a **span** — a named
interval with a ``trace_id`` (one per causal tree), a ``span_id``, and a
``parent_id`` — so a trainer step, the PS client RPC it issued, and the
server-side handler that served it line up as one tree even across
process boundaries (the client sends its current context as a varint
trace header on the PS wire, ``dist.wire.pack_trace_ctx``).

:func:`span` is the ONE way the program opens a span, and it has two
sinks: the in-memory ring below, and — where ``jax`` is loaded and a
profiler session is recording — a ``jax.profiler.TraceAnnotation`` of the
same name, so the span is an event on the xplane's host plane, on the
clock the device's ops are on.

Design points:

  - **Off by default, one-branch cheap.**  A root span is taken when the
    obs gate is on AND either a sampling rate > 0 is set
    (``LIGHTCTR_TRACE`` env or :func:`set_rate`) or a JAX profiler session
    is recording (``TraceAnnotation.is_enabled()``: one call at the root;
    ``jax.profiler.start_trace``, ``POST /profilez`` and
    ``profiling.trace(dir)`` all throw it).  Otherwise :func:`span`
    returns a shared ``nullcontext`` — no allocation, no lock, no
    annotation entered — which is what the tier-1 overhead guard measures.
  - **The decision is per-trace.**  The head (root span) decides once;
    children and remote continuations inherit the decision through the
    thread-local stack, so a recorded trace is always complete and an
    unsampled one costs nothing but the roll.
  - **This module never imports jax.**  PS shards, the master and load
    generators are JAX-free processes: ``TraceAnnotation`` is looked up
    once, and only where ``jax`` is already in ``sys.modules``; without it
    the ring alone is written.
  - **A record is a tuple until somebody reads it.**  Integer ids, one
    ``time_ns`` and one ``perf_counter_ns`` at entry, one at exit; hex
    ids, ``round()`` and ``pid`` are made by :func:`finished`, the JSONL
    sink (``LIGHTCTR_TRACE_DIR`` or :func:`configure`) and the flight
    bundle (obs/flight.py).
  - **Timestamps are wall-clock, durations are monotonic.**
    ``start_ns`` / ``ts`` is ``time.time_ns()`` (the clock processes — and
    the profiler — share); ``end_ns`` is ``start_ns`` plus a
    ``perf_counter_ns`` delta, ``dur_s`` that delta in seconds.

``tools/trace_report.py`` summarizes span files (and flight bundles),
names what a thread was inside during a long wait (``--stalls``) and
exports Chrome-trace/Perfetto JSON.  See docs/OBSERVABILITY.md.
"""

from __future__ import annotations

import collections
import contextlib
import os
import random
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from lightctr_tpu.obs import gate
from lightctr_tpu.obs.events import EventLog

SPAN_SCHEMA_VERSION = 1

#: ids are 63-bit so they survive the zigzag-varint int64 wire codec
_ID_BITS = 63


def _parse_rate(val: Optional[str]) -> float:
    """``LIGHTCTR_TRACE`` -> sampling rate: unset/0/off -> 0.0 (no root
    is sampled), ``1`` -> every trace, a float in (0, 1] -> head sampling."""
    if not val:
        return 0.0
    v = val.strip().lower()
    if v in ("0", "false", "off", "no", ""):
        return 0.0
    if v in ("1", "true", "on", "yes"):
        return 1.0
    try:
        rate = float(v)
    except ValueError:
        return 0.0
    return min(1.0, max(0.0, rate))


_rate: float = _parse_rate(os.environ.get("LIGHTCTR_TRACE"))
_lock = threading.Lock()
_ring: collections.deque = collections.deque(maxlen=4096)
_sink: Optional[EventLog] = None


class _Ctx(threading.local):
    """Per-thread span stack: entries are (trace_id, span_id) tuples for
    live recorded spans, or ``None`` for an unsampled trace head (so the
    whole subtree below it skips without re-rolling)."""

    def __init__(self):
        self.stack: list = []


_ctx = _Ctx()
_NULL = contextlib.nullcontext()

#: ``jax.profiler.TraceAnnotation`` once found (never imported from here)
_annotation = None


def _find_annotation():
    """``TraceAnnotation`` where jax is ALREADY loaded, else None (and the
    next root looks again: a server may load jax after its first frame)."""
    global _annotation
    jax = sys.modules.get("jax")
    ta = getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)
    if ta is not None and callable(getattr(ta, "is_enabled", None)):
        _annotation = ta
    return _annotation


def profiling() -> bool:
    """True while a JAX profiler session is recording in this process."""
    ta = _annotation or _find_annotation()
    return ta is not None and ta.is_enabled()


def _new_id() -> int:
    return random.getrandbits(_ID_BITS) or 1


def enabled() -> bool:
    """True when NEW root spans may start in this process: obs gate on and
    either a sampling rate > 0 or a recording profiler session.  Remote
    continuations only need the gate."""
    return gate.enabled() and (_rate > 0.0 or profiling())


def set_rate(rate: float) -> float:
    """Set the head-sampling rate; returns the PREVIOUS rate."""
    global _rate
    prev = _rate
    _rate = min(1.0, max(0.0, float(rate)))
    return prev


@contextlib.contextmanager
def override_rate(rate: float):
    """Scoped sampling-rate override (tests, targeted captures)."""
    prev = set_rate(rate)
    try:
        yield
    finally:
        set_rate(prev)


def current_context() -> Optional[Tuple[int, int]]:
    """(trace_id, span_id) of the innermost live recorded span on THIS
    thread, or None — the tuple a client packs into the wire trace
    header, and the ``parent`` :func:`record` takes.  Gate-checked so a
    disabled process never leaks context."""
    stack = _ctx.stack
    if not stack or not gate.enabled():
        return None
    return stack[-1]  # may be None: unsampled head marker


class _Unsampled:
    """An unsampled trace head: marks the stack so that the subtree below
    skips without another roll.  Shared; holds nothing."""

    __slots__ = ()

    def __enter__(self):
        _ctx.stack.append(None)
        return None  # like the null context: ``as sp`` is a span or None

    def __exit__(self, exc_type, exc, tb):
        _ctx.stack.pop()
        return False


_UNSAMPLED = _Unsampled()


def _keep(rec: tuple) -> None:
    with _lock:
        _ring.append(rec)
        sink = _sink
    if sink is not None:
        # outside the module lock: EventLog has its own lock, and its
        # periodic file flush must not serialize every thread's span
        # exits (PS connection threads all finish spans concurrently)
        fields = _as_dict(rec)
        del fields["kind"]
        sink.emit("span", **fields)


class _SpanCM:
    """Context manager for one recorded span.  Never raises."""

    __slots__ = ("_name", "_attrs", "_trace", "_parent", "_span", "_ta",
                 "_wall", "_t0")

    def __init__(self, name: str, trace_id: int, parent: int, attrs):
        self._name = name
        self._attrs = attrs
        self._trace = trace_id
        self._parent = parent

    def __enter__(self):
        self._span = span_id = _new_id()
        _ctx.stack.append((self._trace, span_id))
        ta = _annotation or _find_annotation()
        if ta is not None and ta.is_enabled():
            self._ta = ta = ta(self._name)
            ta.__enter__()
        else:
            self._ta = None
        self._wall = time.time_ns()
        self._t0 = time.perf_counter_ns()
        return self

    @property
    def span_id(self) -> int:
        return self._span

    def set(self, **attrs) -> None:
        """Add attributes known only once the span is under way."""
        self._attrs.update(attrs)

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter_ns()
        if self._ta is not None:
            self._ta.__exit__(exc_type, exc, tb)
        _ctx.stack.pop()
        _keep((self._name, self._trace, self._span, self._parent,
               self._wall, self._wall + t1 - self._t0,
               threading.get_ident(), self._attrs,
               exc_type.__name__ if exc_type is not None else None))
        return False


def span(name: str, remote: Optional[Tuple[int, int]] = None, **attrs):
    """Span context manager.

    ``remote=(trace_id, parent_span_id)`` continues a trace started in
    ANOTHER process (the server side of the wire trace header): the
    sender already made the sampling decision, so only the obs gate is
    checked.  Without ``remote``, a root span is taken while a profiler
    session records, else it rolls the sampling dice; children inherit
    the parent's decision — including children of a remote continuation
    in a process whose OWN rate is 0 (a PS server without LIGHTCTR_TRACE
    still records the full subtree under a traced request; the rate only
    gates NEW roots).

    Returns a shared nullcontext when nothing is recorded — the off path
    is a thread-local stack peek, one rate comparison and (where jax is
    loaded) one ``is_enabled()`` call."""
    if remote is not None:
        if not gate.enabled():
            return _NULL
        return _SpanCM(name, remote[0], remote[1], attrs)
    stack = _ctx.stack
    if stack:
        # a live parent carries the inherited decision: record (or skip)
        # with it, independent of this process's head rate
        top = stack[-1]
        if top is None or not gate.enabled():
            return _NULL
        return _SpanCM(name, top[0], top[1], attrs)
    if not gate.enabled():
        return _NULL
    # a root: while a profiler session records (``profiling()``, inlined:
    # this is the off path of every instrumented boundary), else by rate
    ta = _annotation or _find_annotation()
    if ta is None or not ta.is_enabled():
        if _rate <= 0.0:
            return _NULL
        if _rate < 1.0 and random.random() >= _rate:
            return _UNSAMPLED
    return _SpanCM(name, _new_id(), 0, attrs)


def record(name: str, start_ns: int, end_ns: int,
           parent: Optional[Tuple[int, int]], **attrs) -> None:
    """Record an interval that starts on one thread and ends on another
    (a request's wait in a queue): ``start_ns`` / ``end_ns`` are
    ``time.time_ns()`` readings, ``parent`` the :func:`current_context`
    of the span it belongs under, taken on the thread that owns it — None
    (no recorded trace there) records nothing.  Ring and sink only: an
    annotation cannot be back-dated onto the profiler's timeline."""
    if parent is None or not gate.enabled():
        return
    _keep((name, parent[0], _new_id(), parent[1], int(start_ns),
           int(end_ns), threading.get_ident(), attrs, None))


def _as_dict(rec: tuple) -> Dict:
    """The record as readers get it (``finished``, sink, flight bundle)."""
    name, trace_id, span_id, parent, start_ns, end_ns, tid, attrs, err = rec
    out = {
        "kind": "span",
        "v": SPAN_SCHEMA_VERSION,
        "trace": f"{trace_id:016x}",
        "span": f"{span_id:016x}",
        "name": name,
        "ts": round(start_ns / 1e9, 6),
        "pid": os.getpid(),
        "tid": tid,
    }
    if parent:
        out["parent"] = f"{parent:016x}"
    if attrs:
        out["attrs"] = attrs
    out["dur_s"] = round((end_ns - start_ns) / 1e9, 9)
    out["start_ns"] = start_ns
    out["end_ns"] = end_ns
    if err is not None:
        out["error"] = err
    return out


# -- ring / sink management --------------------------------------------------


def finished() -> List[Dict]:
    """The bounded ring of finished span records, oldest first (by END:
    a parent follows its children)."""
    with _lock:
        recs = list(_ring)
    return [_as_dict(r) for r in recs]


def reset() -> None:
    """Drop all buffered spans (tests)."""
    with _lock:
        _ring.clear()


def configure(
    path: Optional[str] = None,
    capacity: int = 4096,
    flush_every: int = 16,
) -> None:
    """(Re)configure the span ring size and the JSONL file sink, starting
    a FRESH ring (spans from a previous configuration never leak into the
    next capture or flight bundle).  With a ``path``, finished spans
    stream to it through an EventLog (appended, flushed every
    ``flush_every`` spans and at exit).  ``configure()`` with no
    arguments drops the sink and resets the ring."""
    global _sink, _ring
    with _lock:
        if _sink is not None:
            _sink.close()
        _sink = (
            EventLog(path=path, capacity=capacity, flush_every=flush_every)
            if path is not None else None
        )
        _ring = collections.deque(maxlen=int(capacity))


def flush() -> None:
    """Flush the file sink (no-op without one)."""
    with _lock:
        sink = _sink
    if sink is not None:
        sink.flush()


def sink_path() -> Optional[str]:
    with _lock:
        return _sink.path if _sink is not None else None


# -- export ------------------------------------------------------------------


def to_chrome_trace(records) -> Dict:
    """Span records -> Chrome trace-event JSON (Perfetto-loadable): one
    complete ("X") event per span, plus flow arrows ("s"/"f") for edges
    that cross a process boundary, so the stitching is visible."""
    by_span = {}
    for r in records:
        if r.get("kind", "span") == "span" and "span" in r:
            by_span[r["span"]] = r
    events = []
    for r in by_span.values():
        args = {"trace": r.get("trace"), "span": r.get("span")}
        if "parent" in r:
            args["parent"] = r["parent"]
        if "error" in r:
            args["error"] = r["error"]
        args.update(r.get("attrs") or {})
        ts_us = float(r["ts"]) * 1e6
        dur_us = float(r.get("dur_s", 0.0)) * 1e6
        base = {"pid": r.get("pid", 0), "tid": r.get("tid", 0)}
        events.append({
            "name": r["name"], "cat": "lightctr", "ph": "X",
            "ts": ts_us, "dur": dur_us, "args": args, **base,
        })
        parent = by_span.get(r.get("parent"))
        if parent is not None and parent.get("pid") != r.get("pid"):
            # cross-process edge: draw the flow arrow parent -> child
            flow_id = int(r["span"], 16) & 0x7FFFFFFF
            events.append({
                "name": "rpc", "cat": "lightctr", "ph": "s",
                "id": flow_id, "ts": float(parent["ts"]) * 1e6,
                "pid": parent.get("pid", 0), "tid": parent.get("tid", 0),
            })
            events.append({
                "name": "rpc", "cat": "lightctr", "ph": "f", "bp": "e",
                "id": flow_id, "ts": ts_us,
                **base,
            })
    events.sort(key=lambda e: e["ts"])
    return {"traceEvents": events, "displayTimeUnit": "ms"}


# -- env wiring --------------------------------------------------------------

_dir = os.environ.get("LIGHTCTR_TRACE_DIR")
if _dir:
    # one span file per process: tools/trace_report.py merges the set.
    # Deliberately independent of the local rate — a PS server deployed
    # with only LIGHTCTR_TRACE_DIR still records (and must persist) the
    # subtrees of remote-continued traces; the file is not created until
    # a span actually flushes
    try:
        os.makedirs(_dir, exist_ok=True)
        configure(path=os.path.join(_dir, f"trace-{os.getpid()}.jsonl"))
    except OSError:
        pass  # tracing must never break the traced process
