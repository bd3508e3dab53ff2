"""From a profiler trace to numbers: the one reduction every PR is read by.

Input is either an ``.xplane.pb`` the JAX profiler wrote (``load_xplane``)
or the same content as plain lists (the small recorded trace the tests
keep): per device a list of operation events, and a list of the host spans:
the benchmark's own (``jax.profiler.TraceAnnotation`` names starting with
``bench/``) and the program's on the training path (``trainer/``,
``ingest/``; ``lightctr_tpu/obs/trace.py`` writes them into the same
profiler session).  Times are nanoseconds on the profile's own clock, which host
and device planes share.

An operation event is ``(name, scope, start_ns, dur_ns)``: ``name`` the HLO
instruction (``fusion.12``), ``scope`` the JAX name stack that produced it
(``jit(step)/sparse_tables/apply/scatter-add``), which is where
``jax.named_scope`` names survive a refactor of kernels.
"""

from __future__ import annotations

import functools
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, str, float, float]
Span = Tuple[str, float, float]

COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute"
    r"|all_reduce|all_gather|all_to_all|reduce_scatter|collective_permute"
    r"|psum|ppermute", re.I)
#: device lines that hold one event per executed HLO operation (the
#: "Async XLA Ops" line repeats copies that run beside them)
OP_LINES = ("XLA Ops",)
#: host spans kept: the benchmark's own, and the program's that name what
#: the host does in a training step and in its input pipeline
SPAN_PREFIXES = ("bench/", "trainer/", "ingest/")
WINDOW = "bench/window"
#: ``jit(``, ``transpose(jvp(`` ... in a name stack, and their closing brackets
TRANSFORM_RE = re.compile(r"[A-Za-z_][\w.\-]*\(|\)")


def load_xplane(path: str) -> Dict:
    """``{"devices": {plane: [Event]}, "spans": [Span]}`` from an xplane file.
    The spans are those of the one thread that placed ``bench/window``, the
    thread that drives the steps: the ingest worker's (``ingest/produce``,
    ``ingest/put_wait``) run beside it, and an idle gap is named by what the
    driving thread was doing (``ingest/get_wait`` is its own)."""
    from . import xplane

    planes = xplane.read_planes(
        path,
        want_plane=lambda p: p.startswith(("/device:TPU:", "/host:")),
        want_line=lambda p, ln: ln in OP_LINES or p.startswith("/host:"))
    devices: Dict[str, List[Event]] = {}
    spans: List[Span] = []
    for plane, lines in planes.items():
        if plane.startswith("/device:"):
            ops = [(name, stats.get("tf_op", "").rstrip(":"), start, dur)
                   for ln in OP_LINES for name, stats, start, dur in lines.get(ln, ())]
            if ops:
                devices[plane] = ops
        else:
            for events in lines.values():
                found = [(name, start, start + dur)
                         for name, _, start, dur in events
                         if name.startswith(SPAN_PREFIXES)]
                if any(name == WINDOW for name, _, _ in found):
                    spans = found
    return {"devices": devices, "spans": sorted(spans, key=lambda sp: sp[1])}


# -- intervals ----------------------------------------------------------------


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: Sequence[Tuple[float, float]], b: Sequence[Tuple[float, float]]
             ) -> List[Tuple[float, float]]:
    """The parts of (merged) ``a`` that (merged) ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(events: Iterable[Event], t0: float, t1: float) -> List[Event]:
    """Events cut to the window ``[t0, t1]``."""
    out = []
    for name, scope, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((name, scope, a, b - a))
    return out


def _iv(events: Iterable[Event]) -> List[Tuple[float, float]]:
    return [(s, s + d) for _, _, s, d in events]


def is_collective(ev: Event) -> bool:
    return _collective(ev[0], ev[1])


@functools.lru_cache(maxsize=None)
def _collective(name: str, scope: str) -> bool:
    # a trace repeats a few thousand (name, scope) pairs millions of times
    return bool(COLLECTIVE_RE.search(name) or COLLECTIVE_RE.search(scope))


# -- the reduction ------------------------------------------------------------


def window_of(spans: Sequence[Span], name: str = WINDOW) -> Tuple[float, float]:
    for n, s, e in spans:
        if n == name:
            return s, e
    raise ValueError(f"the trace holds no {name!r} span")


def reduce_trace(trace: Dict, top: int = 10) -> Dict:
    """Busy and idle time, per-operation sums, collective time with the
    part of it no other operation covers (exposed), and the idle gaps by
    what the host was doing, over the ``bench/window``
    span.  Seconds, averaged over the devices that ran anything."""
    t0, t1 = window_of(trace["spans"])
    spans = [s for s in trace["spans"] if s[0] != WINDOW]
    # from here on an event's name is its label, worked out once
    per_dev = {d: [(op_label(n, sc), sc, s, dur) for n, sc, s, dur in clip(evs, t0, t1)]
               for d, evs in trace["devices"].items()}
    per_dev = {d: evs for d, evs in per_dev.items() if evs}
    n_dev = max(1, len(per_dev))
    busy_ns = coll_ns = exposed_ns = 0.0
    op_ns: Dict[str, float] = {}
    gaps_ns: Dict[str, float] = {}
    for i, (dev, evs) in enumerate(sorted(per_dev.items())):
        busy = union(_iv(evs))
        busy_ns += total(busy)
        coll = union(_iv(e for e in evs if is_collective(e)))
        comp = union(_iv(e for e in evs if not is_collective(e)))
        coll_ns += total(coll)
        exposed_ns += total(subtract(coll, comp))
        for key, _, _, d in evs:
            op_ns[key] = op_ns.get(key, 0.0) + d
        if i == 0:
            gaps_ns = attribute_gaps(subtract([(t0, t1)], busy),
                                     host_timeline(spans))
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": busy_ns / n_dev / 1e9,
        "devices": len(per_dev),
        "collective_s": coll_ns / n_dev / 1e9,
        "exposed_collective_s": exposed_ns / n_dev / 1e9,
        "device_ops": [[k, v / n_dev / 1e9] for k, v in rank(op_ns)],
        "idle_gaps": [[k, v / 1e9] for k, v in rank(gaps_ns)],
        "per_device": per_dev,
    }


@functools.lru_cache(maxsize=None)
def op_label(name: str, scope: str) -> str:
    """One label per operation: instruction name plus its name stack, in a
    name's characters."""
    label = f"{name}__{scope}" if scope and scope != name else name
    return re.sub(r"[^A-Za-z0-9_./\-]", "_", label)[:120]


def host_timeline(spans: Sequence[Span]) -> List[Tuple[float, float, str]]:
    """``(start, end, label)`` pieces, in order and not overlapping: at each
    moment the innermost (shortest) span that covers it; the benchmark's own
    lose their ``bench/`` prefix, the program's keep their names."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    by_start = sorted(spans, key=lambda sp: sp[1])
    out, active, j = [], [], 0
    for lo, hi in zip(cuts, cuts[1:]):
        while j < len(by_start) and by_start[j][1] <= lo:
            active.append(by_start[j])
            j += 1
        active = [sp for sp in active if sp[2] > lo]
        if active:
            name = min(active, key=lambda sp: sp[2] - sp[1])[0]
            label = name[len("bench/"):] if name.startswith("bench/") else name
            if out and out[-1][2] == label and out[-1][1] == lo:
                out[-1] = (out[-1][0], hi, label)
            else:
                out.append((lo, hi, label))
    return out


def attribute_gaps(gaps: Sequence[Tuple[float, float]],
                   timeline: Sequence[Tuple[float, float, str]]) -> Dict[str, float]:
    """Idle nanoseconds by what the host was doing: each gap is cut at the
    timeline's edges; what no span covers is ``host_other``."""
    import bisect

    ends = [e for _, e, _ in timeline]
    out: Dict[str, float] = {}
    for gs, ge in gaps:
        covered = 0.0
        i = bisect.bisect_right(ends, gs)
        while i < len(timeline) and timeline[i][0] < ge:
            s, e, label = timeline[i]
            part = min(e, ge) - max(s, gs)
            if part > 0:
                out[label] = out.get(label, 0.0) + part
                covered += part
            i += 1
        if ge - gs - covered > 0:
            out["host_other"] = out.get("host_other", 0.0) + (ge - gs - covered)
    return out


@functools.lru_cache(maxsize=None)
def stack_path(scope: str) -> str:
    """A name stack with the transforms' wrappers taken off:
    ``jit(step)/jvp(seq/gqa)/full/dot_general`` is
    ``step/seq/gqa/full/dot_general``, where a scope a transform closes
    around reads as it was opened."""
    return TRANSFORM_RE.sub("", scope)


def scope_seconds(reduced: Dict, pattern: str) -> float:
    """Device seconds (mean over devices) of operations whose label or whole
    name stack (``stack_path``; the label is cut at 120 characters) matches
    ``pattern`` — found by scope, not by kernel name.  Overlapping matches
    on one device count once."""
    ns = 0.0
    for evs in reduced["per_device"].values():
        ns += total(union(_iv(e for e in evs if _in_scope(pattern, e[0], e[1]))))
    return ns / max(1, len(reduced["per_device"])) / 1e9


@functools.lru_cache(maxsize=None)
def _in_scope(pattern: str, label: str, scope: str) -> bool:
    rx = re.compile(pattern)
    return bool(rx.search(label) or rx.search(stack_path(scope)))
