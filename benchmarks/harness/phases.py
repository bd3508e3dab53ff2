"""The device's step by phase: which of the step's six phases each operation
of a reduced trace belongs to, and each phase's time.

The jitted step opens one scope a phase but the model's
(``jax.named_scope`` through the program's ``annotate``;
docs/OBSERVABILITY.md, "The phases of the step"), and JAX keeps a scope
through ``jvp`` and ``transpose``, so an operation's name stack — an event's
``scope`` in ``reduced['per_device']``, which is whole where its label is cut
at 120 characters — says which phase made it:
``jit(step)/transpose(jvp(model/expand))/jit(_take)/scatter-add`` is the
``expand`` phase's.  An operation of the step (its name stack starts with
``jit(step)/``, or ``jit(local_step)/``) belongs to the FIRST scope of
``SCOPES`` that is a whole run of components of its name stack, the
transforms' wrappers taken off — the accumulator's gather inside
``sparse_tables/apply`` is ``apply`` — and, under none of the five, to
``model``: the forward and backward pass of the loss open no scope of their
own, so the stacks of what runs inside them (``seq/kda/scan``) are what they
were before the step named its phases.  A primitive that is merely called
``gather``, in another program, is nothing.

A wrapper (``cond.N``, ``conditional.N``, ``while.N``, their clones) has an
event of its own around its body's and, where the compiler made it, no name
stack: one whose interval encloses operations of exactly one phase is that
phase's for the whole interval (the placement between a switch's branches is
the work of the phase that switches); one that encloses several phases or none
stays unphased for what its children do not cover.

A phase's time on a device is the union of its events' intervals, so nested
events count once; phases are disjoint sets of events, and on one chip, where
operations run one after another, disjoint in time too.  On a mesh an
asynchronous collective may run beside another phase's operations, and then
the phases sum to more than the busy time by that overlap.
"""

from __future__ import annotations

import bisect
import functools
import re
from typing import Dict, List, Optional, Tuple

from benchmarks.harness.trace_reduce import Event, stack_path, subtract, total, union

#: phase -> scope, in the order that decides an operation under several
SCOPES = {
    "apply": "sparse_tables/apply",
    "dedup": "sparse_tables/dedup_gather/dedup_ids",
    "gather": "sparse_tables/dedup_gather/gather_rows",
    "expand": "model/expand",
    "update": "step/update",
}
#: what the step runs under none of them is the model's
PHASES = tuple(SCOPES) + ("model",)
#: the scopes the parent of the PR that named the phases lacks: a program
#: that opens none of them has no split to read
_NEW = ("dedup", "gather", "expand", "update")
_STEP = re.compile(r"jit\((?:\w+_)?step\)/")

Interval = Tuple[float, float]


@functools.lru_cache(maxsize=None)
def phase_of(scope: str) -> Optional[str]:
    """The phase of a name stack, or ``None``."""
    if not _STEP.match(scope):
        return None
    path = "/" + stack_path(scope) + "/"
    for phase, name in SCOPES.items():
        if "/" + name + "/" in path:
            return phase
    return "model"


def device_phases(events: List[Event]) -> Dict[str, List[Interval]]:
    """One device's merged intervals a phase (phases with none left out),
    after the wrapper rule."""
    found: Dict[str, List[Interval]] = {}
    phased, nameless = [], []
    for label, scope, start, dur in events:
        phase = phase_of(scope)
        if phase is not None:
            phased.append((start, start + dur, phase))
        elif not scope or scope == label:
            nameless.append((start, start + dur))
    phased.sort()
    starts = [s for s, _, _ in phased]
    adopted = []
    for s, e in nameless:
        inside = set()
        for i in range(bisect.bisect_left(starts, s), len(phased)):
            if phased[i][0] >= e:
                break
            if phased[i][1] <= e:
                inside.add(phased[i][2])
        if len(inside) == 1:
            adopted.append((s, e, inside.pop()))
    for s, e, phase in phased + adopted:
        found.setdefault(phase, []).append((s, e))
    return {phase: union(iv) for phase, iv in found.items()}


#: the last reduced trace and its split: seven readers read one run's trace
_last: Tuple[Optional[Dict], Optional[Dict[str, float]]] = (None, None)


def split_ns(reduced: Optional[Dict]) -> Optional[Dict[str, float]]:
    """Nanoseconds a phase in the traced window, mean over the devices that
    ran anything, with ``unphased`` (busy under no phase) and ``busy`` beside
    them; ``None`` for no trace or a program that opens none of the scopes
    that split the step."""
    global _last
    if reduced is None or not reduced["per_device"]:
        return None
    if _last[0] is not reduced:
        _last = (reduced, _split_ns(reduced))
    return _last[1]


def _split_ns(reduced: Dict) -> Optional[Dict[str, float]]:
    out: Dict[str, float] = {}
    for events in reduced["per_device"].values():
        by_phase = device_phases(events)
        for phase, iv in by_phase.items():
            out[phase] = out.get(phase, 0.0) + total(iv)
        busy = union((s, s + d) for _, _, s, d in events)
        covered = union(i for iv in by_phase.values() for i in iv)
        out["busy"] = out.get("busy", 0.0) + total(busy)
        out["unphased"] = out.get("unphased", 0.0) + total(subtract(busy, covered))
    if not any(phase in out for phase in _NEW):
        return None
    n = len(reduced["per_device"])
    return {k: v / n for k, v in out.items()}


def phase_ms_per_step(ctx: Dict, phase: str) -> Optional[float]:
    """What a ``train_phase_<phase>_ms_per_step`` reader returns."""
    ns = split_ns(ctx["reduced"])
    if ns is None or not ctx["steps"] or not ns.get(phase):
        return None
    return ns[phase] / 1e6 / ctx["steps"]


def unphased_share(ctx: Dict) -> Optional[float]:
    """Per cent of the device's busy time under no phase."""
    ns = split_ns(ctx["reduced"])
    if ns is None or ns["busy"] <= 0:
        return None
    return 100.0 * ns["unphased"] / ns["busy"]
