"""The program's own spans, as the ``program_span`` readers take them.

``lightctr_tpu.obs.trace`` keeps finished spans in a ring in the runner's
own process; while the traced window's profiler session records, every
``trainer/step`` is a root with its phases under it and every batch the
ingest worker makes is an ``ingest/produce``.  A reader takes the window's:
the last ``ctx['steps']`` of them (the ring may hold fewer, having dropped
its oldest; in a test process it may hold an earlier run's as well).  A
program without such spans (the parent of the PR that brought them) leaves
the ring empty and every reader returns ``None``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

#: fewer whole samples than this in the ring: nothing is reported
MIN_SAMPLES = 10
STEP = "trainer/step"
#: a step tree is whole when the ring still holds each of these children
STEP_PHASES = ("trainer/input", "trainer/exec", "trainer/record")


def ring() -> List[Dict]:
    import importlib

    try:
        return importlib.import_module("lightctr_tpu.obs.trace").finished()
    except ImportError:
        return []


def dur_ms(rec: Dict) -> float:
    if "start_ns" in rec:
        return (rec["end_ns"] - rec["start_ns"]) / 1e6
    return 1e3 * float(rec.get("dur_s", 0.0))


def last_spans(ctx: Dict, name: str) -> Optional[List[Dict]]:
    """The last ``ctx['steps']`` spans called ``name``, oldest first."""
    found = [r for r in ring() if r.get("name") == name]
    found = found[-int(ctx["steps"]):] if ctx["steps"] else []
    return found if len(found) >= MIN_SAMPLES else None


def last_steps(ctx: Dict) -> Optional[List[Tuple[Dict, List[Dict]]]]:
    """The last ``ctx['steps']`` whole ``trainer/step`` trees, oldest first:
    ``(root, its descendants)``."""
    records = ring()
    children: Dict[str, List[Dict]] = {}
    for r in records:
        if "parent" in r:
            children.setdefault(r["parent"], []).append(r)
    trees = []
    for root in records:
        if root.get("name") != STEP or "parent" in root:
            continue
        below, todo = [], [root]
        while todo:
            kids = children.get(todo.pop()["span"], ())
            below.extend(kids)
            todo.extend(kids)
        direct = {r["name"] for r in children.get(root["span"], ())}
        if all(p in direct for p in STEP_PHASES):
            trees.append((root, below))
    trees = trees[-int(ctx["steps"]):] if ctx["steps"] else []
    return trees if len(trees) >= MIN_SAMPLES else None


def named(below: List[Dict], name: str) -> List[Dict]:
    """Descendants called ``name``."""
    return [r for r in below if r["name"] == name]
