"""Perf-regression trajectory: fold bench artifacts into an append-only
history and gate on regressions against the trailing median.

The repo accumulates point-in-time bench artifacts (``BENCH_r*.json``,
``TIERED_BENCH.json``, ``SERVE_BENCH.json``, ...) but nothing connects
them: a 20% throughput regression between two PRs is invisible unless a
human diffs the files.  This tool gives the artifacts a time axis:

``fold``
    walk one artifact's numeric leaves into ``(bench, cell, metric)``
    keyed rows appended to ``BENCH_HISTORY.jsonl`` — one JSONL line per
    metric per run, so the history is merge-friendly and grep-able.
    ``BENCH_r<NN>.json`` driver artifacts (the ``parsed`` single-metric
    shape) fold as ``bench=trainer, cell=single_process``; ``/devicez``
    dumps / ProgramCatalog snapshots fold per compiled program as
    ``bench=device, cell=<component>.<program>`` (flops, intensity,
    utilization, memory_*_bytes); everything else folds generically with
    the artifact stem as the bench name and the dotted leaf path as the
    cell.

``gate``
    group the history by key and compare each key's LATEST value against
    the median of its trailing window.  A metric whose name says which
    way is better (``*_per_s``/``qps``/``ratio``/``auc`` up;
    ``*_seconds``/``p99``/``bytes``/``loss`` down) fails the gate when
    the latest value regresses more than ``--max-regress`` (default 20%)
    past that median; direction-unknown metrics are tracked but never
    gated, and keys with fewer than two runs are skipped.  Exit 1 on any
    failure — the CI hook.

``tiered_bench.py --history`` / ``serve_bench.py --history`` run the
fold-in + gate automatically after writing their artifact, so a bench
run refuses to quietly land a regression in its own trajectory.

Usage:
    python tools/bench_history.py fold SERVE_BENCH.json --run r06
    python tools/bench_history.py fold TIERED_BENCH.json
    python tools/bench_history.py gate --max-regress 0.2 --window 5
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

DEFAULT_HISTORY = "BENCH_HISTORY.jsonl"

# metric-name keywords -> direction (checked in order; higher-better
# first so "examples_per_sec" never matches a latency keyword).
_HIGHER = ("per_sec", "per_s", "_qps", "qps", "throughput", "examples",
           "rows_per", "ratio", "auc", "hit_rate", "hit", "reduction",
           "utilization", "intensity")
_LOWER = ("seconds", "_ms", "_us", "p50", "p99", "p999", "latency",
          "bytes", "loss", "stale", "shed", "drop", "fail", "err",
          "compile")


def metric_direction(name: str) -> int:
    """+1 = higher is better, -1 = lower is better, 0 = unknown (the
    metric is tracked in the history but never gated)."""
    n = name.lower()
    for kw in _HIGHER:
        if kw in n:
            return 1
    for kw in _LOWER:
        if kw in n:
            return -1
    return 0


def _walk_leaves(node, path: Tuple[str, ...] = ()):
    """Yield (path, value) for every numeric leaf (bools excluded —
    pass/fail flags are gates already, not trajectories)."""
    if isinstance(node, bool):
        return
    if isinstance(node, (int, float)):
        yield path, float(node)
        return
    if isinstance(node, dict):
        for k, v in node.items():
            yield from _walk_leaves(v, path + (str(k),))
    elif isinstance(node, list):
        for i, v in enumerate(node):
            yield from _walk_leaves(v, path + (str(i),))


def _device_catalogs(node):
    """Yield ProgramCatalog snapshots found anywhere in an artifact — a
    bare ``snapshot()``/``payload()``, a ``/devicez`` dump
    (``{"device": {provider: snapshot}}``), or a flight bundle's device
    section.  Catalog snapshots are the ones that self-mark with
    ``device: True`` AND carry a ``backend`` (census/donation/profile
    sections self-mark too but have no roofline rows to fold)."""
    if not isinstance(node, dict):
        return
    if node.get("device") is True and "backend" in node \
            and isinstance(node.get("programs"), dict):
        yield node
        return
    for v in node.values():
        yield from _device_catalogs(v)


def _device_entries(data, run_id: str, source: str) -> List[Dict]:
    """Per-program device rows: bench=device, cell=<component>.<program>,
    metrics = flops / bytes_accessed / intensity / utilization /
    ewma_seconds / memory_<kind>_bytes — stable keys, so the gate tracks
    each compiled program's roofline and footprint across runs."""
    out: List[Dict] = []
    for cat in _device_catalogs(data):
        comp = cat.get("component", "device")
        for prog, rec in sorted((cat.get("programs") or {}).items()):
            if not isinstance(rec, dict):
                continue
            ana = rec.get("analysis") or {}
            row = {"flops": ana.get("flops"),
                   "bytes_accessed": ana.get("bytes_accessed"),
                   "intensity": ana.get("intensity"),
                   "utilization": rec.get("utilization"),
                   "ewma_seconds": rec.get("ewma_seconds")}
            for kind, v in sorted((ana.get("memory") or {}).items()):
                row[f"memory_{kind}_bytes"] = v
            for metric, v in row.items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    out.append({
                        "run": run_id, "bench": "device",
                        "cell": f"{comp}.{prog}", "metric": metric,
                        "value": float(v), "source": source,
                    })
    return out


def _entries_for(path: str, run: Optional[str]) -> List[Dict]:
    """One artifact file -> history rows (no I/O on the history)."""
    with open(path) as f:
        data = json.load(f)
    stem = os.path.splitext(os.path.basename(path))[0]
    run_id = run if run else stem.lower()
    # the driver's single-metric shape: {"parsed": {"metric", "value"}}
    parsed = data.get("parsed") if isinstance(data, dict) else None
    if isinstance(parsed, dict) and "metric" in parsed and "value" in parsed:
        return [{
            "run": run_id, "bench": "trainer", "cell": "single_process",
            "metric": str(parsed["metric"]), "value": float(parsed["value"]),
            "unit": parsed.get("unit"), "source": os.path.basename(path),
        }]
    # /devicez dumps and catalog snapshots fold with stable per-program
    # keys instead of the generic dotted-path walk
    device = _device_entries(data, run_id, os.path.basename(path))
    if device:
        return device
    out = []
    for leaf_path, value in _walk_leaves(data):
        if not leaf_path:
            continue
        out.append({
            "run": run_id, "bench": stem.lower(),
            "cell": ".".join(leaf_path[:-1]) or "root",
            "metric": leaf_path[-1], "value": value,
            "source": os.path.basename(path),
        })
    return out


def fold_artifact(path: str, history: str = DEFAULT_HISTORY,
                  run: Optional[str] = None) -> List[Dict]:
    """Append one artifact's rows to the history file; returns them."""
    entries = _entries_for(path, run)
    with open(history, "a") as f:
        for e in entries:
            f.write(json.dumps(e, sort_keys=True) + "\n")
    return entries


def read_history(history: str = DEFAULT_HISTORY) -> List[Dict]:
    out = []
    try:
        with open(history) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue  # a torn append must not kill the gate
    except OSError:
        pass
    return out


def _median(vals: List[float]) -> float:
    s = sorted(vals)
    n = len(s)
    mid = n // 2
    return s[mid] if n % 2 else 0.5 * (s[mid - 1] + s[mid])


def gate_history(history: str = DEFAULT_HISTORY, max_regress: float = 0.2,
                 window: int = 5) -> Dict:
    """Latest-vs-trailing-median regression check over the whole history.

    Returns ``{"ok", "checked", "skipped", "failures": [...]}`` —
    a failure row names the key, the latest value, the trailing median,
    and the fractional regression past the allowed band.
    """
    series: Dict[Tuple[str, str, str], List[float]] = {}
    for e in read_history(history):
        try:
            key = (str(e["bench"]), str(e["cell"]), str(e["metric"]))
            series.setdefault(key, []).append(float(e["value"]))
        except (KeyError, TypeError, ValueError):
            continue
    checked = skipped = 0
    failures: List[Dict] = []
    for (bench, cell, metric), vals in sorted(series.items()):
        direction = metric_direction(metric)
        if len(vals) < 2 or direction == 0:
            skipped += 1
            continue
        latest = vals[-1]
        trailing = vals[max(0, len(vals) - 1 - window):-1]
        med = _median(trailing)
        checked += 1
        if med == 0.0:
            continue
        if direction > 0:
            regress = (med - latest) / abs(med)
        else:
            regress = (latest - med) / abs(med)
        if regress > max_regress:
            failures.append({
                "bench": bench, "cell": cell, "metric": metric,
                "latest": latest, "trailing_median": med,
                "regress": round(regress, 4),
                "direction": "higher" if direction > 0 else "lower",
                "runs": len(vals),
            })
    return {"ok": not failures, "checked": checked, "skipped": skipped,
            "max_regress": max_regress, "window": window,
            "failures": failures}


def fold_and_gate(path: str, history: str = DEFAULT_HISTORY,
                  run: Optional[str] = None, max_regress: float = 0.2,
                  window: int = 5) -> Dict:
    """The bench tools' fold-in hook: append, then gate.  Returns the
    gate report with the fold count attached."""
    entries = fold_artifact(path, history, run=run)
    report = gate_history(history, max_regress=max_regress, window=window)
    report["folded"] = len(entries)
    report["history"] = history
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    f = sub.add_parser("fold", help="append artifacts to the history")
    f.add_argument("artifacts", nargs="+", help="bench JSON artifact(s)")
    f.add_argument("--history", default=DEFAULT_HISTORY)
    f.add_argument("--run", default=None,
                   help="run id stamped on every row (default: file stem)")
    g = sub.add_parser("gate", help="fail on trailing-median regressions")
    g.add_argument("--history", default=DEFAULT_HISTORY)
    g.add_argument("--max-regress", type=float, default=0.2,
                   help="allowed fractional regression vs the trailing "
                        "median (default 0.2)")
    g.add_argument("--window", type=int, default=5,
                   help="trailing runs the median is taken over")
    args = ap.parse_args(argv)
    if args.cmd == "fold":
        total = 0
        for path in args.artifacts:
            entries = fold_artifact(path, args.history, run=args.run)
            total += len(entries)
            print(f"{path}: {len(entries)} rows -> {args.history}",
                  file=sys.stderr)
        print(json.dumps({"folded": total, "history": args.history}))
        return 0
    report = gate_history(args.history, max_regress=args.max_regress,
                          window=args.window)
    print(json.dumps(report, indent=1))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
