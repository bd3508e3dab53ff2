"""From a runner's readings to the contract's one last line."""

from __future__ import annotations

import math
from typing import Dict


def print_checks(checks: Dict, stream) -> None:
    """Each number compared beside its limit: the last lines of stderr."""
    for name, c in checks.items():
        v = c["value"]
        ok = v is not None and v <= c["limit"]
        print(f"[check] {name} value={v!r} limit={c['limit']!r} "
              f"{'ok' if ok else 'FAILED'}", file=stream, flush=True)


def read_per_layer(man, cell: Dict, ctx: Dict) -> Dict:
    """Every per-layer metric of the cell whose reader finds something to
    read; a reader that returns ``None`` leaves its metric out."""
    out = {}
    for entry in man.metrics_for(cell["name"], "per_layer"):
        value = man.metric_reader(entry["name"]).read(ctx)
        if value is None:
            continue
        value = float(value)
        if not math.isfinite(value):
            raise ValueError(f"metric {entry['name']} read {value}")
        out[entry["name"]] = {"value": value, "unit": entry["unit"]}
    return out


def result_line(man, cell: Dict, result: Dict, trace: bool) -> Dict:
    """``result``: the runner's dict with ``end_to_end`` values, ``ctx`` for
    the per-layer readers, ``checks``, ``correct``, ``attempted``,
    ``failed`` and ``device``."""
    if trace:
        metrics = read_per_layer(man, cell, result["ctx"])
    else:
        metrics = {}
        for entry in man.metrics_for(cell["name"], "end_to_end"):
            metrics[entry["name"]] = {
                "value": float(result["end_to_end"][entry["name"]]),
                "unit": entry["unit"]}
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": metrics, "device": result["device"]}
    if trace and result.get("breakdown"):
        line["breakdown"] = result["breakdown"]
    line.update(result.get("extra", {}))
    line["checks"] = result["checks"]      # comes last
    return line
