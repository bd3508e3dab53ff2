"""The table of peaks, keyed by ``device_kind``; an unknown kind is an error."""

from __future__ import annotations

import os
from typing import Dict

from .manifest import BENCH_DIR, load_json


def peaks_for(device_kind: str, bench_dir: str = BENCH_DIR) -> Dict[str, float]:
    table = load_json(os.path.join(bench_dir, "peaks.json"))
    if device_kind.startswith("_") or device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in peaks.json (have "
            f"{sorted(k for k in table if not k.startswith('_'))}); add it "
            "with its source, do not default")
    return table[device_kind]
