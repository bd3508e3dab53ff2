"""The training rows of a cell: written once per checkout, compiled by the
program's own ingest to ``lcshard-v1`` shards, replayed by every later run.

The rows depend on the traffic file and the configuration's sizes only
(``data_seed``), never on ``--seed``: a run's seed picks the order of
replay, so every seed trains on the same set of batches in another order.
The shards live at a fixed path inside the checkout, named from everything
that determines their content.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
from typing import Dict

from . import datagen


def rows_key(cfg: Dict, traffic: Dict) -> Dict:
    spec = traffic["rows"]
    return {"generator": spec["generator"], "exponent": spec["exponent"],
            "cardinalities": spec["cardinalities"],
            "numeric_ids": spec["numeric_ids"], "data_seed": spec["data_seed"],
            "rows": int(spec["distinct_batches"]) * int(cfg["batch"]),
            "vocab": cfg["vocab"], "fields": cfg["fields"], "n_cat": cfg["n_cat"]}


def shard_cache(cache_root: str, cfg: Dict, traffic: Dict):
    """The compiled ``ShardCache`` of the cell's rows (built on first use)."""
    from lightctr_tpu.data import ingest
    from lightctr_tpu.native import bindings

    key = rows_key(cfg, traffic)
    if key["generator"] != "criteo_rows":
        raise ValueError(f"unknown rows generator {key['generator']!r}")
    name = hashlib.sha1(json.dumps(key, sort_keys=True).encode()).hexdigest()[:16]
    cache_dir = os.path.join(cache_root, "rows", name)
    key_path = os.path.join(cache_dir, "rows_key.json")
    if os.path.isfile(key_path):
        with open(key_path) as f:
            same = json.load(f) == key
        cache = ingest.load_cache(cache_dir) if same else None
        if cache is not None and cache.rows == key["rows"]:
            return cache
    if not bindings.available():
        raise RuntimeError("the native parser did not build; the ingest path "
                           "the benchmark times is the native one")
    shutil.rmtree(cache_dir, ignore_errors=True)
    os.makedirs(cache_dir, exist_ok=True)
    text = os.path.join(cache_root, "rows", name + ".ffm")
    try:
        datagen.write_libffm(
            text, key["rows"], key["data_seed"], traffic["rows"],
            fields=key["fields"], n_cat=key["n_cat"], vocab=key["vocab"])
        cache = ingest.compile_shards(
            text, key["fields"], cache_dir=cache_dir, feature_cnt=key["vocab"],
            field_cnt=key["fields"], native=True)
    finally:
        if os.path.exists(text):
            os.unlink(text)
    if cache.rows != key["rows"]:
        raise RuntimeError(f"shards hold {cache.rows} rows, wrote {key['rows']}")
    with open(key_path, "w") as f:
        json.dump(key, f)
    return cache
