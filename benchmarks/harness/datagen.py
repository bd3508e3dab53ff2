"""Traffic generation: the one general generator every traffic file feeds.

numpy only.  Rows have the Criteo layout (one feature per field, slot ``j``
is field ``j``; ``n_cat`` categorical fields, then the numeric ones).  A
traffic file's ``rows`` says how ids are drawn:

``cardinalities``  ``"table"``: every drawing field's vocabulary is the
                   table itself, ids are used as drawn; or a list with one
                   vocabulary size per drawing field: a value is its field's
                   rank, hashed with the field's number into the table's
                   rows (two values may share a row, as in any hashed
                   deployment);
``exponent``       ``s`` of the power law a field's ranks follow:
                   ``P(rank = k) = ((k+2)^(1-s) - (k+1)^(1-s)) / ((N+1)^(1-s) - 1)``
                   for ``k`` in ``[0, N)``, which falls as ``k^-s`` (Zipf);
                   0 is uniform;
``numeric_ids``    ``"fixed"``: each numeric field keeps one id (its field
                   index) and carries the measurement as its value, the
                   libFFM form of a Criteo row; ``"drawn"``: numeric fields
                   draw ids like the categorical ones, so every slot of a
                   row touches its own table row.

The row layout and the labels are a copy of the program's
``data/synth.write_criteo_proxy`` (the yardstick keeps its own traffic
code; the original is listed in PERF.md for a later PR to retire).
"""

from __future__ import annotations

import os
from typing import Dict

import numpy as np


def seed_words(*parts: int) -> list:
    """A numpy seed sequence from whole numbers of any size (the driver's
    seeds pass 2**31)."""
    return [int(p) & 0xFFFFFFFF for part in parts
            for p in (int(part), int(part) >> 32)]


def power_law_ranks(u: np.ndarray, n: int, s: float) -> np.ndarray:
    """Ranks in ``[0, n)`` from uniforms in ``[0, 1)`` by the inverse of the
    continuous power law ``x^-s`` on ``[1, n+1)``, floored."""
    if s == 0.0:
        x = u * n + 1.0
    elif s == 1.0:
        x = np.exp(u * np.log(n + 1.0))
    else:
        e = 1.0 - s
        x = (u * ((n + 1.0) ** e - 1.0) + 1.0) ** (1.0 / e)
    return np.minimum(x.astype(np.int64) - 1, n - 1)


def hash_rows(ranks: np.ndarray, field: int, vocab: int) -> np.ndarray:
    """A field's value -> a table row (splitmix64's finaliser, mod vocab)."""
    z = ranks.astype(np.uint64) + np.uint64((field + 1) * 0x9E3779B97F4A7C15 % 2**64)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    z ^= z >> np.uint64(31)
    return (z % np.uint64(vocab)).astype(np.int64)


def criteo_rows(rng: np.random.Generator, n: int, spec: Dict, *, fields: int,
                n_cat: int, vocab: int) -> Dict[str, np.ndarray]:
    """``n`` rows drawn as ``spec`` (a traffic file's ``rows``) says.
    Labels follow a logistic in the first two numeric fields and the parity
    of field 0's id."""
    numeric_ids, cards = spec["numeric_ids"], spec["cardinalities"]
    if numeric_ids not in ("fixed", "drawn"):
        raise ValueError(f"numeric_ids must be fixed or drawn, got {numeric_ids!r}")
    drawing = n_cat if numeric_ids == "fixed" else fields
    if cards != "table" and len(cards) != drawing:
        raise ValueError(f"{len(cards)} cardinalities for {drawing} drawing fields")
    s = float(spec["exponent"])
    u = rng.random(size=(n, fields))
    fids = np.empty((n, fields), np.int64)
    for j in range(drawing):
        if cards == "table":
            fids[:, j] = power_law_ranks(u[:, j], vocab, s)
        else:
            fids[:, j] = hash_rows(power_law_ranks(u[:, j], int(cards[j]), s), j, vocab)
    if numeric_ids == "fixed":
        fids[:, n_cat:] = np.arange(n_cat, fields, dtype=np.int64)[None, :]
    vals = np.ones((n, fields), np.float32)
    vals[:, n_cat:] = rng.exponential(
        1.0, size=(n, fields - n_cat)).astype(np.float32).round(3)
    z = ((vals[:, n_cat] - 1.0) + (vals[:, n_cat + 1] - 1.0)
         + (fids[:, 0] % 2).astype(np.float32) - 0.5)
    p = 1.0 / (1.0 + np.exp(-2.0 * z))
    labels = (rng.random(n) < p).astype(np.float32)
    return {"fids": fids.astype(np.int32), "vals": vals, "labels": labels}


def write_libffm(path: str, rows: int, seed: int, spec: Dict, *, fields: int,
                 n_cat: int, vocab: int, chunk: int = 16384) -> str:
    """The rows as a libFFM text file (``label field:fid:val ...``), the
    format the program's ingest compiles to shards."""
    rng = np.random.default_rng(seed_words(seed))
    cols = np.arange(fields)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        done = 0
        while done < rows:
            n = min(chunk, rows - done)
            r = criteo_rows(rng, n, spec, fields=fields, n_cat=n_cat, vocab=vocab)
            fids, vals, labels = r["fids"], r["vals"], r["labels"]
            lines = []
            for i in range(n):
                feats = " ".join(
                    f"{j}:{fid}:{val:g}" for j, fid, val
                    in zip(cols, fids[i].tolist(), vals[i].tolist()))
                lines.append(f"{int(labels[i])} {feats}\n")
            f.writelines(lines)
            done += n
    os.replace(tmp, path)
    return path


def distinct_ids_per_batch(spec: Dict, *, fields: int, n_cat: int, vocab: int,
                           batch: int, seed: int = 0) -> int:
    """How many distinct table rows one batch of this traffic touches — a
    count from the generator, which the byte functions need (touched rows,
    not the table)."""
    rng = np.random.default_rng(seed_words(seed))
    r = criteo_rows(rng, batch, spec, fields=fields, n_cat=n_cat, vocab=vocab)
    return int(np.unique(r["fids"]).size)
