"""Packed token sequences: the generator behind a ``train_seq`` traffic
file's ``rows``, and their shards.

numpy only.  Documents arrive one after another, each with a length drawn
from a log-normal (``median_tokens``, ``sigma``, clipped to ``min_tokens``
.. ``max_tokens``) and token ids drawn by a power law of ``exponent`` over
the vocabulary slice (``datagen.power_law_ranks``) and hashed into its
rows (``datagen.hash_rows``); they are laid end to end and cut at every
``tokens``-th token: no padding, and a document the cut divides goes on in
the next sequence as a document of its own.  A sequence is one row of the
program's ingest: ``label doc:token:1 ...`` in libFFM form, the field
column carrying the document's number within the sequence.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
from typing import Dict

import numpy as np

from . import datagen

SPEC_KEYS = ("generator", "exponent", "median_tokens", "sigma", "min_tokens",
             "max_tokens", "data_seed", "distinct_batches")


def packed_sequences(rng: np.random.Generator, n: int, spec: Dict, *,
                     tokens: int, vocab: int) -> Dict[str, np.ndarray]:
    """``n`` sequences of ``tokens`` ids: ``tokens`` [n, tokens] int32 and
    ``segments`` [n, tokens] int32 (the document's number in its sequence)."""
    need, lengths = n * tokens, []
    while sum(lengths) < need:
        draw = np.exp(rng.normal(np.log(spec["median_tokens"]), spec["sigma"],
                                 size=max(16, need // spec["median_tokens"])))
        lengths.extend(np.clip(np.rint(draw), spec["min_tokens"],
                               spec["max_tokens"]).astype(np.int64).tolist())
    doc_of = np.repeat(np.arange(len(lengths)), lengths)[:need].reshape(n, tokens)
    ranks = datagen.power_law_ranks(rng.random(need), vocab, float(spec["exponent"]))
    ids = datagen.hash_rows(ranks, 0, vocab).reshape(n, tokens)
    return {"tokens": ids.astype(np.int32),
            "segments": (doc_of - doc_of[:, :1]).astype(np.int32)}


def write_libffm(path: str, seqs: Dict[str, np.ndarray]) -> str:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        for toks, segs in zip(seqs["tokens"].tolist(), seqs["segments"].tolist()):
            f.write("0 " + " ".join(f"{s}:{t}:1" for s, t in zip(segs, toks)) + "\n")
    os.replace(tmp, path)
    return path


def counted(spec: Dict, *, tokens: int, vocab: int, sequences: int) -> Dict:
    """Counts from the generator over the replayed set, which the byte and
    FLOP functions need: distinct rows and documents a step, and the
    (query, key) pairs of one document a step (``sum L (L + 1) / 2``)."""
    n = int(spec["distinct_batches"]) * sequences
    seqs = packed_sequences(np.random.default_rng(datagen.seed_words(spec["data_seed"])),
                            n, spec, tokens=tokens, vocab=vocab)
    distinct, docs, pairs = [], [], []
    for i in range(0, n, sequences):
        distinct.append(np.unique(seqs["tokens"][i:i + sequences]).size)
        d = p = 0
        for seg in seqs["segments"][i:i + sequences]:
            runs = np.diff(np.flatnonzero(np.concatenate(
                [[True], seg[1:] != seg[:-1], [True]])))
            d += runs.size
            p += int(np.sum(runs * (runs + 1) // 2))
        docs.append(d)
        pairs.append(p)
    return {"distinct_rows_per_step": float(np.mean(distinct)),
            "documents_per_step": float(np.mean(docs)),
            "attended_pairs_per_step": float(np.mean(pairs))}


def shard_cache(cache_root: str, cfg: Dict, traffic: Dict):
    """The compiled ``ShardCache`` of the cell's sequences (built on first
    use; they depend on the traffic file and the configuration's sizes,
    never on ``--seed``, which picks the order of replay)."""
    from lightctr_tpu.data import ingest
    from lightctr_tpu.native import bindings

    spec = traffic["rows"]
    tokens = cfg["batch"] // cfg["sequences"]
    key = {k: spec[k] for k in SPEC_KEYS}
    key.update(rows=int(spec["distinct_batches"]) * cfg["sequences"],
               tokens=tokens, vocab=cfg["vocab"])
    if key["generator"] != "packed_sequences":
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
        write_libffm(text, packed_sequences(
            np.random.default_rng(datagen.seed_words(key["data_seed"])),
            key["rows"], spec, tokens=tokens, vocab=key["vocab"]))
        # a shard of 8 sequences, so that the replay's shard shuffle has
        # shards to order; a block is what the parser holds at once
        cache = ingest.compile_shards(
            text, tokens, cache_dir=cache_dir, feature_cnt=key["vocab"],
            field_cnt=tokens, block_rows=8, shard_rows=8, native=True)
    finally:
        if os.path.exists(text):
            os.unlink(text)
    if cache.rows != key["rows"]:
        raise RuntimeError(f"shards hold {cache.rows} rows, wrote {key['rows']}")
    with open(key_path, "w") as f:
        json.dump(key, f)
    return cache
