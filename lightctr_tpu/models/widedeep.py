"""Wide & Deep CTR model — the reference's PS-mode distributed model.

Capability parity with ``Distributed_Algo_Abst`` (``distributed_algo_abst.h:93-349``):

  wide  = W . x over sparse fids          (distributed_algo_abst.h:203-212)
  deep  = concat_f embedding[rep_fid(f)]  (one factor_dim vector per field,
          keyed by the FIRST fid seen in that field per row —
          distributed_algo_abst.h:210-226)
          -> FC_tanh(field_cnt*factor_dim -> 50) -> FC_sigmoid(50 -> 1)
          (distributed_algo_abst.h:116-118)
  pCTR  = sigmoid(wide + deep)            (distributed_algo_abst.h:233)

In the reference, W lives in the PS sparse table and the embeddings in the PS
dense tensor table, pulled/pushed per batch with unique-key dedup
(distributed_algo_abst.h:178-196).  Here both are device arrays; on a mesh the
embedding table rows shard over the ``embed`` axis (see lightctr_tpu.embed)
and the pull/push round-trips become XLA gather/scatter with collectives.

``field_representatives`` precomputes the per-(row, field) representative fid
on host — data prep, not model state.
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from lightctr_tpu.nn import dense
from lightctr_tpu.ops.activations import sigmoid
from lightctr_tpu.ops.sparse_kernels import expand_rows


def field_representatives(
    fids: np.ndarray, fields: np.ndarray, mask: np.ndarray, field_cnt: int
) -> Tuple[np.ndarray, np.ndarray]:
    """Per row, the first active fid of each field (+ presence mask) —
    the reference's ``tensor_map`` construction (distributed_algo_abst.h:210-215).
    Returns (rep_fids [N, field_cnt] int32, rep_mask [N, field_cnt] f32).

    One pass over the N x P slots, whatever the layout: every active slot
    (mask on, field in ``[0, field_cnt)``) is keyed by (row, field) — the
    others share one key past the table — and a key keeps the least of its
    slots' numbers (``np.minimum.at``: defined for repeated keys, which an
    indexed assignment is not)."""
    n, p = fids.shape
    fields = np.asarray(fields)
    active = (np.asarray(mask) > 0) & (fields >= 0) & (fields < field_cnt)
    none, dump = n * p, n * field_cnt          # no slot; the inactive's key
    index = np.int32 if max(none, dump) < 2**31 - 1 else np.int64
    key = np.arange(0, dump, field_cnt, dtype=index)[:, None] + fields
    np.putmask(key, ~active, dump)
    first = np.full(dump + 1, none, index)
    np.minimum.at(first, key.reshape(-1), np.arange(none, dtype=index))
    first = first[:dump]
    # (a key no slot wrote reads the 0 behind the last fid)
    rep = np.take(np.append(np.asarray(fids).reshape(-1), 0), first)
    return (rep.astype(np.int32, copy=False).reshape(n, field_cnt),
            (first < none).astype(np.float32).reshape(n, field_cnt))


def init(
    key: jax.Array,
    feature_cnt: int,
    field_cnt: int,
    factor_dim: int,
    hidden: int = 50,
) -> Dict[str, jax.Array]:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w": jnp.zeros((feature_cnt,), jnp.float32),
        # PS lazy-init draws uniform gaussian*sqrt(1/dim) (paramserver.h check_and_find)
        "embed": jax.random.normal(k1, (feature_cnt, factor_dim), jnp.float32)
        / jnp.sqrt(float(factor_dim)),
        "fc1": dense.init(k2, field_cnt * factor_dim, hidden),
        "fc2": dense.init(k3, hidden, 1),
    }


def logits(params: Dict[str, jax.Array], batch: Dict[str, jax.Array]) -> jax.Array:
    vals = batch["vals"] * batch["mask"]
    w = expand_rows(params["w"], batch["fids"])
    wide = jnp.sum(w * vals, axis=-1)

    emb = expand_rows(params["embed"], batch["rep_fids"])        # [B, Fl, D]
    emb = emb * batch["rep_mask"][..., None]                      # absent fields -> 0
    deep_in = emb.reshape(emb.shape[0], -1)                       # [B, Fl*D]
    h = dense.apply(params["fc1"], deep_in, activation=jnp.tanh)
    deep = dense.apply(params["fc2"], h, activation=sigmoid)[:, 0]
    return wide + deep


def make_batch(ds, rep_fids: np.ndarray, rep_mask: np.ndarray) -> Dict[str, np.ndarray]:
    b = ds.batch_dict()
    b["rep_fids"] = rep_fids
    b["rep_mask"] = rep_mask
    return b
