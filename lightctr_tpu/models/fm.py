"""Factorization Machine — TPU-native.

Capability parity with ``FM_Algo_Abst`` + ``Train_FM_Algo``
(``fm_algo_abst.h:37-172``, ``train/train_fm_algo.cpp``), re-designed for XLA:
the reference's per-row sumVX trick (train_fm_algo.cpp:68-88, an O(k*nnz)
reformulation of the pairwise interaction) *is* the right formulation on TPU
too, but computed batched:

    vx      = V[fids] * vals[..., None]          # gather -> [B, P, k]
    sumvx   = sum_p vx                           # [B, k]
    pred    = W[fids]·vals + 0.5 * (|sumvx|^2 - sum_p |vx|^2)

The backward pass (hand-derived at train_fm_algo.cpp:90-117) falls out of
``jax.grad`` as a fused gather/scatter-add program.  Init matches
fm_algo_abst.h:53-67: W zero, V ~ N(0, 1) / sqrt(k).

Note: the reference folds W's L2 term into V's gradient through its shared
``gradW`` scalar (train_fm_algo.cpp:110-115) — an artifact of code reuse, not
of the model; we regularize W and V independently (the textbook objective).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from lightctr_tpu.ops.sparse_kernels import expand_rows


def init(key: jax.Array, feature_cnt: int, factor_cnt: int) -> Dict[str, jax.Array]:
    """W zero-init, V ~ N(0, 1/k) (fm_algo_abst.h:53-67)."""
    return {
        "w": jnp.zeros((feature_cnt,), jnp.float32),
        "v": jax.random.normal(key, (feature_cnt, factor_cnt), jnp.float32)
        / jnp.sqrt(float(factor_cnt)),
    }


def logits(params: Dict[str, jax.Array], batch: Dict[str, jax.Array]) -> jax.Array:
    """Batched sumVX forward (train_fm_algo.cpp:63-88)."""
    return logits_with_l2(params, batch)[0]


def logits_with_l2(params: Dict[str, jax.Array], batch: Dict[str, jax.Array]):
    """Forward plus the touched-row L2 penalty from the SAME gathers —
    computing the penalty separately would re-read W and V rows (25% of the
    step on a bandwidth-bound backend)."""
    vals = batch["vals"] * batch["mask"]          # [B, P]; padding already 0
    mask = batch["mask"]
    w = expand_rows(params["w"], batch["fids"])                 # [B, P]
    linear = jnp.sum(w * vals, axis=-1)                          # [B]
    v = expand_rows(params["v"], batch["fids"])                  # [B, P, k]
    vx = v * vals[..., None]                                     # [B, P, k]
    sumvx = jnp.sum(vx, axis=1)                                  # [B, k]
    second = 0.5 * (
        jnp.sum(sumvx * sumvx, axis=-1) - jnp.sum(vx * vx, axis=(1, 2))
    )
    l2 = 0.5 * (jnp.sum(w * w * mask) + jnp.sum(v * v * mask[..., None]))
    return linear + second, l2


def densify(arrays: Dict, feature_cnt: int) -> Dict:
    """Host-side one-time densification of a (small-vocab) sparse batch.

    On TPU the FLOPs live on the MXU; the gather/scatter formulation above
    spends its time in scatter-add backward instead (measured 20.6 ms/step vs
    0.46 ms/step dense at F=8245, B=1000 on v5e).  For full-batch training on
    a compacted vocabulary the batch is constant, so we materialize it ONCE as
    dense matrices and the whole train step becomes three [B,F]x[F,k] matmuls
    and their transposes — no scatters anywhere.

    Exact-parity construction (matches the per-slot semantics of
    ``logits_with_l2`` even when a row repeats a fid):
      x[i,f]   = sum of vals over slots with that fid   (linear & sumvx terms
                 are linear in x, so merging slots is exact)
      x2[i,f]  = sum of vals^2 over slots               (the self-interaction
                 subtraction is per-slot, NOT (sum vals)^2)
      cnt[f]   = number of touched slots                (per-occurrence L2,
                 train_fm_algo.cpp:108-115)

    Memory: 2 * B * F floats — caller's job to check it fits (bench data:
    1000 x 8245 = 33 MB fp32).
    """
    import numpy as np

    fids = np.asarray(arrays["fids"])
    vals = np.asarray(arrays["vals"]) * np.asarray(arrays["mask"])
    mask = np.asarray(arrays["mask"]) > 0
    if mask.any():
        lo, hi = fids[mask].min(), fids[mask].max()
        if lo < 0 or hi >= feature_cnt:
            raise ValueError(
                f"fid out of range [{lo}, {hi}] for feature_cnt={feature_cnt}; "
                "negative/overflow ids would scatter into the wrong dense column"
            )
    n, p = fids.shape
    x = np.zeros((n, feature_cnt), np.float32)
    x2 = np.zeros((n, feature_cnt), np.float32)
    cnt = np.zeros((feature_cnt,), np.float32)
    rows = np.broadcast_to(np.arange(n)[:, None], (n, p))
    np.add.at(x, (rows[mask], fids[mask]), vals[mask])
    np.add.at(x2, (rows[mask], fids[mask]), vals[mask] ** 2)
    np.add.at(cnt, fids[mask], 1.0)
    return {
        "x": x,
        "x2": x2,
        "cnt": cnt,
        "labels": np.asarray(arrays["labels"]),
    }


def dense_logits(params: Dict[str, jax.Array], batch: Dict[str, jax.Array]) -> jax.Array:
    return dense_logits_with_l2(params, batch)[0]


def dense_logits_with_l2(params: Dict[str, jax.Array], batch: Dict[str, jax.Array]):
    """Matmul formulation of ``logits_with_l2`` over a densified batch.

    z = x @ w + 0.5 * (|x @ V|^2 - x2 @ (V*V) summed)      — all MXU matmuls;
    the backward is x^T @ (...) matmuls instead of scatter-adds."""
    w, v = params["w"], params["v"]
    linear = batch["x"] @ w                                   # [B]
    sumvx = batch["x"] @ v                                    # [B, k]
    self_term = batch["x2"] @ (v * v)                         # [B, k]
    second = 0.5 * (jnp.sum(sumvx * sumvx, -1) - jnp.sum(self_term, -1))
    l2 = 0.5 * (batch["cnt"] @ (w * w) + batch["cnt"] @ jnp.sum(v * v, -1))
    return linear + second, l2


def l2_penalty(params: Dict[str, jax.Array], batch: Dict[str, jax.Array]) -> jax.Array:
    """L2 on the *touched* rows only, matching the reference which adds
    ``L2Reg_ratio * W[fid]`` per occurrence (train_fm_algo.cpp:108-115) rather
    than decaying the whole table."""
    vals_mask = batch["mask"]
    w = expand_rows(params["w"], batch["fids"])
    v = expand_rows(params["v"], batch["fids"])
    return 0.5 * (
        jnp.sum(w * w * vals_mask) + jnp.sum(v * v * vals_mask[..., None])
    )
