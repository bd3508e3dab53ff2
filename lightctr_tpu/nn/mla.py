"""Multi-head latent attention without positions (DeepSeek-V2's MLA as
Kimi Linear uses it, ``mla_use_nope``), causal inside each packed document.

Keys and values come from one low-rank latent ``c`` per token; a key is
its head's ``d_nope`` channels beside ``d_pe`` channels all heads share
(the "rope" channels of the published layer, carried and not rotated), so
keys are ``d_nope + d_pe`` wide beside values of ``d_v``:

    q = x Wq                              [T, H, d_nope + d_pe]
    [c | k_pe] = x Wkva                   [T, r + d_pe]
    [k_nope | v] = RMSNorm(c) Wkvb        [T, H, d_nope + d_v]
    a = softmax(q [k_nope | k_pe]^T / sqrt(d_nope + d_pe) + mask)

``nn/flash_attention.py`` takes one head width and no document mask, and
``full_attention`` at 8,192 tokens x 32 heads is 8.6 GB of scores.
:func:`segment_attention` is the blocked XLA form: a block of queries at
a time against the keys up to that block's end (what lies above the
diagonal is never multiplied), the block's scores made again in the
backward pass (``jax.checkpoint``), never kept.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from lightctr_tpu.nn.kda import head_groups, rms_norm
from lightctr_tpu.utils.profiling import annotate

BLOCK = 512


def _block(q, k, v, seg_q, seg_k, first):
    """Queries ``q`` [Bq, H, dk] at positions ``first + i`` against keys
    ``k`` [Tk, H, dk], values ``v`` [Tk, H, dv] at positions ``0..Tk``."""
    scores = jnp.einsum("qhd,khd->hqk", q, k) * q.shape[-1] ** -0.5
    pos_q = first + jnp.arange(q.shape[0])
    keep = ((seg_q[:, None] == seg_k[None, :])
            & (jnp.arange(k.shape[0])[None, :] <= pos_q[:, None]))
    probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v)


def segment_attention(q, k, v, seg, block: int = BLOCK):
    """Causal softmax attention in which a token sees its own document
    alone.  ``q``, ``k`` [T, H, dk], ``v`` [T, H, dv], ``seg`` [T]; every
    query sees at least itself, so no row of the softmax is empty."""
    n_tok = q.shape[0]
    out = []
    for first in range(0, n_tok, block):
        last = min(first + block, n_tok)
        out.append(jax.checkpoint(_block, static_argnums=(5,))(
            q[first:last], k[:last], v[:last], seg[first:last], seg[:last],
            first))
    return jnp.concatenate(out, axis=0)


def mixer(p: Dict, x: jax.Array, seg: jax.Array, *, heads: int, d_nope: int,
          d_pe: int, eps: float, block: int = BLOCK, groups: int = 1) -> jax.Array:
    """The MLA token mixer.  ``x`` [B, T, D], ``seg`` [B, T]; ``p``: ``wq``
    [D, H (d_nope + d_pe)], ``wkva`` [D, r + d_pe], ``kv_norm`` [r],
    ``wkvb`` [r, H (d_nope + d_v)], ``wo`` [H d_v, D].  Like KDA's, the
    heads run in ``groups`` runs one after another, each from its slice of
    ``wq``, ``wkvb`` and ``wo``; the latent and the shared key part are
    made once."""
    b, n_tok, _ = x.shape
    rank, hg = p["kv_norm"].shape[0], heads // groups

    def run(args):
        w, latent, k_pe = args
        q = (x @ w["wq"]).reshape(b, n_tok, hg, d_nope + d_pe)
        kv = (latent @ w["wkvb"]).reshape(b, n_tok, hg, -1)
        k = jnp.concatenate(
            [kv[..., :d_nope],
             jnp.broadcast_to(k_pe[:, :, None], (b, n_tok, hg, d_pe))], -1)
        with annotate("seq/mla/attention"):
            o = jax.vmap(lambda *a: segment_attention(*a, block))(
                q, k, kv[..., d_nope:], seg)
        return o.reshape(b, n_tok, -1) @ w["wo"]

    with annotate("seq/mla"):
        kva = x @ p["wkva"]
        latent = rms_norm(kva[..., :rank], p["kv_norm"], eps)
        split = {"wq": head_groups(p["wq"], groups),
                 "wkvb": head_groups(p["wkvb"], groups),
                 "wo": head_groups(p["wo"], groups, 0)}
        parts = jax.lax.map(
            lambda w: jax.checkpoint(run)((w, latent, kva[..., rank:])), split)
        return jnp.sum(parts, axis=0)
