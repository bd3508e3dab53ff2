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
:func:`segment_attention` is a blocked XLA form that skips the blocks the
mask drops: a block of queries at a time against the key blocks from the
one holding its first query's document start up to its own (what lies
above the diagonal or in another document's earlier blocks is never
multiplied), with an online softmax, and a backward pass written out that
makes a visited block's scores again from the saved log-sum-exp.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from lightctr_tpu.nn.kda import head_groups, rms_norm
from lightctr_tpu.utils.profiling import annotate

BLOCK = 512


def _block(q, k, v, seg_q, seg_k, first, key_first=None, window: int = 0):
    """Queries ``q`` [Bq, H, dk] at positions ``first + i`` against keys
    ``k`` [Tk, Hk, dk], values ``v`` [Tk, Hk, dv] at positions ``0..Tk``
    (``key_first + j`` where given).  ``Hk`` divides ``H``: query head
    ``h`` reads key head ``h // (H / Hk)`` (grouped-query attention);
    with ``window`` a key ``window`` or more positions back is masked too."""
    grouped = k.shape[1] != q.shape[1]
    if grouped:
        qg = q.reshape(q.shape[0], k.shape[1], -1, q.shape[-1])
        scores = jnp.einsum("qjgd,kjd->jgqk", qg, k).reshape(
            q.shape[1], q.shape[0], k.shape[0]) * q.shape[-1] ** -0.5
    else:
        scores = jnp.einsum("qhd,khd->hqk", q, k) * q.shape[-1] ** -0.5
    pos_q = first + jnp.arange(q.shape[0])
    same = seg_q[:, None] == seg_k[None, :]
    pos_k = jnp.arange(k.shape[0])
    if key_first is not None:
        pos_k = key_first + pos_k
    keep = same & (pos_k[None, :] <= pos_q[:, None])
    if window:
        keep = keep & (pos_q[:, None] - pos_k[None, :] < window)
    probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), axis=-1)
    if grouped:
        pg = probs.reshape(k.shape[1], -1, q.shape[0], k.shape[0])
        return jnp.einsum("jgqk,kjd->qjgd", pg, v).reshape(
            q.shape[0], q.shape[1], v.shape[-1])
    return jnp.einsum("hqk,khd->qhd", probs, v)


def document_starts(seg: jax.Array) -> jax.Array:
    """The index of each token's packed document's first token: ``seg``
    [..., T] -> int32 [..., T] (a document is a run of one id)."""
    t = jnp.arange(seg.shape[-1], dtype=jnp.int32)
    prev = jnp.concatenate(
        [jnp.full(seg.shape[:-1] + (1,), -3, seg.dtype), seg[..., :-1]], axis=-1)
    return jax.lax.cummax(jnp.where(seg != prev, t, 0), axis=seg.ndim - 1)


def _one_row(seg: jax.Array) -> jax.Array:
    """``seg`` [B, T] as one sequence [B T] in which no document of a row
    is another row's (or [T] as it is)."""
    if seg.ndim == 1:
        return seg
    rows = seg.shape[0]
    return (seg * rows + jnp.arange(rows, dtype=seg.dtype)[:, None]).reshape(-1)


def _fit(n_tok: int, block: int) -> int:
    """The query and key block: ``block``, or the whole sequence where
    ``block`` does not divide it."""
    return block if n_tok % block == 0 else n_tok


def _reach(seg: jax.Array, block: int) -> jax.Array:
    """Per query block of the one-row ``seg``, the first key block it
    visits: the one holding its first query's document start.  A later
    query of the block starts its document there or after, so no key it
    sees lies before."""
    return document_starts(seg)[::block] // block


def visited_pairs(seg: jax.Array, block: int = BLOCK) -> jax.Array:
    """The (query, key) pairs :func:`segment_attention` multiplies a head
    over ``seg`` [B, T] or [T], int32: its visited blocks times
    ``block**2``, from the same bounds."""
    flat = _one_row(seg)
    block = _fit(flat.shape[0], block)
    first = _reach(flat, block)
    blocks = jnp.sum(jnp.arange(first.shape[0], dtype=jnp.int32) + 1 - first)
    return blocks * jnp.int32(block * block)


def _take(x, i):
    return jax.lax.dynamic_index_in_dim(x, i, keepdims=False)


def _scores(qi, kj, seg_i, seg_j, i, j, block):
    """Query block ``i`` [Bq, Hk, G, dk] against key block ``j`` [Bk, Hk,
    dk]: [Hk, G, Bq, Bk], ``-inf`` outside the mask (same document, key
    not after query)."""
    s = jnp.einsum("qjgd,kjd->jgqk", qi, kj) * qi.shape[-1] ** -0.5
    at = jnp.arange(block)
    keep = ((seg_i[:, None] == seg_j[None, :])
            & (j * block + at[None, :] <= i * block + at[:, None]))
    return jnp.where(keep, s, -jnp.inf)


def _blocks(q, k, v, seg, block):
    """``q`` as [n, block, Hk, G, dk], ``k``, ``v``, ``seg`` as [n, block,
    ...]: ``n`` blocks of ``block`` tokens."""
    hk = k.shape[1]
    qg = q.reshape(q.shape[0], hk, -1, q.shape[-1])
    return tuple(x.reshape((-1, block) + x.shape[1:]) for x in (qg, k, v, seg))


def _forward(q, k, v, seg, block):
    qb, kb, vb, sb = _blocks(q, k, v, seg, block)
    hk, g, dv = k.shape[1], q.shape[1] // k.shape[1], v.shape[-1]
    first = _reach(seg, block)

    def one(i):
        qi, si = _take(qb, i), _take(sb, i)

        def visit(j, carry):
            top, total, acc = carry
            s = _scores(qi, _take(kb, j), si, _take(sb, j), i, j, block)
            new = jnp.maximum(top, jnp.max(s, axis=-1))
            # a row that has seen no key yet keeps exp(-inf) = 0 throughout
            ref = jnp.where(new == -jnp.inf, 0.0, new)
            p = jnp.exp(s - ref[..., None])
            kept = jnp.exp(top - ref)
            return (new, total * kept + jnp.sum(p, axis=-1),
                    acc * kept[..., None] + jnp.einsum("jgqk,kjd->jgqd", p, _take(vb, j)))

        zero = jnp.zeros((hk, g, block), q.dtype)
        top, total, acc = jax.lax.fori_loop(
            _take(first, i), i + 1, visit,
            (zero - jnp.inf, zero, jnp.zeros((hk, g, block, dv), q.dtype)))
        return acc / total[..., None], top + jnp.log(total)

    o, lse = jax.lax.map(one, jnp.arange(qb.shape[0]))     # [n, Hk, G, Bq, ...]
    return jnp.moveaxis(o, 3, 1).reshape(q.shape[:2] + (dv,)), (lse, first)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _attend(q, k, v, seg, block):
    return _forward(q, k, v, seg, block)[0]


def _attend_fwd(q, k, v, seg, block):
    out, (lse, first) = _forward(q, k, v, seg, block)
    return out, (q, k, v, seg, out, lse, first)


def _attend_bwd(block, res, d_out):
    q, k, v, seg, out, lse, first = res
    qb, kb, vb, sb = _blocks(q, k, v, seg, block)
    hk = k.shape[1]

    def by_block(x):                   # [T, H, ...] -> [n, Hk, G, Bq, ...]
        x = x.reshape((-1, block, hk, q.shape[1] // hk) + x.shape[2:])
        return jnp.moveaxis(x, 1, 3)

    dob, delta = by_block(d_out), by_block(jnp.sum(d_out * out, axis=-1))
    scale = q.shape[-1] ** -0.5

    def one(carry, i):
        qi, si, doi = _take(qb, i), _take(sb, i), _take(dob, i)
        lse_i, delta_i = _take(lse, i)[..., None], _take(delta, i)[..., None]

        def visit(j, c):
            dq, dk, dv = c
            kj, vj = _take(kb, j), _take(vb, j)
            p = jnp.exp(_scores(qi, kj, si, _take(sb, j), i, j, block) - lse_i)
            ds = p * (jnp.einsum("jgqd,kjd->jgqk", doi, vj) - delta_i) * scale
            dk = jax.lax.dynamic_update_index_in_dim(
                dk, _take(dk, j) + jnp.einsum("jgqk,qjgd->kjd", ds, qi), j, 0)
            dv = jax.lax.dynamic_update_index_in_dim(
                dv, _take(dv, j) + jnp.einsum("jgqk,jgqd->kjd", p, doi), j, 0)
            return dq + jnp.einsum("jgqk,kjd->qjgd", ds, kj), dk, dv

        dq, dk, dv = jax.lax.fori_loop(_take(first, i), i + 1, visit,
                                       (jnp.zeros_like(qi),) + carry)
        return (dk, dv), dq

    (dk, dv), dq = jax.lax.scan(one, (jnp.zeros_like(kb), jnp.zeros_like(vb)),
                                jnp.arange(qb.shape[0]))
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape), None


_attend.defvjp(_attend_fwd, _attend_bwd)


def segment_attention(q, k, v, seg, block: int = BLOCK):
    """Causal softmax attention in which a token sees its own document
    alone.  ``q`` [T, H, dk], ``k`` [T, Hk, dk], ``v`` [T, Hk, dv] with
    ``Hk`` dividing ``H`` (query head ``h`` reads key head ``h // (H /
    Hk)``), ``seg`` [T] — or each with a leading batch axis, the rows run
    as one sequence whose documents are each row's own.  Every query sees
    at least itself, so no row of the softmax is empty.  A block of
    ``block`` queries visits the key blocks from :func:`_reach`'s up to
    its own (:func:`visited_pairs` counts them); the result is the masked
    softmax's, up to the order of the float sums."""
    lead = seg.shape
    flat = _one_row(seg)
    q, k, v = (x.reshape(flat.shape + x.shape[seg.ndim:]) for x in (q, k, v))
    out = _attend(q, k, v, flat, _fit(flat.shape[0], block))
    return out.reshape(lead + out.shape[1:])


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
            o = segment_attention(q, k, kv[..., d_nope:], seg, block)
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
