"""Grouped-query attention with rotary positions over packed documents, in
a window or whole (Mellum 2's two layer kinds).

``H`` query heads share ``Hk`` key/value heads (query head ``h`` reads
KV head ``h // (H / Hk)``); q and k are rotated (RoPE, rotate-half over
all ``d`` channels) by the token's offset inside its own document, which
restarts at every packed document's first token:

    q = x Wq [T, H, d]    k = x Wk, v = x Wv [T, Hk, d]
    q, k <- q cos + rotate_half(q) sin     (cos, sin of pos * inv_freq, times a factor)
    o_i = sum_j softmax_j(q_i k_j / sqrt(d)) v_j   over j: same document, j <= i, and i - j < W in a window layer
    Attn(x) = concat_heads(o) Wo

``inv_freq`` is the default rope's ``theta^(-2j/d)`` or HF's YaRN
(``yarn_inv_freq``); YaRN multiplies cos and sin by its attention factor.

Both cores are blocked XLA forms: a block of queries at a time, its
scores made again in the backward pass.  A full layer's block reads the
key blocks from its first query's document start up to its own
(``mla.segment_attention`` itself); a window layer's block of ``B``
queries reads only the ``B + ceil((W-1)/B) B`` keys its window can reach
(:func:`window_attention`), so its work is O(T W), not O(T^2).  The heads
run a KV group at a time.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from lightctr_tpu.nn.mla import BLOCK, _block, document_starts, segment_attention
from lightctr_tpu.utils.profiling import annotate


def default_inv_freq(dim: int, theta: float) -> np.ndarray:
    """``theta^(-2j/dim)``, ``j < dim/2`` (float64, numpy)."""
    return theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)


def yarn_range(dim: int, theta: float, original: int, beta_fast: float,
               beta_slow: float) -> Tuple[int, int]:
    """HF's ``find_correction_range`` with ``truncate``: the channel pairs
    below ``low`` keep their frequency, those above ``high`` are
    interpolated by ``factor``."""
    def corr(rotations):
        return (dim * math.log(original / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    return max(math.floor(corr(beta_fast)), 0), min(math.ceil(corr(beta_slow)), dim - 1)


def yarn_inv_freq(dim: int, theta: float, factor: float, original: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """HF's ``_compute_yarn_parameters``: ``base_j / factor`` where the
    ramp is 1, ``base_j`` where it is 0, linear between ``low`` and
    ``high``."""
    low, high = yarn_range(dim, theta, original, beta_fast, beta_slow)
    base = default_inv_freq(dim, theta)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return base / factor * ramp + base * (1.0 - ramp)


def positions(seg: jax.Array) -> jax.Array:
    """A token's offset inside its packed document: ``seg`` [..., T] ->
    int32 [..., T], 0 at every document's first token."""
    return jnp.arange(seg.shape[-1], dtype=jnp.int32) - document_starts(seg)


def rope_table(pos: jax.Array, inv_freq: np.ndarray, factor: float = 1.0):
    """``(cos, sin)`` [..., T, d] of ``pos`` [..., T] (rotate-half layout:
    the ``d/2`` angles twice), each times ``factor``."""
    angles = pos[..., None].astype(jnp.float32) * jnp.asarray(inv_freq, jnp.float32)
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.cos(angles) * factor, jnp.sin(angles) * factor


def rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """``x`` [..., T, heads, d] rotated: ``x cos + rotate_half(x) sin``."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[..., None, :] + turned * sin[..., None, :]


def window_attention(q, k, v, seg, window: int, block: int = BLOCK):
    """:func:`segment_attention` with a window: key ``j`` is seen by query
    ``i`` only if ``i - j < window`` besides.  A block of ``block`` queries
    starting at ``first`` reads the keys ``[first - reach, first + block)``
    alone, ``reach = ceil((window - 1) / block) * block`` (or ``T -
    block``, if less): the keys are
    padded in front by ``reach`` positions of a document no query belongs
    to, so every block's slice has one shape and the blocks run as one
    ``lax.map``.  ``q`` [T, H, dk], ``k`` [T, Hk, dk], ``v`` [T, Hk, dv]."""
    n_tok = q.shape[0]
    if n_tok % block:
        block = n_tok
    # (no block reaches further back than the sequence's first token)
    reach = min(-(-(window - 1) // block) * block, n_tok - block)

    def front(x, value=0):
        return jnp.pad(x, ((reach, 0),) + ((0, 0),) * (x.ndim - 1),
                       constant_values=value)

    kp, vp, segp = front(k), front(v), front(seg, -2)

    def one(i):
        first = i * block

        def cut(x, n, at):
            return jax.lax.dynamic_slice_in_dim(x, at, n, axis=0)

        return _block(cut(q, block, first), cut(kp, block + reach, first),
                      cut(vp, block + reach, first), cut(seg, block, first),
                      cut(segp, block + reach, first), first,
                      key_first=first - reach, window=window)

    out = jax.lax.map(jax.checkpoint(one), jnp.arange(n_tok // block))
    return out.reshape((n_tok,) + out.shape[2:])


def attended_pairs(seg: jax.Array, window: int = 0) -> jax.Array:
    """The (query, key) pairs inside the mask, int32: a token sees its
    offset + 1 keys of its document, at most ``window``; padding
    (``seg`` < 0) sees none."""
    seen = positions(seg) + 1
    if window:
        seen = jnp.minimum(seen, window)
    return jnp.sum(jnp.where(seg >= 0, seen, 0), dtype=jnp.int32)


def mixer(p: Dict, x: jax.Array, seg: jax.Array, cos: jax.Array,
          sin: jax.Array, *, heads: int, kv_heads: int, window: int = 0,
          block: int = BLOCK) -> jax.Array:
    """The attention of the module docstring.  ``x`` [B, T, D], ``seg``
    [B, T], ``cos``/``sin`` [B, T, d]; ``p``: ``wq`` [D, H d], ``wk``,
    ``wv`` [D, Hk d], ``wo`` [H d, D], no biases.  ``window`` 0 is a full
    layer.  The projections take all heads at once; the cores run the
    ``Hk`` KV groups one after another (``lax.map``), a group's scores
    made again in the backward pass (the window's by a ``jax.checkpoint``
    a group, the full core's by its own backward pass: the layer's
    checkpoint already makes its forward again once), under a scope of
    their own (``seq/gqa/window`` or ``seq/gqa/full``) that stays near the
    front of the name stack: a trace's label keeps its first 120
    characters."""
    b, n_tok, _ = x.shape
    dim = cos.shape[-1]

    def core(args):
        q, k, v = args
        if window:
            return jax.vmap(lambda *a: window_attention(*a, window, block))(q, k, v, seg)
        return segment_attention(q, k, v, seg, block)

    def groups(y):                           # [B, T, H, d] -> [Hk, B, T, H / Hk, d]
        return jnp.moveaxis(y.reshape(b, n_tok, kv_heads, -1, dim), 2, 0)

    with annotate("seq/gqa"):
        q = groups(rope((x @ p["wq"]).reshape(b, n_tok, heads, dim), cos, sin))
        k = groups(rope((x @ p["wk"]).reshape(b, n_tok, kv_heads, dim), cos, sin))
        v = groups((x @ p["wv"]).reshape(b, n_tok, kv_heads, dim))
        # (a nested scope's name adds to the stack: it reads seq/gqa/window)
        with annotate("window" if window else "full"):
            o = jax.lax.map(jax.checkpoint(core) if window else core, (q, k, v))
        return jnp.moveaxis(o, 0, 2).reshape(b, n_tok, -1) @ p["wo"]
