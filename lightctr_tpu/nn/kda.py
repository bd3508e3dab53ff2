"""Kimi Delta Attention: the gated delta rule with a decay per channel
(arXiv:2510.26692, "Kimi Linear"), chunked, for packed documents.

Per head, with ``alpha_t = exp(g_t)`` in (0, 1) per key channel:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t / sqrt(d_k)            S = 0 at a document's first token

``nn/lstm.py`` walks a sequence a token a ``lax.scan`` step; 8,192 tokens
of a 128 x 128 state would be 8,192 dependent steps of rank-one updates.
:func:`chunked_delta_rule` walks it a chunk of ``C`` tokens a step
(docs/KERNELS.md, "The chunked scan").  With ``G_t`` the decay's running
log-sum inside a chunk and ``S_0`` the state the chunk starts from, write
``S_t = Diag(alpha_t) S_{t-1} + k_t n_t^T``; then

    (I + Diag(beta) A) N = Diag(beta) (V - K+ S_0)       A_ts = sum_c k_tc k_sc exp(G_tc - G_sc),  s < t
    O = Q+ S_0 + P N                                      P_ts = sum_c q_tc k_sc exp(G_tc - G_sc),  s <= t
    S_C = Diag(exp(G_C)) S_0 + (K exp(G_C - G))^T N       K+ = K exp(G),  Q+ = Q exp(G)

Every exponent is a decay between two tokens of one chunk taken as a
difference before ``exp``, so none is positive whatever the decay's
strength (``K exp(-G)`` as a factor of its own overflows float32 at the
published ``A_log`` range).  A document boundary is a mask: pairs of
tokens of two documents drop out of ``A`` and ``P``, and ``S_0`` reaches
only the tokens of the document the chunk began in.

Three phases: ``A`` and ``P`` a chunk at a time (``lax.map``; the pairwise
decay is ``[C, C, H, d_k]``, 67 MB a chunk at the published sizes, and is
made again in the backward pass, never kept); one batched unit-triangular
solve for all chunks at once, which does not need ``S_0`` (``U = T V``,
``W = T K+`` with ``T = (I + Diag(beta) A)^-1 Diag(beta)``); then the
``lax.scan`` over chunks that carries ``S``: three ``C x d x d`` products
and ``P N`` a head.  The backward pass is autodiff through this form: the
scan keeps ``S_0`` and ``N`` of every chunk (268 + 134 MB a layer at 8,192
tokens, 32 heads).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from lightctr_tpu.utils.profiling import annotate

CHUNK = 64


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def l2_norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def short_conv(x: jax.Array, w: jax.Array, seg: jax.Array) -> jax.Array:
    """Depthwise causal convolution over time that does not reach across a
    document boundary: ``y_t = sum_j w[:, K-1-j] x_{t-j}`` over the taps
    ``j`` whose token lies in ``t``'s own document.  ``x`` [T, C], ``w``
    [C, K], ``seg`` [T] (a document's number, the same for all its
    tokens)."""
    taps = w.shape[1]
    y = x * w[:, taps - 1]
    for j in range(1, taps):
        xj = jnp.pad(x, ((j, 0), (0, 0)))[:-j]
        same = jnp.pad(seg, (j, 0), constant_values=-1)[:-j] == seg
        y = y + jnp.where(same[:, None], xj, 0.0) * w[:, taps - 1 - j]
    return y


def _pairwise(q, k, G, same):
    """One chunk's ``A`` (strictly lower) and ``P`` (lower, diagonal
    included), ``[H, C, C]`` each: ``q``, ``k``, ``G`` ``[C, H, d_k]``,
    ``same`` ``[C, C]`` (the two tokens share a document)."""
    c = q.shape[0]
    t, s = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    keep = (same & (s <= t))[:, :, None, None]
    decay = jnp.exp(jnp.where(keep, G[:, None] - G[None, :], -jnp.inf))
    a = jnp.einsum("thc,shc,tshc->hts", k, k, decay)
    p = jnp.einsum("thc,shc,tshc->hts", q, k, decay)
    return a * (s < t), p


def _delta_rule_one(q, k, v, g, beta, seg, chunk):
    """One sequence: ``q``, ``k``, ``g`` [T, H, d_k], ``v`` [T, H, d_v],
    ``beta`` [T, H], ``seg`` [T]; ``T`` a multiple of ``chunk``."""
    n_tok, heads, dk = q.shape
    nc = n_tok // chunk
    prev = jnp.pad(seg, (1, 0), constant_values=-1)[:-1]
    # a document's first token starts from S = 0: its decay multiplies nothing
    g = jnp.where((seg != prev)[:, None, None], 0.0, g)

    def chunks(x):
        return x.reshape((nc, chunk) + x.shape[1:])

    qc, kc, vc, bc, sc = map(chunks, (q, k, v, beta, seg))
    G = jnp.cumsum(chunks(g), axis=1)                      # [nc, C, H, dk]
    same = sc[:, :, None] == sc[:, None, :]                # [nc, C, C]
    before = chunks(prev)[:, 0]                            # the document S_0 belongs to
    carried = (sc == before[:, None])                      # [nc, C]: S_0 reaches the token
    to_end = (sc == sc[:, -1:])                            # [nc, C]: the token reaches S_C

    a, p = jax.lax.map(
        lambda args: jax.checkpoint(_pairwise)(*args), (qc, kc, G, same))
    grow = jnp.exp(G)                                      # decay from the chunk's start
    k_in = kc * grow * carried[:, :, None, None]
    q_in = qc * grow * carried[:, :, None, None]
    k_out = kc * jnp.exp(G[:, -1:] - G) * to_end[:, :, None, None]
    keep = jnp.exp(G[:, -1]) * (sc[:, -1] == before)[:, None, None]    # [nc, H, dk]

    bh = bc.transpose(0, 2, 1)[..., None]                  # [nc, H, C, 1]
    lower = jnp.eye(chunk, dtype=q.dtype) + bh * a
    rhs = bh * jnp.concatenate(
        [vc.transpose(0, 2, 1, 3), k_in.transpose(0, 2, 1, 3)], axis=-1)
    x = jax.scipy.linalg.solve_triangular(
        lower, rhs, lower=True, unit_diagonal=True)
    u, w = x[..., :v.shape[-1]], x[..., v.shape[-1]:]      # [nc, H, C, dv], [nc, H, C, dk]

    def step(state, xs):
        u_i, w_i, p_i, q_i, k_i, keep_i = xs
        n = u_i - jnp.einsum("htk,hkv->htv", w_i, state)
        o = (jnp.einsum("thk,hkv->htv", q_i, state)
             + jnp.einsum("hts,hsv->htv", p_i, n))
        state = keep_i[..., None] * state + jnp.einsum("shk,hsv->hkv", k_i, n)
        return state, o

    state0 = jnp.zeros((heads, dk, v.shape[-1]), q.dtype)
    _, o = jax.lax.scan(step, state0, (u, w, p, q_in, k_out, keep))
    # [nc, H, C, dv] -> [T, H, dv]
    return o.transpose(0, 2, 1, 3).reshape(n_tok, heads, -1) * dk ** -0.5


def chunked_delta_rule(q, k, v, g, beta, seg, chunk: int = CHUNK):
    """``o`` [B, T, H, d_v] of the recurrence in the module docstring.
    ``q``, ``k`` [B, T, H, d_k], ``v`` [B, T, H, d_v], ``g`` [B, T, H,
    d_k] the decay's logarithm (<= 0), ``beta`` [B, T, H], ``seg`` [B, T]
    int32, constant over a document and different for neighbours.  A
    ``T`` that ``chunk`` does not divide is padded with tokens of a
    document of their own, which no other token sees."""
    n_tok = q.shape[1]
    pad = -n_tok % chunk
    if pad:
        def grown(x, value=0):
            return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2),
                           constant_values=value)
        q, k, v, g, beta = map(grown, (q, k, v, g, beta))
        seg = jnp.pad(seg, ((0, 0), (0, pad)), constant_values=-2)
    o = jax.vmap(lambda *a: _delta_rule_one(*a, chunk))(q, k, v, g, beta, seg)
    return o[:, :n_tok]


def head_groups(w: jax.Array, groups: int, axis: int = -1) -> jax.Array:
    """``w`` with the axis that runs over ``heads * width`` split into
    ``groups`` runs of whole heads, the groups leading: ``[groups, ...]``."""
    axis %= w.ndim
    shape = w.shape[:axis] + (groups, w.shape[axis] // groups) + w.shape[axis + 1:]
    return jnp.moveaxis(w.reshape(shape), axis, 0)


def mixer(p: Dict, x: jax.Array, seg: jax.Array, *, heads: int,
          eps: float, chunk: int = CHUNK, groups: int = 1) -> jax.Array:
    """The KDA token mixer.  ``x`` [B, T, D], ``seg`` [B, T]; ``p``: ``wq``,
    ``wk``, ``wv`` [D, H d], their short convolutions ``conv_q/k/v`` [H d,
    K], the decay's low-rank gate ``f_down`` [D, r], ``f_up`` [r, H d] with
    ``dt_bias`` [H d] and ``a_log`` [H], ``wb`` [D, H], the output gate
    ``g_down`` [D, r], ``g_up`` [r, H d], the head norm ``o_norm`` [d] and
    ``wo`` [H d, D].

    Heads do not meet before ``wo``, so the mixer runs ``groups`` runs of
    ``heads / groups`` heads one after another (``lax.map``), each from
    its slice of the weights to its part of the output, and makes a run's
    activations again in the backward pass: at 8,192 tokens x 32 heads x
    128 the twenty-odd ``[T, H d]`` intermediates of one pass are 2.7 GB,
    of a run of 8 heads 0.7."""
    b, n_tok, _ = x.shape
    hg = heads // groups
    conv = jax.vmap(short_conv, in_axes=(0, None, 0))

    def heads_of(y):
        return y.reshape(b, n_tok, hg, -1)

    def run(args):
        w, f_low, g_low = args
        q = l2_norm(heads_of(jax.nn.silu(conv(x @ w["wq"], w["conv_q"], seg))))
        k = l2_norm(heads_of(jax.nn.silu(conv(x @ w["wk"], w["conv_k"], seg))))
        v = heads_of(jax.nn.silu(conv(x @ w["wv"], w["conv_v"], seg)))
        g = -jnp.exp(w["a_log"])[:, None] * heads_of(jax.nn.softplus(
            f_low @ w["f_up"] + w["dt_bias"]))
        beta = jax.nn.sigmoid(x @ w["wb"])
        with annotate("seq/kda/scan"):
            o = chunked_delta_rule(q, k, v, g, beta, seg, chunk)
        gate = jax.nn.sigmoid(heads_of(g_low @ w["g_up"]))
        o = rms_norm(o, p["o_norm"], eps) * gate
        return o.reshape(b, n_tok, -1) @ w["wo"]

    with annotate("seq/kda"):
        split = {k: head_groups(p[k], groups) for k in (
            "wq", "wk", "wv", "f_up", "dt_bias", "a_log", "wb", "g_up")}
        split.update({k: head_groups(p[k], groups, 0) for k in (
            "conv_q", "conv_k", "conv_v", "wo")})
        # the gates' shared low-rank halves, once for all runs
        f_low, g_low = x @ p["f_down"], x @ p["g_down"]
        parts = jax.lax.map(
            lambda w: jax.checkpoint(run)((w, f_low, g_low)), split)
        return jnp.sum(parts, axis=0)
