"""A routed expert layer that is told which experts it holds.

The router scores every token against all ``E`` experts of the layer and
picks ``top_k`` of them; this chip holds experts ``first .. first +
n_held`` and computes their part of the result.  Two routers:

    "sigmoid":  s = sigmoid(x Wr);  E(t) = top_k(s + bias);  w_e = scaling s_e / sum_{e' in E(t)} s_e'
    "softmax":  s = softmax(x Wr);  E(t) = top_k(s);         w_e = s_e / sum_{e' in E(t)} s_e'
    y_t = [Shared(x_t)] + sum_{e in E(t), first <= e < first + n_held} w_e Expert_e(x_t)

(the first is DeepSeek-V3's auxiliary-loss-free router, with a selection
bias added for the pick and not for the weight, as Kimi Linear configures
it; the second the softmax router with ``norm_topk_prob``, as Mellum 2
does, with no shared expert).  The weights are renormalised over all the
picked experts, never over the held ones alone.

What the absent experts would add is left out: under expert parallelism
their chips add it (tests/test_kimi_linear.py and tests/test_mellum.py sum
the shares back to the whole layer, one test a router).  Nothing is
dropped: every assignment to a held expert is computed whatever the
imbalance, with static shapes.

The grouped product (docs/KERNELS.md, "The grouped expert product"): the
``N top_k`` assignments are sorted by held expert (the others behind
them), each expert's run is laid out in tiles of ``tile`` rows — a tile
belongs to one expert, an expert's last tile is part empty — and a loop
over the tiles **that are in use** (a dynamic trip count: the worst case
of ``N min(top_k, n_held) / tile + n_held`` tiles is a bound on index
arrays, never a bound on work) gathers the tile's token rows, multiplies
them through that expert's SwiGLU and adds the weighted result to the
tokens' rows.  The backward pass is written out (``jax.custom_vjp``): a
loop with a dynamic trip count has no transpose, and autodiff of a
``scan`` over the worst case would add a whole ``[n_held, D, F]`` buffer
to each weight's gradient a tile.  It walks the same tiles, recomputes
the tile's activations, and adds into the touched expert's slice of the
weight gradients in place.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from lightctr_tpu.utils.profiling import annotate

TILE = 128


def swiglu(p: Dict, x: jax.Array, block: int = 0) -> jax.Array:
    """``W_down(silu(x W_gate) * x W_up)`` over ``x`` [N, D]; with ``block``
    (a divisor of ``N``) a block of rows at a time, each made again in the
    backward pass: a dense layer's 8,192 x 9,216 activations are 0.3 GB a
    piece, and a pass and its transpose keep six."""
    def one(rows):
        return (jax.nn.silu(rows @ p["w_gate"]) * (rows @ p["w_up"])) @ p["w_down"]

    if not block or x.shape[0] <= block:
        return one(x)
    y = jax.lax.map(jax.checkpoint(one), x.reshape(-1, block, x.shape[-1]))
    return y.reshape(x.shape)


def route(x: jax.Array, w_router: jax.Array, bias, top_k: int,
          scaling: float, router: str = "sigmoid") -> Tuple[jax.Array, jax.Array]:
    """``(experts [N, top_k] int32, weights [N, top_k])`` over all the
    router's experts (``router``: the module docstring's two; the softmax
    one takes no ``bias``)."""
    if router == "softmax":
        s = jax.nn.softmax(x @ w_router, axis=-1)
        _, idx = jax.lax.top_k(s, top_k)
        picked = jnp.take_along_axis(s, idx, axis=-1)
        return idx, scaling * picked / jnp.sum(picked, axis=-1, keepdims=True)
    s = jax.nn.sigmoid(x @ w_router)
    _, idx = jax.lax.top_k(s + bias, top_k)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    return idx, scaling * picked / jnp.sum(picked, axis=-1, keepdims=True)


def tile_plan(idx: jax.Array, weights: jax.Array, first: int, n_held: int,
              tile: int):
    """The assignments to held experts, laid out in tiles.  Returns
    ``(tok [M, tile], wt [M, tile], expert [M], n_tiles, counts
    [n_held])``: tile ``i < n_tiles`` holds rows of local expert
    ``expert[i]``, row ``j`` is token ``tok[i, j]`` with weight ``wt[i,
    j]`` (0 on a tile's empty rows, whose token is 0); ``M`` is the worst
    case."""
    n, k = idx.shape
    m = -(-n * min(k, n_held) // tile) + n_held
    local = idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < n_held), local, n_held)
    counts = jnp.sum(key[:, None] == jnp.arange(n_held)[None, :], axis=0,
                     dtype=jnp.int32)
    order = jnp.argsort(key, stable=True)
    tiles_of = -(-counts // tile)
    tile_end = jnp.cumsum(tiles_of)
    row_start = jnp.cumsum(counts) - counts
    i = jnp.arange(m, dtype=jnp.int32)
    expert = jnp.minimum(jnp.searchsorted(tile_end, i, side="right"),
                         n_held - 1).astype(jnp.int32)
    j = ((i - (tile_end - tiles_of)[expert]) * tile)[:, None] + jnp.arange(tile)
    valid = (j < counts[expert][:, None]) & (i < tile_end[-1])[:, None]
    src = order[jnp.clip(row_start[expert][:, None] + j, 0, n * k - 1)]
    tok = jnp.where(valid, src // k, 0).astype(jnp.int32)
    wt = jnp.where(valid, weights.reshape(-1)[src], 0.0)
    return tok, wt, expert, tile_end[-1].astype(jnp.int32), counts


def _slice(w, e):
    return jax.lax.dynamic_index_in_dim(w, e, keepdims=False)


def _add_slice(acc, e, delta):
    return jax.lax.dynamic_update_index_in_dim(acc, _slice(acc, e) + delta, e, 0)


@jax.custom_vjp
def grouped_experts(x, wt, tok, expert, n_tiles, w_gate, w_up, w_down):
    """``y [N, D]``: for every tile in use, ``y[tok] += wt * SwiGLU_expert(
    x[tok])``.  ``w_gate``, ``w_up`` [n_held, D, F], ``w_down`` [n_held,
    F, D]; the rest as :func:`tile_plan` returns it."""
    def body(i, y):
        t, e = tok[i], expert[i]
        xt = x[t]
        h = jax.nn.silu(xt @ _slice(w_gate, e)) * (xt @ _slice(w_up, e))
        return y.at[t].add(wt[i][:, None] * (h @ _slice(w_down, e)))

    return jax.lax.fori_loop(0, n_tiles, body, jnp.zeros_like(x))


def _grouped_fwd(x, wt, tok, expert, n_tiles, w_gate, w_up, w_down):
    y = grouped_experts(x, wt, tok, expert, n_tiles, w_gate, w_up, w_down)
    return y, (x, wt, tok, expert, n_tiles, w_gate, w_up, w_down)


def _grouped_bwd(res, dy):
    x, wt, tok, expert, n_tiles, w_gate, w_up, w_down = res

    def body(i, carry):
        dx, dwt, dg, du, dd = carry
        t, e = tok[i], expert[i]
        g, u, d = _slice(w_gate, e), _slice(w_up, e), _slice(w_down, e)
        xt = x[t]
        a, b = xt @ g, xt @ u
        sig = jax.nn.sigmoid(a)
        act = a * sig
        h = act * b
        dyt = dy[t]
        back = dyt @ d.T                       # d(out)/dh of an unweighted row
        dwt = dwt.at[i].set(jnp.sum(back * h, axis=-1))     # = dy . (h @ d)
        dh = wt[i][:, None] * back
        da = dh * b * sig * (1.0 + a * (1.0 - sig))
        db = dh * act
        dx = dx.at[t].add(da @ g.T + db @ u.T)
        return (dx, dwt, _add_slice(dg, e, xt.T @ da),
                _add_slice(du, e, xt.T @ db),
                _add_slice(dd, e, h.T @ (wt[i][:, None] * dyt)))

    zeros = tuple(map(jnp.zeros_like, (x, wt, w_gate, w_up, w_down)))
    dx, dwt, dg, du, dd = jax.lax.fori_loop(0, n_tiles, body, zeros)
    return dx, dwt, None, None, None, dg, du, dd


grouped_experts.defvjp(_grouped_fwd, _grouped_bwd)


def ffn(p: Dict, x: jax.Array, *, top_k: int, scaling: float = 1.0,
        first: int = 0, tile: int = TILE, router: str = "sigmoid"
        ) -> Tuple[jax.Array, jax.Array]:
    """The expert layer of the module docstring over ``x`` [..., D], and
    int32 ``[3]``: the assignments the router made, those to held experts,
    and the busiest held expert's tokens.  ``p``: ``router`` [D, E],
    ``router_bias`` [E] (the sigmoid router's), ``shared`` (a SwiGLU's
    ``w_gate``, ``w_up``, ``w_down``; optional) and ``experts`` (the held
    experts' three, stacked)."""
    flat = x.reshape(-1, x.shape[-1])
    held = p["experts"]
    with annotate("seq/moe"):
        with annotate("seq/moe/route"):
            idx, weights = route(flat, p["router"], p.get("router_bias"),
                                 top_k, scaling, router)
            tok, wt, expert, n_tiles, counts = tile_plan(
                idx, weights, first, held["w_gate"].shape[0], tile)
        shared = swiglu(p["shared"], flat) if "shared" in p else None
        with annotate("seq/moe/experts"):
            y = grouped_experts(flat, wt, tok, expert, n_tiles,
                                held["w_gate"], held["w_up"], held["w_down"])
            if shared is not None:
                y = shared + y
    stats = jnp.stack([jnp.int32(idx.size), jnp.sum(counts), jnp.max(counts)])
    return y.reshape(x.shape), stats
