"""Ring attention — sequence/context parallelism over the ``seq`` mesh axis.

The reference's sequence stack is a single-device 28-step LSTM + additive
attention (SURVEY.md §5 "long-context: none"); this module is the
beyond-parity capability the TPU build owes long sequences: memory-linear
exact attention whose sequence dimension is sharded across devices.

Algorithm (Ring Attention with blockwise softmax): each device holds one
sequence block of Q, K, V.  K/V blocks rotate around the ring via
``lax.ppermute`` while every device accumulates its queries' attention with a
numerically-stable online softmax (running max ``m``, denominator ``l``,
numerator ``o``).  After ``seq_parallelism`` hops every Q block has attended
to every K/V block — exact attention, never materializing the [T, T] matrix,
with communication overlapped hop by hop on ICI.

Causal masking uses global positions derived from each block's rank so the
sharded result equals single-device causal attention.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _ring_attention_local(
    q: jax.Array,  # [B, Tb, H, D] this device's query block
    k: jax.Array,  # [B, Tb, H, D]
    v: jax.Array,  # [B, Tb, H, D]
    axis_name: str,
    n_ring: int,
    causal: bool,
) -> jax.Array:
    b, tb, h, d = q.shape
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    my = jax.lax.axis_index(axis_name)
    perm = [(j, (j + 1) % n_ring) for j in range(n_ring)]
    q_pos = my * tb + jnp.arange(tb)                      # global query positions

    # online-softmax statistics accumulate in float32 regardless of the input
    # dtype (bf16 denominators round away terms after a few hundred adds);
    # mark them varying over the ring axis so the scan carry types match
    def _vary(x):
        return jax.lax.pcast(x, (axis_name,), to="varying")

    m0 = _vary(jnp.full((b, h, tb), NEG_INF, jnp.float32))
    l0 = _vary(jnp.zeros((b, h, tb), jnp.float32))
    o0 = _vary(jnp.zeros((b, h, tb, d), jnp.float32))

    def step(i, carry):
        k_cur, v_cur, m, l, o = carry
        # the block currently held arrived from rank (my - i) mod n
        src = (my - i) % n_ring
        k_pos = src * tb + jnp.arange(tb)
        s = (
            jnp.einsum(
                "bqhd,bkhd->bhqk", q, k_cur,
                preferred_element_type=jnp.float32,
            )
            * scale
        )
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]        # [Tq, Tk]
            s = jnp.where(mask[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(v_cur.dtype), v_cur,
            preferred_element_type=jnp.float32,
        )

        def rotate(kv):
            return (
                jax.lax.ppermute(kv[0], axis_name, perm),
                jax.lax.ppermute(kv[1], axis_name, perm),
            )

        # last hop's rotation would be discarded — skip the ICI traffic
        k_next, v_next = jax.lax.cond(
            i < n_ring - 1, rotate, lambda kv: kv, (k_cur, v_cur)
        )
        return k_next, v_next, m_new, l_new, o_new

    _, _, m, l, o = jax.lax.fori_loop(0, n_ring, step, (k, v, m0, l0, o0))
    # fully-masked rows (causal, position 0 block boundaries) have l == 0
    out = o / jnp.maximum(l, 1e-30)[..., None]             # [B, H, Tq, D] f32
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def ring_self_attention(
    mesh: Mesh,
    q: jax.Array,  # [B, T, H, D] with T divisible by mesh.shape[axis]
    k: jax.Array,
    v: jax.Array,
    axis: str = "seq",
    causal: bool = False,
) -> jax.Array:
    """Exact multi-head attention with the sequence dim sharded over ``axis``."""
    n = mesh.shape[axis]
    t = q.shape[1]
    if t % n != 0:
        raise ValueError(f"sequence length {t} not divisible by ring size {n}")
    fn = shard_map(
        partial(_ring_attention_local, axis_name=axis, n_ring=n, causal=causal),
        mesh=mesh,
        in_specs=(P(None, axis, None, None),) * 3,
        out_specs=P(None, axis, None, None),
    )
    return fn(q, k, v)


def full_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    key_mask: jax.Array | None = None,
) -> jax.Array:
    """Single-device exact attention (the test oracle and the short-sequence
    production core).  ``key_mask`` [B, T] zeroes attention TO padded keys."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.asarray(d, q.dtype))
    if causal:
        t = q.shape[1]
        mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    if key_mask is not None:
        s = jnp.where(key_mask[:, None, None, :] > 0, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)
