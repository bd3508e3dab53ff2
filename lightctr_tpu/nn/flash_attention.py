"""Blockwise (flash) attention — Pallas TPU kernel.

The single-chip counterpart of :mod:`lightctr_tpu.nn.ring_attention`: exact
attention computed block-by-block with an online softmax, never materializing
the [T, T] score matrix.  The grid is (batch*heads, q-blocks, k-blocks) with
the k-axis innermost and marked ``arbitrary`` so Mosaic double-buffers the
K/V block fetches from HBM while the MXU works on the previous block; running
(max, denom, accumulator) statistics live in VMEM scratch across k-steps.

Running stats are kept as [block_q, 128] tiles (lane-width replicated) rather
than 1-D vectors — TPU vregs are (8, 128), so the replicated form keeps every
elementwise op a full-tile VPU op instead of a sublane-reduction dance.

Causal mode skips K blocks strictly above the diagonal (no MXU work issued),
halving FLOPs at long T.  Forward-only: the production differentiable paths
are ``full_attention`` (short T) and ``ring_attention`` (sharded long T);
this kernel serves long-context inference/eval on one core.

Gates against the ``full_attention`` oracle: tests/test_flash_attention.py
(interpret mode), tests_tpu/test_compiled_kernels.py and ``chip_smoke.py``'s
kernel phase (compiled, on the chip).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lightctr_tpu.ops.sparse_kernels import register_kernel, resolve_impl

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)
LANES = 128


def _cols(x, n):
    """Broadcast a lane-replicated [bq, 128] stat tile to n columns (any n:
    ceil-tile then slice — the rows are constant, so any slice is exact)."""
    reps, rem = divmod(n, LANES)
    if reps == 0:
        return x[:, :n]
    if rem:
        return jnp.tile(x, (1, reps + 1))[:, :n]
    return jnp.tile(x, (1, reps))


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, block_q: int, block_k: int, nk: int
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    if causal:
        # run iff the block's bottom-left corner is on/below the diagonal
        should_run = (qi + 1) * block_q - 1 >= kj * block_k
    else:
        should_run = True

    @pl.when(should_run)
    def _run():
        q = q_ref[:]                                    # [BQ, D]
        k = k_ref[:]                                    # [BK, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                       # [BQ, BK]
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0
            )
            cols = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1
            )
            s = jnp.where(rows >= cols, s, NEG_INF)

        m_prev, l_prev = m_scr[:], l_scr[:]             # [BQ, 128]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - _cols(m_next, block_k))
        alpha = jnp.exp(m_prev - m_next)
        l_corr = alpha * l_prev
        l_next = jnp.sum(p, axis=1)[:, None] + l_corr
        m_scr[:] = m_next
        l_scr[:] = l_next
        l_inv = jnp.where(l_next == 0.0, 1.0, 1.0 / l_next)
        d = acc_scr.shape[-1]
        acc_scr[:] *= _cols(l_corr * l_inv, d)
        o_curr = jax.lax.dot(
            p.astype(v_ref.dtype), v_ref[:], preferred_element_type=jnp.float32
        )
        acc_scr[:] += o_curr * _cols(l_inv, d)

    @pl.when(kj == nk - 1)
    def _out():
        o_ref[:] = acc_scr[:].astype(o_ref.dtype)


def _flash_reference(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool, block_q: int, block_k: int,
) -> jax.Array:
    """The XLA form: the ``full_attention`` oracle the kernel is
    tested against (blocks are pallas tuning knobs — unused here)."""
    from lightctr_tpu.nn.ring_attention import full_attention

    return full_attention(q, k, v, causal=causal)


def flash_attention(
    q: jax.Array,  # [B, T, H, D]
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Registry-dispatched: compiled Mosaic on TPU, the exact
    ``full_attention`` off-TPU (a flash call on CPU no longer crashes),
    the interpreter under an explicit ``interpret=True``.  Block validation runs on every path so
    caller bugs surface regardless of backend."""
    from lightctr_tpu.ops import sparse_kernels

    impl = "interpret" if interpret else resolve_impl("flash_attention")
    block_q, block_k = _validate_blocks(q.shape[1], block_q, block_k)
    sparse_kernels._record("attention", impl)
    if impl == "xla":
        return _flash_reference(q, k, v, causal, block_q, block_k)
    return _flash_pallas(q, k, v, causal, block_q, block_k,
                         interpret=(impl == "interpret"))


def _validate_blocks(t: int, block_q: int, block_k: int):
    """Shrink requested blocks to divisors of T (callers pick tuning
    caps, the kernel accepts any T with a power-of-two-divisible length);
    raise when none fits.  The single source for wrapper AND kernel, so
    the validation always matches what the kernel runs."""
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    while block_q > 8 and t % block_q:
        block_q //= 2
    while block_k > 8 and t % block_k:
        block_k //= 2
    if t % block_q or t % block_k:
        raise ValueError(
            f"block sizes ({block_q}, {block_k}) must divide T={t}"
        )
    return block_q, block_k


@partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"),
)
def _flash_pallas(
    q: jax.Array,  # [B, T, H, D]
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, t, h, d = q.shape
    block_q, block_k = _validate_blocks(t, block_q, block_k)
    scale = 1.0 / (d ** 0.5)
    nk = t // block_k

    # [B, T, H, D] -> [B*H, T, D]
    def fold(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)

    qf, kf, vf = fold(q), fold(k), fold(v)
    grid = (b * h, t // block_q, nk)
    out = pl.pallas_call(
        partial(
            _flash_kernel,
            scale=scale,
            causal=causal,
            block_q=block_q,
            block_k=block_k,
            nk=nk,
        ),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j, kk: (i, kk, 0)),
            pl.BlockSpec((None, block_k, d), lambda i, j, kk: (i, kk, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda i, j, kk: (i, j, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)


register_kernel("flash_attention", phase="attention",
                reference=_flash_reference, pallas=_flash_pallas)
