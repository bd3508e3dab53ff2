"""Fused Adagrad table update — Pallas TPU kernel behind the registry.

The dense Adagrad update reads (w, accum, grad) and writes (w', accum'):
four HBM array traversals when left to separate XLA ops, and the embedding
tables are the framework's largest arrays.  This kernel fuses the whole
update into one pass per block with in-place buffer aliasing — the
TPU-native counterpart of the reference's single AVX loop over the
parameter arrays (AdagradUpdater_Num, gradientUpdater.h:138-150).

Math (identical to optim.adagrad): accum' = accum + g^2 ;
w' = w - lr * g / sqrt(accum' + eps).

Dispatch rides the kernel registry
(:mod:`lightctr_tpu.ops.sparse_kernels`, phase ``adagrad``): compiled
Mosaic on TPU, a jitted donating pure-XLA twin elsewhere, the interpreter
under ``LIGHTCTR_KERNELS=interpret`` or an explicit ``interpret=True``.
``fused_adagrad_update`` stays a drop-in for the (update, apply) pair on
flat fp32 tables; the optax-style transform remains the composable
default.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from lightctr_tpu.ops.sparse_kernels import register_kernel, resolve_impl


def _kernel(w_ref, a_ref, g_ref, w_out, a_out, *, lr: float, eps: float):
    g = g_ref[:]
    a_new = a_ref[:] + g * g
    a_out[:] = a_new
    w_out[:] = w_ref[:] - lr * g * jax.lax.rsqrt(a_new + eps)


@partial(jax.jit, static_argnames=("lr", "eps", "block"),
         donate_argnums=(0, 1))
def _adagrad_reference(
    w: jax.Array, accum: jax.Array, grad: jax.Array,
    lr: float, eps: float, block: int,
) -> Tuple[jax.Array, jax.Array]:
    """The pure-XLA twin: one fused elementwise expression (XLA's own
    fusion does the single-pass job on CPU/GPU; ``block`` is unused but
    kept so both impls share a signature)."""
    a_new = accum + grad * grad
    return w - lr * grad * jax.lax.rsqrt(a_new + eps), a_new


@partial(jax.jit, static_argnames=("lr", "eps", "block", "interpret"),
         donate_argnums=(0, 1))
def _adagrad_pallas(
    w: jax.Array,
    accum: jax.Array,
    grad: jax.Array,
    lr: float,
    eps: float,
    block: int,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    shape = w.shape
    flat_w = w.reshape(-1)
    n = flat_w.shape[0]
    block = min(block, n)
    pad = (-n) % block
    if pad:
        flat_w = jnp.pad(flat_w, (0, pad))
    flat_a = jnp.pad(accum.reshape(-1), (0, pad)) if pad else accum.reshape(-1)
    flat_g = jnp.pad(grad.reshape(-1), (0, pad)) if pad else grad.reshape(-1)
    grid = (flat_w.shape[0] // block,)
    w2, a2 = pl.pallas_call(
        partial(_kernel, lr=lr, eps=eps),
        out_shape=(
            jax.ShapeDtypeStruct(flat_w.shape, flat_w.dtype),
            jax.ShapeDtypeStruct(flat_a.shape, flat_a.dtype),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ],
        out_specs=(
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (i,)),
        ),
        input_output_aliases={0: 0, 1: 1},
        interpret=interpret,
    )(flat_w, flat_a, flat_g)
    if pad:
        w2, a2 = w2[:n], a2[:n]
    return w2.reshape(shape), a2.reshape(shape)


register_kernel("fused_adagrad", phase="adagrad",
                reference=_adagrad_reference, pallas=_adagrad_pallas)


def fused_adagrad_update(
    w: jax.Array,
    accum: jax.Array,
    grad: jax.Array,
    lr: float,
    eps: float = 1e-7,
    block: int = 1 << 16,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """One-pass Adagrad on a flat (or flattenable) fp32 tensor; returns
    (w', accum').  Buffers are donated and aliased — updated in place.
    ``interpret=True`` forces the Pallas kernel under the interpreter
    (the CPU parity-test path); otherwise the registry picks compiled
    Pallas on TPU and the XLA twin elsewhere."""
    from lightctr_tpu.ops import sparse_kernels

    impl = "interpret" if interpret else resolve_impl("fused_adagrad")
    sparse_kernels._record("adagrad", impl)
    if impl == "xla":
        return _adagrad_reference(w, accum, grad, lr, eps, block)
    return _adagrad_pallas(w, accum, grad, lr, eps, block,
                           interpret=(impl == "interpret"))
