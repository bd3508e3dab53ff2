"""Compiled-mode (real TPU) gates for the Pallas kernels.

The main suite (tests/) pins a virtual CPU platform and exercises these
kernels in interpret mode; this directory runs on the live chip only:

    python -m pytest tests_tpu -q        # from the repo root, TPU visible

Collection fails off a TPU (conftest.py).  Same oracles as tests/, but
through the actual Mosaic lowering path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_compiled_matches_full(causal):
    from lightctr_tpu.nn.flash_attention import flash_attention
    from lightctr_tpu.nn.ring_attention import full_attention

    b, t, h, d = 2, 1024, 4, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (b, t, h, d), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, t, h, d), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, t, h, d), jnp.float32)
    got = np.asarray(flash_attention(q, k, v, causal=causal))
    want = np.asarray(full_attention(q, k, v, causal=causal))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)


# -- sparse hot-path registry kernels: compiled Mosaic gates -----------------


def test_quantize_pack_compiled_bit_identical():
    from lightctr_tpu.ops import quantize
    from lightctr_tpu.ops import sparse_kernels as sk

    r = np.random.default_rng(2)
    t = quantize.build_table(-1.0, 1.0, bits=8)
    x = jnp.asarray((2.0 * r.normal(size=(1024, 16))).astype(np.float32))
    carried = jnp.asarray((0.1 * r.normal(size=(1024, 16))).astype(np.float32))
    mask = jnp.ones((1024, 1), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(sk.KERNELS["quantize_pack"].pallas(t, x, interpret=False)),
        np.asarray(quantize.compress(t, x)))
    c0, d0 = sk.KERNELS["quantize_pack_ef"].reference(t, x, carried, mask)
    c1, d1 = sk.KERNELS["quantize_pack_ef"].pallas(t, x, carried, mask,
                                                   interpret=False)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c0))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d0))
