"""Table initialisers made for the chip: one elementwise pass over index
arithmetic, so a 4 GB table appears in milliseconds in whatever sharding
its consumer asks for (``jax.random.normal`` took 17.7 s for 2^25 x 32
values on a v5e, my chip run, PR 25: most of a run's set-up)."""

from __future__ import annotations


def _mix(x):
    """lowbias32 (Wellons): a full-avalanche 32-bit integer hash."""
    import jax.numpy as jnp

    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def hashed_normal(key, rows: int, cols: int):
    """``[rows, cols]`` float32 standard normals, a pure function of the
    key and each element's index (Box-Muller over two hashes of it)."""
    import jax
    import jax.numpy as jnp

    words = jnp.asarray(key).astype(jnp.uint32).reshape(-1)
    s1, s2 = words[0], words[-1]
    r = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 0)
    c = jax.lax.broadcasted_iota(jnp.uint32, (rows, cols), 1)
    idx = r * jnp.uint32(cols) + c
    h1 = _mix(_mix(idx ^ s1) + jnp.uint32(0x9E3779B9))
    h2 = _mix(_mix(idx ^ s2) + jnp.uint32(0x85EBCA6B))
    u1 = ((h1 >> 8).astype(jnp.float32) + 1.0) * (1.0 / (1 << 24))
    u2 = (h2 >> 8).astype(jnp.float32) * (1.0 / (1 << 24))
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos((2.0 * jnp.pi) * u2)
