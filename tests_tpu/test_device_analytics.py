"""Compiled-mode (real TPU) gates for the device observability plane
(ISSUE 19): the ProgramCatalog's HLO cost/memory analytics must be
readable for the registered kernels through the actual Mosaic
lowering path — not just the interpreter the main suite proves —
and donation verification must confirm a donated update really
aliases on chip (the property whose silent loss doubles HBM traffic).

    python -m pytest tests_tpu -q        # from the repo root, TPU visible

Collection fails off a TPU (conftest.py).
"""

import jax
import jax.numpy as jnp
import numpy as np

from lightctr_tpu import obs
from lightctr_tpu.obs import device


def test_catalog_reads_cost_and_memory_for_compiled_matmul():
    """On hardware the catalog must surface real FLOPs/bytes AND — when
    the chip generation is in PEAK_SPECS — a roofline utilization in
    (0, ~1]; an unknown generation must stay honestly unavailable
    (peak None, utilization None), never a fake number."""
    reg = obs.MetricsRegistry()
    cat = device.ProgramCatalog(component="tpu_gate", registry=reg)
    f = jax.jit(lambda a, b: a @ b)
    x = jnp.zeros((512, 512), jnp.bfloat16)
    try:
        with obs.override(True):
            cat.offer("mm", f, (x, x))
            cat.note_step(0.001, "mm")
            ana = cat.analyze()["mm"]
        assert ana["available"] is True
        assert ana["flops"] >= 2 * 512 ** 3
        assert ana["bytes_accessed"] > 0
        assert ana["memory"]["peak_estimate"] > 0
        snap = cat.snapshot()
        assert snap["backend"] == "tpu"
        rec = snap["programs"]["mm"]
        if cat.peak is not None:
            assert rec["utilization"] is not None
            assert 0.0 < rec["utilization"] < 10.0  # sane, not garbage
        else:  # unknown generation: honest unavailability
            assert rec["utilization"] is None
    finally:
        cat.close()


def test_catalog_analyzes_registered_mosaic_kernels():
    """cost_analysis()/memory_analysis() through the compiled Mosaic
    path for registry kernels ``auto`` selects on a TPU: the payload
    packers.  The Pallas custom-call may report zero FLOPs — that is
    XLA's honest answer for an opaque call — but the MEMORY analysis
    (argument/output/peak bytes) must be real, because the census
    budgets key off it."""
    from lightctr_tpu.ops import quantize
    from lightctr_tpu.ops import sparse_kernels as sk

    r = np.random.default_rng(0)
    m, d = 1024, 16
    t8 = quantize.build_table(-1.0, 1.0, bits=8)
    x = jnp.asarray(r.normal(size=(m, d)).astype(np.float32))
    carried = jnp.asarray((0.1 * r.normal(size=(m, d))).astype(np.float32))
    mask = jnp.ones((m, 1), jnp.float32)

    def pack(x):
        return sk.KERNELS["quantize_pack"].pallas(t8, x, interpret=False)

    def pack_ef(x, carried, mask):
        return sk.KERNELS["quantize_pack_ef"].pallas(
            t8, x, carried, mask, interpret=False)

    reg = obs.MetricsRegistry()
    cat = device.ProgramCatalog(component="tpu_kernels", registry=reg)
    try:
        with obs.override(True):
            cat.offer("quantize_pack", jax.jit(pack), (x,))
            cat.offer("quantize_pack_ef", jax.jit(pack_ef),
                      (x, carried, mask))
            out = cat.analyze()
        for name in ("quantize_pack", "quantize_pack_ef"):
            ana = out[name]
            assert ana["available"] is True, (name, ana)
            mem = ana["memory"]
            assert mem["argument"] >= m * d * 4 and mem["output"] > 0
            assert mem["peak_estimate"] >= mem["output"]
        gauges = reg.snapshot()["gauges"]
        assert gauges[obs.labeled("device_program_memory_bytes",
                                  program="quantize_pack",
                                  kind="argument")] > 0
    finally:
        cat.close()


def test_donated_adagrad_update_aliases_on_chip():
    """verify_donation on a REAL donated Adagrad update: the aliased
    path must record checks with zero misses on hardware — the
    counterpart of the CPU test's broken control, run where the aliasing
    actually pays (in-place HBM update vs a full table copy)."""
    def update(w, a, g):
        a = a + g * g
        return w - 0.1 * g * jax.lax.rsqrt(a + 1e-7), a

    watch = device.DonationWatch(register=False)
    fn = jax.jit(update, donate_argnums=(0, 1))
    checked = device.verify_donation(
        "adagrad_update", fn, donate_argnums=(0, 1),
        watch=watch, sample_every=1)
    n = 1 << 16
    with obs.override(True):
        w2, a2 = checked(
            jax.random.normal(jax.random.PRNGKey(0), (n,), jnp.float32),
            jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (n,),
                                      jnp.float32)),
            jax.random.normal(jax.random.PRNGKey(2), (n,), jnp.float32))
    jax.block_until_ready((w2, a2))
    snap = watch.snapshot()
    assert snap["programs"]["adagrad_update"]["checks"] == 1
    assert snap["programs"]["adagrad_update"]["misses"] == 0
    watch.close()


def test_census_sees_device_buffers_with_real_sizes():
    reg = obs.MetricsRegistry()
    cen = device.LiveBufferCensus(registry=reg, name="tpu_census",
                                  register=False, sample_every=1)
    big = jnp.zeros((1024, 1024), jnp.float32)  # 4 MiB on-chip
    cen.register_tag("workload", lambda: big)
    try:
        with obs.override(True):
            cen.sample()
        last = cen.snapshot()
        assert last["available"] is True
        assert last["tags"]["workload"]["bytes"] == 4 * 1024 * 1024
        assert last["total_bytes"] >= 4 * 1024 * 1024
    finally:
        cen.close()
        del big
