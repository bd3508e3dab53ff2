"""Per-phase A/B bench for the fused sparse-hot-path kernels (ISSUE 9).

For each phase of the per-step sparse tax — id dedup, segment merge +
optimizer apply, quantize pack (plain and EF-folded) — this times the
pure-XLA reference chain against the registry-dispatched fused kernel at
Criteo-ish shapes and writes ``SPARSE_KERNEL_BENCH.json``.

HONESTY CONTRACT: the dispatcher is measured, not assumed.  Each cell
records which implementation the registry actually resolved
(``impl_fused``) on this platform; off-TPU the capability gate resolves
the XLA reference, so a CPU run shows speedup ~1.0x with
``fused_is_reference: true`` rather than faking a win.  ``--force
interpret`` times the Pallas kernels under the interpreter (a CORRECTNESS
path, catastrophically slow by design — the cells carry a warning).  The
compiled-Mosaic numbers come from running this same tool on a real TPU.

Run:  python -m tools.sparse_kernel_bench [--steps 20]
          [--out SPARSE_KERNEL_BENCH.json] [--force auto|xla|interpret]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

from lightctr_tpu.utils.devicecheck import pin_cpu_platform  # noqa: E402

if "JAX_PLATFORMS" not in os.environ and "--tpu" not in sys.argv:
    pin_cpu_platform(8)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from lightctr_tpu.ops import quantize  # noqa: E402
from lightctr_tpu.ops import sparse_kernels as sk  # noqa: E402


def _timeit(fn, steps: int) -> float:
    """Median wall ms per call of a jitted thunk (block_until_ready)."""
    out = fn()
    jax.tree_util.tree_map(
        lambda x: x.block_until_ready() if hasattr(x, "block_until_ready")
        else x, out)
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        out = fn()
        jax.tree_util.tree_map(
            lambda x: x.block_until_ready() if hasattr(x, "block_until_ready")
            else x, out)
        times.append((time.perf_counter() - t0) * 1e3)
    return float(np.median(times))


def _cell(phase, kernel, shape_note, ref_fn, fused_fn, steps):
    impl = sk.resolve_impl(kernel)
    t_ref = _timeit(ref_fn, steps)
    t_fused = _timeit(fused_fn, steps)
    return {
        "phase": phase,
        "kernel": kernel,
        "shape": shape_note,
        "impl_ref": "xla",
        "impl_fused": impl,
        "fused_is_reference": impl == "xla",
        "t_ref_ms": round(t_ref, 4),
        "t_fused_ms": round(t_fused, 4),
        "speedup_x": round(t_ref / max(t_fused, 1e-9), 3),
        **({"warning": "interpret mode times the CORRECTNESS path — "
                       "orders of magnitude slower than compiled Mosaic"}
           if impl == "interpret" else {}),
    }


def run(steps: int = 20, out: str = "SPARSE_KERNEL_BENCH.json",
        force: str | None = None):
    if force and force != "auto":
        os.environ[sk.ENV_FLAG] = force
    interp = sk.resolve_impl("dedup_ids") == "interpret"
    r = np.random.default_rng(0)
    cells = []

    # -- dedup: batch id stream, Criteo-ish nnz -------------------------
    k = 4096 if interp else 16384
    vocab = 1 << 20
    ids = jnp.asarray(r.integers(1, vocab, size=k).astype(np.int32))
    ref = jax.jit(lambda x: sk.KERNELS["dedup_ids"].reference(x, k))
    fused = jax.jit(lambda x: sk.dedup_ids(x))
    cells.append(_cell("dedup", "dedup_ids", f"K={k} ids, vocab=2^20",
                       lambda: ref(ids), lambda: fused(ids), steps))
    print(f"dedup: {cells[-1]['t_ref_ms']}ms ref vs "
          f"{cells[-1]['t_fused_ms']}ms {cells[-1]['impl_fused']}",
          file=sys.stderr, flush=True)

    # -- gather: the device-resident row path's read half (ISSUE 15) ----
    gb, gd = 1 << 16, 16
    gn = 2048 if interp else 8192
    block = jnp.asarray(r.normal(size=(gb, gd)).astype(np.float32))
    gidx = jnp.asarray(r.integers(0, gb, size=gn).astype(np.int32))
    ref = jax.jit(lambda b, i: sk.KERNELS["gather_rows"].reference(b, i))
    fused = jax.jit(lambda b, i: sk.gather_rows(b, i))
    cells.append(_cell("gather", "gather_rows",
                       f"{gn} rows of [{gb}, {gd}] block",
                       lambda: ref(block, gidx),
                       lambda: fused(block, gidx), steps))
    print(f"gather: {cells[-1]['t_ref_ms']}ms ref vs "
          f"{cells[-1]['t_fused_ms']}ms {cells[-1]['impl_fused']}",
          file=sys.stderr, flush=True)

    # -- merge + apply: touched-row adagrad over a big table ------------
    s = 1024 if interp else 8192
    m, dim, tv = 4 * s, 16, 1 << 18
    u = np.unique(r.integers(1, tv, size=s))
    uids_np = np.zeros(s, np.int64)
    uids_np[:u.size] = u
    uids = jnp.asarray(uids_np)
    inv = jnp.asarray(r.integers(0, u.size, size=m).astype(np.int32))
    rows = jnp.asarray(r.normal(size=(m, dim)).astype(np.float32))
    table = jnp.asarray(r.normal(size=(tv, dim)).astype(np.float32))
    accum = jnp.asarray(np.abs(r.normal(size=(tv, dim))).astype(np.float32))

    ref = jax.jit(lambda t, a, g: sk.KERNELS["merge_apply"].reference(
        t, a, uids, g, inv, lr=0.05, eps=1e-7, denom=8.0))
    fused = jax.jit(lambda t, a, g: sk.merge_apply(
        t, a, uids, g, inv, lr=0.05, eps=1e-7, denom=8.0))
    cells.append(_cell(
        "merge_apply", "merge_apply",
        f"M={m} grad rows -> S={s} touched of [{tv}, {dim}] table",
        lambda: ref(table, accum, rows), lambda: fused(table, accum, rows),
        steps))
    print(f"merge_apply: {cells[-1]['t_ref_ms']}ms ref vs "
          f"{cells[-1]['t_fused_ms']}ms {cells[-1]['impl_fused']}",
          file=sys.stderr, flush=True)

    # -- quantize pack: the coded-collective payload encode --------------
    p = (2048, dim) if interp else (16384, dim)
    payload = jnp.asarray((0.1 * r.normal(size=p)).astype(np.float32))
    qt = quantize.build_table(-1.0, 1.0, bits=8)
    ref = jax.jit(lambda x: quantize.compress(qt, x))
    fused = jax.jit(lambda x: sk.quantize_pack(qt, x))
    cells.append(_cell("pack", "quantize_pack",
                       f"{p[0]}x{p[1]} fp32 -> uint8 codes",
                       lambda: ref(payload), lambda: fused(payload), steps))

    carried = jnp.asarray((0.01 * r.normal(size=p)).astype(np.float32))
    mask = jnp.ones((p[0], 1), jnp.float32)
    ref = jax.jit(lambda x, c: sk.KERNELS["quantize_pack_ef"].reference(
        qt, x, c, mask))
    fused = jax.jit(lambda x, c: sk.quantize_pack_ef(qt, x, c, mask))
    cells.append(_cell("pack", "quantize_pack_ef",
                       f"{p[0]}x{p[1]} EF-folded encode",
                       lambda: ref(payload, carried),
                       lambda: fused(payload, carried), steps))
    print(f"pack: {cells[-2]['t_fused_ms']}ms / ef {cells[-1]['t_fused_ms']}"
          f"ms ({cells[-1]['impl_fused']})", file=sys.stderr, flush=True)

    report = {
        "metric": "sparse_hot_path_kernel_phase_times",
        "platform": jax.devices()[0].platform,
        "env_flag": os.environ.get(sk.ENV_FLAG, "auto"),
        "dispatcher": {
            name: sk.resolve_impl(name) for name in sorted(sk.KERNELS)
            if name in ("dedup_ids", "merge_rows", "merge_apply",
                        "quantize_pack", "quantize_pack_ef")
        },
        "note": (
            "A/B per phase: pure-XLA reference chain vs the registry-"
            "dispatched kernel.  The dispatcher is measured, not assumed: "
            "impl_fused records what actually ran.  Off-TPU the gate "
            "resolves the reference (fused_is_reference=true, speedup "
            "~1.0) — the compiled-Mosaic columns of this artifact must "
            "come from a real-TPU run of the same tool; interpret cells "
            "time the correctness path only."
        ),
        "cells": cells,
    }
    print(json.dumps(report, indent=1))
    if out:
        with open(out, "w") as f:
            json.dump(report, f, indent=1)
    return report


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--out", default="SPARSE_KERNEL_BENCH.json")
    ap.add_argument("--force", choices=("auto", "xla", "interpret",
                                        "pallas"), default=None,
                    help="override the LIGHTCTR_KERNELS capability gate")
    ap.add_argument("--tpu", action="store_true",
                    help="do not pin the virtual CPU platform")
    args = ap.parse_args()
    run(steps=args.steps, out=args.out, force=args.force)


if __name__ == "__main__":
    main()
