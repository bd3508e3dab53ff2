"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once, through the entry points a user
calls, at the full width of the Criteo-shape Wide&Deep configuration the
repo already runs (39 fields = 13 numeric + 26 categorical, hashed
vocabulary 2^20, embedding dim 32, hidden 64, batch 4096):

  data     seeded rows from ``data.synth.write_criteo_proxy`` ->
           ``data.ingest.compile_shards`` (native parser) -> shard replay
           behind ``prefetch_batches``;
  train    ``SparseTableCTRTrainer.train_step`` for ``steps`` steps on one
           chip: finite, falling loss, no compilation after warm-up;
  serve    the trained parameters behind ``ServingModel`` /
           ``PredictionServer`` on a loopback port (the scorer is a thread
           of this process), ``PredictClient`` batches of 1, 7 and 256 rows
           against ``trainer.predict_proba`` on the same rows;
  kernels  every name in ``sparse_kernels.KERNELS``: the Pallas
           implementation compiled with ``interpret=False`` at the shapes
           the trainer uses, run, and compared with its XLA form;
  mesh     with four or more devices, the same trainer on
           ``MeshSpec(data=2, embed=2)`` with embed-sharded tables (the
           GSPMD step) and on ``MeshSpec(data=4)`` (the hybrid exchange
           step): shard placement, per-device memory, and the loss
           trajectory against the one-chip run.

It refuses to run without a TPU, falls back to nothing, and exits non-zero
when any phase fails.  Seconds printed here are set-up facts (is it
compiled, does a step take milliseconds or minutes), not performance
numbers: the benchmark is ``bench.py``'s job.  The facts of the run go out
as one ``[summary] {..., "claim": null}`` line; the LAST line of standard
output is exactly ``{"ok": true, "device": {"platform": "tpu", "kind": "...",
"count": N}}`` and nothing else, printed only when every phase passed.

    python chip_smoke.py            # on a machine with a TPU

The phases are importable functions of a :class:`Shape`, so
``tests/test_chip_smoke.py`` runs them at toy shapes on the virtual CPU
mesh.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import os
import sys
import tempfile
import time
from typing import Callable, Dict, Iterator, List, NamedTuple, Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class Shape:
    """The model and job widths of one smoke run (defaults: phase A,
    ``tools/criteo_scale.py``'s configuration)."""

    fields: int = 39
    n_cat: int = 26
    vocab: int = 1 << 20
    dim: int = 32
    hidden: int = 64
    batch: int = 4096
    steps: int = 24            # one-chip training steps (>= 20)
    mesh_steps: int = 6        # steps per four-device layout
    score_rows: tuple = (1, 7, 256)
    lr: float = 0.05
    seed: int = 0
    flash_t: int = 1024
    flash_d: int = 64

    @property
    def ids_per_step(self) -> int:
        """K: ids each of the two id streams carries per step."""
        return self.batch * self.fields

    @property
    def table_state_bytes(self) -> int:
        """fp32 tables (``w`` + ``embed``) plus their Adagrad accumulators."""
        return 2 * 4 * (self.vocab + self.vocab * self.dim)


# -- device -------------------------------------------------------------------


def device_facts() -> Dict:
    import jax
    import jaxlib

    from importlib.metadata import PackageNotFoundError, version

    dev = jax.devices()[0]
    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = None
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
    }


def require_tpu() -> Dict:
    """The first thing the smoke does: no TPU, no run."""
    facts = device_facts()
    if facts["platform"] != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but jax.devices()[0].platform is "
            f"{facts['platform']!r} ({facts['kind']}); nothing was run"
        )
    return facts


def _bytes_in_use(dev) -> Optional[int]:
    stats = dev.memory_stats()
    return None if stats is None else int(stats["bytes_in_use"])


def result_line(device: Dict) -> str:
    """The last line of standard output: these keys and no others."""
    return json.dumps({
        "ok": True,
        "device": {"platform": str(device["platform"]),
                   "kind": str(device["kind"]),
                   "count": int(device["count"])},
    })


# -- data ---------------------------------------------------------------------


def phase_data(shape: Shape, workdir: str, log=print) -> Dict:
    """Seeded Criteo-shaped rows -> compiled shard caches (train + a small
    held-out set for the scorer), through the native parser."""
    from lightctr_tpu.data import ingest
    from lightctr_tpu.data.synth import write_criteo_proxy
    from lightctr_tpu.native import bindings

    if not bindings.available():
        raise RuntimeError(
            "native library did not build (g++ on lightctr_tpu/native/*.cpp)"
            " — the ingest path this smoke exercises is the native one"
        )
    t0 = time.perf_counter()
    out = {}
    for name, rows, seed in (
        ("train", shape.batch * shape.steps, shape.seed),
        ("eval", sum(shape.score_rows), shape.seed + 1),
    ):
        path = os.path.join(workdir, f"{name}.ffm")
        write_criteo_proxy(path, rows, seed=seed, n_fields=shape.fields,
                           n_cat=shape.n_cat, vocab=shape.vocab)
        out[name] = ingest.compile_shards(
            path, shape.fields, feature_cnt=shape.vocab,
            field_cnt=shape.fields, native=True,
        )
        if out[name].rows != rows:
            raise RuntimeError(
                f"{name} cache holds {out[name].rows} rows, wrote {rows}")
    log(f"[data] {shape.batch * shape.steps} train rows + "
        f"{sum(shape.score_rows)} eval rows written, parsed natively and "
        f"compiled to shards in {time.perf_counter() - t0:.1f}s")
    return out


def _with_reps(shape: Shape, batch: Dict) -> Dict:
    """Wide&Deep's batch layout: one representative feature per field."""
    from lightctr_tpu.models import widedeep

    rep, rep_mask = widedeep.field_representatives(
        batch["fids"], batch["fields"], batch["mask"], shape.fields)
    out = {k: v for k, v in batch.items() if k != "row_mask"}
    out["rep_fids"], out["rep_mask"] = rep, rep_mask
    return out


def batches(shape: Shape, cache, depth: int = 2) -> Iterator[Dict]:
    """The training stream: shard replay -> Wide&Deep layout, ``depth``
    batches prepared behind the step."""
    from lightctr_tpu import obs
    from lightctr_tpu.data import ingest

    replay = ingest.iter_shard_batches(cache, shape.batch)
    # the stream's own registry: the smoke reads no ingest counter, and
    # the process-wide one belongs to whoever does
    return ingest.prefetch_batches(
        (_with_reps(shape, b) for b in replay), depth=depth,
        registry=obs.MetricsRegistry())


# -- train --------------------------------------------------------------------


def make_trainer(shape: Shape, mesh=None, param_shardings=None):
    import jax

    from lightctr_tpu import TrainConfig
    from lightctr_tpu.models import widedeep
    from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer

    params = widedeep.init(jax.random.PRNGKey(shape.seed), shape.vocab,
                           shape.fields, shape.dim, hidden=shape.hidden)
    return SparseTableCTRTrainer(
        params, widedeep.logits, TrainConfig(learning_rate=shape.lr),
        sparse_tables={"w": ["fids"], "embed": ["rep_fids"]},
        mesh=mesh, param_shardings=param_shardings,
    )


def run_steps(trainer, stream: Iterator[Dict], steps: int, label: str,
              log=print) -> Dict:
    """``steps`` ``train_step`` calls; the first is the warm-up (trace +
    compile), the rest are timed one by one, each region ending in
    ``block_until_ready``.  Returns losses and set-up facts."""
    import jax

    from lightctr_tpu.obs.resources import CompileTracker

    tracker = CompileTracker(component=f"chip_smoke:{label}", poll_every=0)
    losses: List[float] = []
    step_s: List[float] = []
    try:
        t0 = time.perf_counter()
        loss = trainer.train_step(next(stream))
        jax.block_until_ready((loss, trainer.params))
        warm_s = time.perf_counter() - t0
        losses.append(float(loss))
        tracker.track("trainer_step", trainer._step)
        base = tracker.snapshot()["backend_compiles"]
        for _ in range(steps - 1):
            batch = next(stream)
            t0 = time.perf_counter()
            loss = trainer.train_step(batch)
            jax.block_until_ready((loss, trainer.params))
            step_s.append(time.perf_counter() - t0)
            losses.append(float(loss))
        # a retrace shows as a new cache entry of the step (even when the
        # persistent cache spares the backend), any other program as a
        # backend compile
        recompiled = max(tracker.poll()["compiles"],
                         tracker.snapshot()["backend_compiles"] - base)
    finally:
        tracker.close()
    trainer.flush_health()
    if not np.all(np.isfinite(losses)):
        raise RuntimeError(f"[{label}] non-finite loss: {losses}")
    facts = {
        "steps": steps,
        "losses": [round(x, 6) for x in losses],
        "warmup_step_s": round(warm_s, 3),
        "steady_step_s_median": round(float(np.median(step_s)), 5),
        "compilations_after_warmup": int(recompiled),
    }
    log(f"[{label}] {steps} steps; loss {losses[0]:.5f} -> {losses[-1]:.5f}; "
        f"set-up facts: warm-up step (trace+compile) {warm_s:.2f}s, steady "
        f"step median {facts['steady_step_s_median'] * 1e3:.2f} ms, "
        f"compilations after warm-up {recompiled}")
    if recompiled:
        raise RuntimeError(
            f"[{label}] {recompiled} compilation(s) after the warm-up step")
    return facts


def phase_train(shape: Shape, cache, log=print):
    """One device: ``steps`` steps, finite falling loss."""
    import jax

    trainer = make_trainer(shape)
    with contextlib.closing(batches(shape, cache)) as stream:
        facts = run_steps(trainer, stream, shape.steps, "train", log)
    k = max(1, shape.steps // 5)
    head = float(np.mean(facts["losses"][:k]))
    tail = float(np.mean(facts["losses"][-k:]))
    if not tail < head:
        raise RuntimeError(
            f"[train] loss did not fall: first {k} mean {head:.5f}, "
            f"last {k} mean {tail:.5f}")
    stats = jax.devices()[0].memory_stats()
    if stats is not None:
        facts["peak_bytes_in_use"] = int(stats["peak_bytes_in_use"])
        facts["table_plus_accumulator_bytes"] = shape.table_state_bytes
        log(f"[train] peak_bytes_in_use {facts['peak_bytes_in_use']:,} "
            f"beside table+accumulator bytes {shape.table_state_bytes:,}")
    log(f"[train] losses {facts['losses']}")
    return trainer, facts


# -- serve --------------------------------------------------------------------


def phase_serve(shape: Shape, trainer, eval_cache, log=print) -> Dict:
    """The trained parameters behind the socket scorer; replies must be
    finite and agree with ``trainer.predict_proba`` on the same rows to
    the fp16 wire's tolerance (vals and scores travel as fp16)."""
    from lightctr_tpu.data import ingest
    from lightctr_tpu.serve import (
        PredictClient, PredictionServer, ServingModel,
    )

    rows = _with_reps(shape, ingest.as_arrays(eval_cache))
    # the reference sees the rows the server sees: vals after the fp16 wire
    rows["vals"] = (rows["vals"] * rows["mask"]).astype(
        np.float16).astype(np.float32)
    want = np.asarray(trainer.predict_proba(rows))
    model = ServingModel("widedeep", trainer.params)
    # the first request of each padded batch size compiles the scorer, so
    # the service deadline is set past any compile: this is not a latency
    # test
    facts = {"requests": []}
    with contextlib.closing(
        PredictionServer(model, deadline_ms=600_000.0)
    ) as server, contextlib.closing(
        PredictClient(server.address, timeout=600.0)
    ) as client:
        ofs = 0
        for n in shape.score_rows:
            req = {k: rows[k][ofs:ofs + n]
                   for k in ("fids", "vals", "rep_fids", "rep_mask")}
            t0 = time.perf_counter()
            got = client.predict(req)
            first_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            again = client.predict(req)
            again_s = time.perf_counter() - t0
            err = float(np.max(np.abs(got - want[ofs:ofs + n])))
            if got.shape != (n,) or not np.all(np.isfinite(got)):
                raise RuntimeError(f"[serve] bad reply for {n} rows")
            if err > 2e-3 or not np.array_equal(got, again):
                raise RuntimeError(
                    f"[serve] {n}-row reply off predict_proba by {err}")
            facts["requests"].append({
                "rows": n, "max_abs_err": round(err, 6),
                "first_reply_s": round(first_s, 4),
                "repeat_reply_s": round(again_s, 5),
            })
            log(f"[serve] {n:>3} rows: max|score - predict_proba| = "
                f"{err:.2e}; set-up facts: first reply (compiles the "
                f"padded size) {first_s:.3f}s, repeat {again_s * 1e3:.2f}"
                f" ms")
            ofs += n
    return facts


# -- kernels ------------------------------------------------------------------


class KernelCase(NamedTuple):
    kernel: str                  # a name in sparse_kernels.KERNELS
    label: str                   # which of the trainer's shapes
    pallas: Callable             # (*arrays) -> outputs, interpret=False
    xla: Callable                # (*arrays) -> outputs, the XLA form
    specs: tuple                 # jax.ShapeDtypeStruct per argument
    make: Callable               # (np.random.Generator) -> arrays
    rtol: float = 0.0            # 0/0 = bit-exact
    atol: float = 0.0


def kernel_cases(shape: Shape) -> List[KernelCase]:
    """One case per registered kernel (two for ``flash_attention``) at the
    shapes the trainer uses: ``K`` ids per stream, ``dim`` lanes a row."""
    import jax
    import jax.numpy as jnp

    import lightctr_tpu.nn.flash_attention  # noqa: F401 (self-registers)
    from lightctr_tpu.ops import quantize
    from lightctr_tpu.ops.sparse_kernels import KERNELS

    k, dim = shape.ids_per_step, shape.dim
    f32 = jnp.float32
    S = jax.ShapeDtypeStruct
    t8 = quantize.build_table(-1.0, 1.0, bits=8)

    def rows(rng, shp, scale=0.01):
        return (scale * rng.standard_normal(shp)).astype(np.float32)

    def kd(name):
        return KERNELS[name]

    def flash_case(causal):
        qkv = S((2, shape.flash_t, 4, shape.flash_d), f32)
        return KernelCase(
            "flash_attention",
            f"T={shape.flash_t} D={shape.flash_d} causal={causal}",
            lambda q, kk, v: kd("flash_attention").pallas(
                q, kk, v, causal, 256, 512, interpret=False),
            lambda q, kk, v: kd("flash_attention").reference(
                q, kk, v, causal, 256, 512),
            (qkv, qkv, qkv),
            lambda rng: tuple(rows(rng, qkv.shape, 1.0) for _ in range(3)),
            rtol=2e-2, atol=2e-2)

    return [
        KernelCase(
            "quantize_pack", f"8-bit [{k},{dim}]",
            lambda x: kd("quantize_pack").pallas(t8, x, interpret=False),
            lambda x: kd("quantize_pack").reference(t8, x),
            (S((k, dim), f32),), lambda rng: (rows(rng, (k, dim), 0.3),)),
        KernelCase(
            "quantize_pack_ef", f"8-bit [{k},{dim}]",
            lambda r, c, m: kd("quantize_pack_ef").pallas(
                t8, r, c, m, interpret=False),
            lambda r, c, m: kd("quantize_pack_ef").reference(t8, r, c, m),
            (S((k, dim), f32), S((k, dim), f32), S((k, 1), f32)),
            lambda rng: (rows(rng, (k, dim), 0.3), rows(rng, (k, dim)),
                         (rng.random((k, 1)) > 0.25).astype(np.float32))),
        flash_case(False),
        flash_case(True),
    ]


def _timed(fn, args) -> tuple:
    import jax

    out = jax.block_until_ready(fn(*args))  # warm (compiled already)
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def phase_kernels(shape: Shape, log=print) -> List[Dict]:
    """Per registered kernel: compile the Pallas implementation for the
    chip, run it, compare with the XLA form.  A kernel that fails any of
    these fails the smoke."""
    import jax

    from lightctr_tpu.ops.sparse_kernels import KERNELS

    cases = kernel_cases(shape)
    missing = set(KERNELS) - {c.kernel for c in cases}
    if missing:
        raise RuntimeError(f"no smoke case for kernel(s) {sorted(missing)}")
    verdicts = []
    for case in cases:
        rng = np.random.default_rng(shape.seed)
        args = tuple(jax.device_put(a) for a in case.make(rng))
        fact = {"kernel": case.kernel, "case": case.label}
        pallas = jax.jit(case.pallas)
        t0 = time.perf_counter()
        pallas.lower(*args).compile()
        fact["compile_s"] = round(time.perf_counter() - t0, 3)
        got, pallas_s = _timed(pallas, args)
        want, xla_s = _timed(jax.jit(case.xla), args)
        fact.update(pallas_s=round(pallas_s, 6), xla_s=round(xla_s, 6))
        match = all(
            np.allclose(np.asarray(g), np.asarray(w),
                        rtol=case.rtol, atol=case.atol)
            for g, w in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)))
        fact["verdict"] = "matches-xla" if match else "MISMATCH"
        if not match:
            raise RuntimeError(f"[kernels] {fact}")
        del args
        log("[kernels] " + " ".join(f"{k}={v}" for k, v in fact.items()))
        verdicts.append(fact)
    return verdicts


# -- four devices -------------------------------------------------------------


def _check_close(label: str, got: List[float], want: List[float]) -> float:
    """The tolerance the CPU parity tests hold sharded / exchanged
    trajectories to (tests/test_sharded_trainer.py, test_sparse_exchange.py)."""
    err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
    if not np.allclose(got, want, rtol=1e-4, atol=1e-5):
        raise RuntimeError(
            f"[{label}] losses {got} left the one-device run's {want}")
    return err


def phase_mesh(shape: Shape, cache, one_chip_losses: List[float],
               log=print) -> Dict:
    """The trainer on four devices, two layouts, same global batch and
    seed as the one-device run."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from lightctr_tpu.core.mesh import MeshSpec, make_mesh

    devices = jax.devices()[:4]
    want = one_chip_losses[:shape.mesh_steps]
    out = {}

    def run(label, mesh, shardings, table_fraction, batch_fraction):
        base = [_bytes_in_use(d) for d in devices]
        trainer = make_trainer(shape, mesh=mesh, param_shardings=shardings)
        with contextlib.closing(batches(shape, cache)) as stream:
            facts = run_steps(trainer, stream, shape.mesh_steps, label, log)
        facts["max_abs_loss_diff_vs_one_device"] = _check_close(
            label, facts["losses"], want)
        # the batch as the trainer places it: no device holds all of it
        probe = trainer._put({"fids": np.zeros(
            (shape.batch, shape.fields), np.int32)})["fids"]
        rows_held = sorted({s.data.shape[0]
                            for s in probe.addressable_shards})
        if rows_held != [int(shape.batch * batch_fraction)]:
            raise RuntimeError(
                f"[{label}] devices hold {rows_held} batch rows, expected "
                f"{shape.batch * batch_fraction:g} each")
        # tables: where the shards live and how much of the table each is
        shards = trainer.params["embed"].addressable_shards
        held = sorted({s.data.shape[0] for s in shards})
        if len({s.device for s in shards}) != 4 or \
                held != [int(shape.vocab * table_fraction)]:
            raise RuntimeError(
                f"[{label}] embed shards {held} rows on "
                f"{len({s.device for s in shards})} devices")
        facts.update(batch_rows_per_device=rows_held[0],
                     embed_rows_per_device=held[0])
        # per-device memory against tables + accumulators + batch share
        used = [None if b is None else _bytes_in_use(d) - b
                for d, b in zip(devices, base)]
        expect = int(shape.table_state_bytes * table_fraction)
        if None not in used:
            facts.update(bytes_in_use_per_device=used,
                         expected_table_state_bytes=expect)
            if not all(0.8 * expect <= u <= 1.5 * expect for u in used):
                raise RuntimeError(
                    f"[{label}] bytes_in_use per device {used} is not of "
                    f"the order of {expect} (tables + accumulators x "
                    f"{table_fraction:g})")
        if trainer.exchange_policy:
            facts["exchange_policy"] = dict(trainer.exchange_policy)
        log(f"[{label}] batch rows/device {rows_held[0]}, embed rows/device "
            f"{held[0]} on 4 devices, bytes_in_use/device {used} vs "
            f"expected ~{expect:,}; max |loss - one-device| "
            f"{facts['max_abs_loss_diff_vs_one_device']:.2e}"
            + (f"; exchange {facts['exchange_policy']}"
               if "exchange_policy" in facts else ""))
        out[label] = facts
        del trainer, probe, shards
        gc.collect()

    mesh = make_mesh(MeshSpec(data=2, embed=2), devices)
    rep = NamedSharding(mesh, P())
    run("mesh:data2xembed2", mesh, {
        "w": NamedSharding(mesh, P("embed")),
        "embed": NamedSharding(mesh, P("embed", None)),
        "fc1": {"w": rep, "b": rep},
        "fc2": {"w": rep, "b": rep},
    }, table_fraction=0.5, batch_fraction=0.5)
    run("mesh:data4", make_mesh(MeshSpec(data=4), devices), None,
        table_fraction=1.0, batch_fraction=0.25)
    return out


# -- main ---------------------------------------------------------------------


def main() -> int:
    device = require_tpu()

    from lightctr_tpu.utils.compile_cache import configure_compile_cache

    print("[device] platform={platform} kind={kind!r} count={count} "
          "jax={jax} jaxlib={jaxlib} libtpu={libtpu}".format(**device),
          flush=True)
    cache_dir = configure_compile_cache()
    warm_entries = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) \
        else 0
    print(f"[cache] persistent compilation cache at {cache_dir} "
          f"({warm_entries} entries at start)", flush=True)

    shape = Shape()

    def log(line):
        print(line, flush=True)

    t_start = time.perf_counter()
    summary: Dict = {}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        caches = phase_data(shape, workdir, log)
        trainer, summary["train"] = phase_train(shape, caches["train"], log)
        summary["serve"] = phase_serve(shape, trainer, caches["eval"], log)
        one_chip_losses = summary["train"]["losses"]
        del trainer
        gc.collect()
        summary["kernels"] = phase_kernels(shape, log)
        if device["count"] >= 4:
            summary["mesh"] = phase_mesh(shape, caches["train"],
                                         one_chip_losses, log)
        else:
            log(f"[mesh] skipped: {device['count']} device(s) visible, "
                "the two four-device layouts need 4")
    log(f"[done] all phases passed in {time.perf_counter() - t_start:.1f}s "
        "(set-up fact)")
    print("[summary] " + json.dumps({
        "versions": {k: device[k] for k in ("jax", "jaxlib", "libtpu")},
        "shape": dataclasses.asdict(shape),
        "compile_cache": {"dir": cache_dir,
                          "entries_at_start": warm_entries},
        **summary,
        "claim": None,
    }), flush=True)
    print(result_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
