"""Runner of a training cell (traffic ``kind: train``).

The window drives ``trainer.train_step`` fed by the program's input
pipeline (``iter_shard_batches`` -> the model's host layout ->
``prefetch_batches``).  Set-up builds that one trainer, drives it from the
seed through its first three steps by the window's own call and feed —
reading each loss, the first gradient's norms out of the Adagrad state
after step one and the touched rows after step three — and hands the same
object to the window.  The plain reference follows those three steps once
the window has closed, the peak memory has been read and the program's
state is freed.
"""

from __future__ import annotations

import functools
import gc
import os
import time
from typing import Dict, List

import numpy as np

from benchmarks.harness import common, dataset, datagen, reference
from benchmarks.harness.peaks import peaks_for

CHECK_STEPS = 3
WARM_STEPS = 3


def feed(ctx: Dict, cache, registry):
    """The timed input pipeline, in the order ``--seed`` picks."""
    from lightctr_tpu.data import ingest

    cfg, replay, model = ctx["cfg"], ctx["traffic"]["replay"], ctx["model"]
    batches = ingest.iter_shard_batches(
        cache, cfg["batch"], loop=True,
        shuffle_batches=int(replay["shuffle_batches"]),
        shard_shuffle=bool(replay["shard_shuffle"]),
        seed=int(ctx["seed"]) & 0xFFFFFFFF)
    return ingest.prefetch_batches(
        (model.feed_layout(cfg, b) for b in batches),
        depth=int(replay["prefetch_depth"]), registry=registry)


@functools.lru_cache(maxsize=None)
def _take_rows():
    """One jitted row gather for the whole run (traced once per table shape)."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda t, ids: jnp.take(t, ids, axis=0))


def table_rows(tree: Dict, union: Dict[str, np.ndarray], model, pad_to: int) -> Dict:
    """The touched rows of each table leaf (a gather on the device), the
    other leaves whole; as host arrays.  The id list is padded to ``pad_to``
    so that the gather — and the reference after it — has one shape whatever
    the seed (one compiled program, found in the cache by every later run);
    the padding's rows are set to zero, and nothing refers to them."""
    import jax
    import jax.numpy as jnp

    take = _take_rows()
    out = {}
    for k, v in tree.items():
        if k in model.TABLES:
            ids = union[model.TABLES[k]]
            padded = np.zeros(pad_to, ids.dtype)
            padded[:ids.size] = ids
            rows = np.array(take(v, jnp.asarray(padded)))
            rows[ids.size:] = 0
            out[k] = rows
        else:
            out[k] = jax.tree_util.tree_map(np.asarray, v)
    return out


def norms(a: Dict, b: Dict = None) -> Dict[str, float]:
    """Per leaf, the norm of ``a`` (or of ``a - b``)."""
    fa = reference.flat_leaves(a)
    fb = reference.flat_leaves(b) if b is not None else None
    return {k: float(np.sqrt(np.sum(np.square(
        np.asarray(v, np.float64) - (0 if fb is None else fb[k])))))
        for k, v in fa.items()}


def first_gradient_norms(trainer, union, model, pad_to) -> Dict[str, float]:
    """Adagrad's accumulator after one step is the squared first gradient
    as the optimizer got it: its root-sum per leaf is the gradient's norm."""
    state = trainer.opt_state
    accum = dict(state["accum"])
    accum.update(state["dense"].accum)
    rows = table_rows(accum, union, model, pad_to)
    return {k: float(np.sqrt(np.sum(np.asarray(v, np.float64))))
            for k, v in reference.flat_leaves(rows).items()}


def run(ctx: Dict) -> Dict:
    import jax

    from lightctr_tpu import obs

    cfg, traffic, model, cell = ctx["cfg"], ctx["traffic"], ctx["model"], ctx["cell"]
    chips, seconds = cell["chips"], float(ctx["seconds"])
    # where set-up goes, phase by phase (process start, imports and the
    # look for the chip are "startup"); printed beside setup_s, not a metric
    phases, last = {}, [ctx["t_start"]]

    def mark(name: str) -> None:
        now = time.perf_counter()
        phases[name], last[0] = now - last[0], now

    mark("startup")
    common.configure(cfg)
    compiles = common.CompileCounter()
    mesh, shardings_of = common.make_mesh(cfg, chips)
    shardings = shardings_of(model.param_specs(cfg)) if mesh is not None else None

    cache = dataset.shard_cache(ctx["cache_root"], cfg, traffic)
    ingest_registry = obs.MetricsRegistry()
    stream = feed(ctx, cache, ingest_registry)
    try:
        # -- set-up: weights, the trainer, the first steps ----------------
        first = [next(stream) for _ in range(CHECK_STEPS)]
        union = reference.touched_union(model, _ref_view(first))
        mark("rows_and_feed")
        pad_to = CHECK_STEPS * cfg["batch"] * cfg["fields"]   # every id distinct
        init = jax.jit(lambda key: model.init_params(cfg, key),
                       out_shardings=shardings)
        params0 = init(common.prng_key(ctx["seed"]))
        rows0 = table_rows(params0, union, model, pad_to)
        mark("weights")
        trainer = model.build_trainer(cfg, params0, mesh, shardings)
        del params0
        mark("trainer")
        got = {"loss": []}
        for i, b in enumerate(first):
            got["loss"].append(float(trainer.train_step(b)))
            if i == 0:
                got["grad_norm"] = first_gradient_norms(trainer, union, model, pad_to)
        rows3 = table_rows(trainer.params, union, model, pad_to)
        got["change_norm"] = norms(rows3, rows0)
        del rows3
        mark("checked_steps")
        for _ in range(WARM_STEPS):
            loss = trainer.train_step(next(stream))
        jax.block_until_ready((loss, trainer.params))
        gc.collect()
        compiles.reset()
        mark("warm_up")

        # -- the window ----------------------------------------------------
        trace_dir = (os.path.join(ctx["cache_root"], "trace", cell["name"])
                     if ctx["trace"] else None)
        steps, wait_s = 0, 0.0
        with common.profiled(trace_dir):
            t0 = time.perf_counter()
            setup_s = t0 - ctx["t_start"]
            with common.span("window"):
                while True:
                    with common.span("next_batch"):
                        tw = time.perf_counter()
                        batch = next(stream)
                        wait_s += time.perf_counter() - tw
                    with common.span("train_step"):
                        loss = trainer.train_step(batch)
                    steps += 1
                    if time.perf_counter() - t0 >= seconds:
                        break
                with common.span("final_sync"):
                    jax.block_until_ready((loss, trainer.params))
            window_s = time.perf_counter() - t0
        compiled_in_window = compiles.count
        mem = common.memory(chips)
        last_loss = float(loss)
    finally:
        stream.close()
    trainer.flush_health()
    reduced = common.read_trace(trace_dir) if trace_dir else None

    # -- the program's state goes, then the reference runs -----------------
    del trainer, loss
    gc.collect()
    compact = reference.compact_batches(model, _ref_view(first), union)
    want = reference.reference_steps(model, cfg, rows0, compact)
    numbers = reference.compare(got, want)
    numbers["last_loss_finite"] = 0.0 if np.isfinite(last_loss) else 1.0
    v = reference.verdict(numbers, dict(ctx["limits"], last_loss_finite=0.0))
    extra = {"compiles_in_window": compiled_in_window, "setup_s": setup_s,
             "setup_phases_s": phases, "steps": steps,
             "memory_in_use_bytes": mem["in_use"]}
    if ctx.get("calibrate"):
        # the control and the planted faults, read as the program is read
        extra["variants"] = {
            name: reference.compare(
                reference.reference_steps(model, cfg, rows0, compact, name), want)
            for name in ctx["calibrate"]}

    distinct = datagen.distinct_ids_per_batch(
        traffic["rows"], fields=cfg["fields"], n_cat=cfg["n_cat"],
        vocab=cfg["vocab"], batch=cfg["batch"], seed=traffic["rows"]["data_seed"])
    examples = steps * cfg["batch"]
    out = {
        "correct": v["correct"], "checks": v["checks"],
        "attempted": steps, "failed": 0,
        "end_to_end": {
            "train_examples_per_s_per_chip": examples / window_s / chips,
            "setup_s": setup_s,
        },
        "device": dict(ctx["device"], memory_peak_bytes=mem["peak"]),
        "extra": extra,
    }
    out["ctx"] = {
        "cell": cell, "cfg": cfg, "traffic": traffic, "chips": chips,
        "window_s": window_s, "steps": steps, "reduced": reduced,
        "ingest_wait_s": wait_s, "memory": mem,
        "peaks": peaks_for(ctx["device"]["kind"]),
        "cost": model.train_step_cost(cfg, distinct), "distinct_ids": distinct,
    }
    return common.attach_trace(out, reduced)


def _ref_view(batches: List[Dict]) -> List[Dict]:
    """What the reference may read of a fed batch: the generator's columns
    (ids, values, mask, labels), not what the program's layout added."""
    return [{k: b[k] for k in ("fids", "vals", "mask", "labels")} for b in batches]
