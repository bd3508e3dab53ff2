"""Runner of a sequence-training cell (traffic ``kind: train_seq``).

``train_cell.py``'s protocol for a model whose rows are packed token
sequences and whose loss is a softmax cross-entropy a token
(``harness/reference.py`` follows the logistic loss over rows with labels
and values, so this kind brings its own reference through the model file:
``model.reference_steps``).  The window drives ``trainer.train_step`` fed
by the program's input pipeline (``iter_shard_batches`` over the shards of
``harness/seqgen.py`` -> the model's host layout -> ``prefetch_batches``).
Set-up builds that one trainer, drives it from the seed through its first
three steps by the window's own call and feed — reading each loss, the
first gradient's norm a leaf out of the Adagrad state after step one and
the change a leaf after step three — and hands the same object to the
window.  The plain reference follows those three steps once the window has
closed, the peak memory has been read and the program's state is freed.

What differs from ``train_cell.py`` besides the reference: the dense
leaves are 3 GB, so their norms are taken on the device (one reduction a
leaf) and the initial weights are made again from the seed where the
comparison needs them, not kept on the host; the embedding table's
touched rows go through ``train_cell.table_rows`` as every table does.  An
example is one packed sequence: ``train_examples_per_s_per_chip`` is
completed sequences over the window's seconds, tokens a second is printed
beside it.  A traffic file of this kind states under ``rows`` the
generator's parameters (``harness/seqgen.py``) and under ``replay`` what
``train`` files state.

The window is whole passes over the replayed set (``distinct_batches``
sequences), each pass in an order ``--seed`` picks: a step's cost follows
the documents of its sequence (the attention cores visit only the blocks a
document reaches), so a window that closes at a time trains another
sample of the set in every run.  Set-up's steps come from the
stream's first pass and the rest of that pass is skipped, so the window
opens at a pass boundary; it holds the least number of passes whose steps,
at the warm-up's step time, cover ``--seconds``.
"""

from __future__ import annotations

import gc
import math
import os
import time
import zlib
from typing import Dict, List, Tuple

import numpy as np

from benchmarks.harness import common, reference, seqgen, train_cell
from benchmarks.harness.peaks import peaks_for
from benchmarks.harness.train_cell import norms, table_rows

CHECK_STEPS = 3
WARM_STEPS = 2
MOE_HELD = "trainer_moe_held_assignments_total"


def feed(ctx: Dict, cache, registry):
    """``train_cell``'s timed input pipeline, in the order ``--seed`` picks;
    a step's batch is ``cfg['sequences']`` rows of the shards (``cfg['batch']``
    counts tokens here)."""
    cfg = dict(ctx["cfg"], batch=ctx["cfg"]["sequences"])
    return train_cell.feed(dict(ctx, cfg=cfg), cache, registry)


def window_plan(pass_steps: int, consumed: int, seconds: float,
                step_s: float) -> Tuple[int, int]:
    """``(skip, passes)``: the batches to skip after set-up's ``consumed``
    so that the window opens at a pass boundary, and the least whole number
    of passes whose steps, at ``step_s`` a step, cover ``seconds``."""
    return -consumed % pass_steps, max(1, math.ceil(seconds / (pass_steps * step_s)))


def compact_batches(views: List[Dict], union: Dict[str, np.ndarray]) -> List[Dict]:
    """The reference's batches: token ids rewritten to positions in the
    compacted table, the document numbers, and each position's next token
    (an id of the slice: a column of the head)."""
    out = []
    for v in views:
        tokens = np.asarray(v["tokens"])
        out.append({
            "tokens": np.searchsorted(union["tokens"], tokens).astype(np.int32),
            "segments": np.asarray(v["segments"], np.int32),
            "targets": np.roll(tokens, -1, axis=1).astype(np.int32)})
    return out


def _dense(tree: Dict, model) -> Dict:
    return {k: v for k, v in tree.items() if k not in model.TABLES}


def _device_norms(fn, *trees) -> Dict[str, float]:
    """Per leaf ``sqrt(sum(fn(leaves)))``, reduced on the device."""
    import jax
    import jax.numpy as jnp

    out = jax.jit(lambda *t: jax.tree_util.tree_map(
        lambda *x: jnp.sqrt(jnp.sum(fn(*x))), *t))(*trees)
    return {k: float(v) for k, v in reference.flat_leaves(jax.device_get(out)).items()}


def first_gradient_norms(trainer, union, model, pad_to) -> Dict[str, float]:
    """Adagrad's accumulator after one step is the squared first gradient
    as the optimizer got it: its root-sum per leaf is the gradient's norm."""
    state = trainer.opt_state
    out = _device_norms(lambda a: a, _dense(state["dense"].accum, model))
    rows = table_rows(dict(state["accum"]), union, model, pad_to)
    out.update({k: float(np.sqrt(np.sum(np.asarray(v, np.float64))))
                for k, v in rows.items()})
    return out


def run(ctx: Dict) -> Dict:
    import jax
    import jax.numpy as jnp

    cfg, traffic, model, cell = ctx["cfg"], ctx["traffic"], ctx["model"], ctx["cell"]
    chips, seconds = cell["chips"], float(ctx["seconds"])
    try:
        model.spec_of(cfg)
    except ImportError as e:
        raise SystemExit(f"benchmark: this checkout's program cannot run "
                         f"{cfg['model']!r} ({e}); nothing was run")
    from lightctr_tpu import obs

    phases, last = {}, [ctx["t_start"]]

    def mark(name: str) -> None:
        now = time.perf_counter()
        phases[name], last[0] = now - last[0], now

    mark("startup")
    common.configure(cfg)
    compiles = common.CompileCounter()
    cache = seqgen.shard_cache(ctx["cache_root"], cfg, traffic)
    ingest_registry = obs.MetricsRegistry()
    stream = feed(ctx, cache, ingest_registry)
    tokens_a_step = cfg["batch"]
    key = common.prng_key(ctx["seed"])
    init = jax.jit(lambda k: model.init_params(cfg, k))
    try:
        # -- set-up: weights, the trainer, the first steps ----------------
        first = [next(stream) for _ in range(CHECK_STEPS)]
        views = model.reference_view(first)
        union = reference.touched_union(model, views)
        mark("rows_and_feed")
        pad_to = CHECK_STEPS * tokens_a_step               # every id distinct
        params0 = init(key)
        tables0 = table_rows({k: params0[k] for k in model.TABLES}, union, model, pad_to)
        mark("weights")
        trainer = model.build_trainer(cfg, params0)
        trainer.telemetry = obs.MetricsRegistry()
        del params0
        mark("trainer")
        got = {"loss": []}
        for i, b in enumerate(first):
            got["loss"].append(float(trainer.train_step(b)))
            if i == 0:
                got["grad_norm"] = first_gradient_norms(trainer, union, model, pad_to)
        now = trainer.params
        got["change_norm"] = _device_norms(
            lambda a, b_: (a - b_) ** 2, _dense(now, model), _dense(init(key), model))
        got["change_norm"].update(norms(
            table_rows({k: now[k] for k in model.TABLES}, union, model, pad_to), tables0))
        del now
        mark("checked_steps")
        t_warm = time.perf_counter()
        for _ in range(WARM_STEPS):
            loss = trainer.train_step(next(stream))
        jax.block_until_ready((loss, trainer.params))
        step_s = (time.perf_counter() - t_warm) / WARM_STEPS
        pass_steps = int(traffic["rows"]["distinct_batches"])
        skip, passes = window_plan(pass_steps, CHECK_STEPS + WARM_STEPS,
                                   seconds, step_s)
        for _ in range(skip):
            next(stream)                   # the rest of the first pass
        trainer.flush_health()
        before = trainer.telemetry.snapshot()["counters"]
        gc.collect()
        compiles.reset()
        mark("warm_up")

        # -- the window: whole passes --------------------------------------
        trace_dir = (os.path.join(ctx["cache_root"], "trace", cell["name"])
                     if ctx["trace"] else None)
        steps, wait_s, trained = passes * pass_steps, 0.0, []
        with common.profiled(trace_dir):
            t0 = time.perf_counter()
            setup_s = t0 - ctx["t_start"]
            with common.span("window"):
                for _ in range(steps):
                    with common.span("next_batch"):
                        tw = time.perf_counter()
                        batch = next(stream)
                        wait_s += time.perf_counter() - tw
                    with common.span("train_step"):
                        loss = trainer.train_step(batch)
                    trained.append(zlib.crc32(np.ascontiguousarray(batch["fids"])))
                with common.span("final_sync"):
                    jax.block_until_ready((loss, trainer.params))
            window_s = time.perf_counter() - t0
        compiled_in_window = compiles.count
        mem = common.memory(chips)
        last_loss = float(loss)
    finally:
        stream.close()
    trainer.flush_health()
    counters = trainer.telemetry.snapshot()["counters"]
    # the window's own held assignments, a step: the cost is the window's
    held = sum(v - before.get(k, 0) for k, v in counters.items()
               if k.startswith(MOE_HELD)) / steps
    reduced = common.read_trace(trace_dir) if trace_dir else None

    # -- the program's state goes, then the reference runs -----------------
    del trainer, loss
    gc.collect()
    compact = compact_batches(views, union)

    def params0():
        return dict(_dense(init(key), model),
                    **{k: jnp.asarray(v) for k, v in tables0.items()})

    want = model.reference_steps(cfg, params0, compact)
    numbers = reference.compare(got, want)
    numbers["last_loss_finite"] = 0.0 if np.isfinite(last_loss) else 1.0
    v = reference.verdict(numbers, dict(ctx["limits"], last_loss_finite=0.0))
    sequences = steps * cfg["sequences"]
    counted_steps = counters.get("trainer_seq_tokens_total", 0) / tokens_a_step
    extra = {"compiles_in_window": compiled_in_window, "setup_s": setup_s,
             "setup_phases_s": phases, "steps": steps, "passes": passes,
             "warm_step_s": step_s,
             # each step's sequence, by a checksum of its token ids
             "window_sequences": trained,
             "tokens_per_s": steps * tokens_a_step / window_s,
             "memory_in_use_bytes": mem["in_use"],
             # what the program counted, a step (all its steps, set-up's too)
             "counted_per_step": {k: v / counted_steps for k, v in sorted(
                 counters.items()) if k.startswith(("trainer_seq_", "trainer_moe_"))
                 and counted_steps}}
    if "calibrate" in ctx:
        # the control and the planted faults, read as the program is read
        extra["variants"] = {
            name: reference.compare(
                model.reference_steps(cfg, params0, compact, name), want)
            for name in ctx["calibrate"]}

    seq = seqgen.counted(traffic["rows"], tokens=tokens_a_step // cfg["sequences"],
                         vocab=cfg["vocab"], sequences=cfg["sequences"])
    out = {
        "correct": v["correct"], "checks": v["checks"],
        "attempted": steps, "failed": 0,
        "end_to_end": {
            "train_examples_per_s_per_chip": sequences / window_s / chips,
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
        "counters": counters, "distinct_ids": seq["distinct_rows_per_step"],
        "cost": model.train_step_cost(
            cfg, seq["distinct_rows_per_step"], held,
            seq["attended_pairs_per_step"]),
    }
    return common.attach_trace(out, reduced)
