"""Full benchmark matrix vs the reference's published charts (BASELINE.md).

Cells:
  FM  k=8/16/32/64   — 1000 full-batch epochs on train_sparse.csv (1000 rows);
                       baseline 9.32/12.35/18.14/29.94 s  (vs_libfm.png)
  FFM k=2/4/8/16     — 100 full-batch epochs, same data;
                       baseline 48.92/64.69/81.22/114.82 s (vs_libffm.png)
  NN  batch=50..400  — 5000 minibatch steps of the LeNet CNN on
                       train_dense.csv; baseline 26.08/45.52/102.82/202.23 s
                       (vs_tf_cpu.png — the reference's DL-family benchmark)

Each cell prints one JSON line {"metric", "value", "unit", "vs_baseline"} and
the full matrix is written to BENCH_MATRIX.json with device info.  Runs on a
TPU or not at all (exit code non-zero, nothing measured).

Usage: python bench_matrix.py [--quick] [--only fm|ffm|nn]
  --quick: 1/10th epochs/steps (CI smoke; vs_baseline scaled accordingly).
"""

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from lightctr_tpu.data.synth import (
    REFERENCE_DENSE,
    REFERENCE_SPARSE,
    resolve_dense_csv,
    resolve_libffm,
)

# vs_baseline compares against BASELINE.md timings measured on the
# reference datasets; on substitute (synthetic) data the ratio is not
# comparable and is reported as null.  Resolution is lazy (inside each
# bench) and per-dataset: a partially-mounted reference still yields real
# vs_baseline numbers for the cells that ran on reference data.
_RESOLVED = {}


def _sparse_data():
    if "sparse" not in _RESOLVED:
        path = resolve_libffm()
        _RESOLVED["sparse"] = (path, path == REFERENCE_SPARSE)
    return _RESOLVED["sparse"]


def _dense_data():
    if "dense" not in _RESOLVED:
        path = resolve_dense_csv()
        _RESOLVED["dense"] = (path, path == REFERENCE_DENSE)
    return _RESOLVED["dense"]

# reference seconds per full workload (BASELINE.md)
FM_BASE_S = {8: 9.32, 16: 12.35, 32: 18.14, 64: 29.94}       # 1000 epochs
FFM_BASE_S = {2: 48.92, 4: 64.69, 8: 81.22, 16: 114.82}      # 100 epochs
NN_BASE_S = {50: 26.08, 100: 45.52, 200: 102.82, 400: 202.23}  # 5000 steps


def _best_of(fn, reps=3):
    best = float("inf")
    for r in range(reps):
        dt = fn()
        print(f"    rep {r}: {dt:.3f}s", file=sys.stderr)
        best = min(best, dt)
    return best


def bench_fm(epochs):
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.data import load_libffm
    from lightctr_tpu.models import fm
    from lightctr_tpu.models.ctr_trainer import CTRTrainer

    sparse_path, comparable = _sparse_data()
    ds, _ = load_libffm(sparse_path).compact()
    arrays = ds.batch_dict()
    n_rows = len(arrays["labels"])
    cfg = TrainConfig(learning_rate=0.1, lambda_l2=0.001)

    dense = fm.densify(arrays, ds.feature_cnt)
    dense = {k: jax.device_put(jnp.asarray(v)) for k, v in dense.items()}
    jax.block_until_ready(dense)

    out = []
    for k in (8, 16, 32, 64):
        params = fm.init(jax.random.PRNGKey(0), ds.feature_cnt, k)
        tr = CTRTrainer(
            params, fm.dense_logits, cfg, fused_fn=fm.dense_logits_with_l2
        )
        tr.warmup_fullbatch_scan(dense, epochs)

        def one():
            tr.reset(params)
            t0 = time.perf_counter()
            losses = tr.fit_fullbatch_scan(dense, epochs)
            jax.block_until_ready(tr.params)
            dt = time.perf_counter() - t0
            assert losses[-1] < losses[0], "diverged"
            return dt

        dt = _best_of(one)
        ex_s = epochs * n_rows / dt
        base_ex_s = 1000 * 1000 / FM_BASE_S[k]
        out.append({
            "metric": f"fm_k{k}_train_examples_per_sec",
            "value": round(ex_s, 1),
            "unit": "examples/s",
            "vs_baseline": round(ex_s / base_ex_s, 3) if comparable else None,
        })
        print(json.dumps(out[-1]), flush=True)
    return out


def bench_ffm(epochs):
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.data import load_libffm
    from lightctr_tpu.models import ffm
    from lightctr_tpu.models.ctr_trainer import CTRTrainer

    sparse_path, comparable = _sparse_data()
    ds, _ = load_libffm(sparse_path).compact()
    arrays = ds.batch_dict()
    n_rows = len(arrays["labels"])
    cfg = TrainConfig(learning_rate=0.1, lambda_l2=0.001)

    dense, perm, slices = ffm.densify(arrays, ds.feature_cnt, ds.field_cnt)
    dense = {k: jax.device_put(jnp.asarray(v)) for k, v in dense.items()}
    jax.block_until_ready(dense)
    fused = ffm.make_dense_logits(slices)

    out = []
    for k in (2, 4, 8, 16):
        p0 = ffm.init(jax.random.PRNGKey(0), ds.feature_cnt, ds.field_cnt, k)
        params = {"w": p0["w"][perm], "v": p0["v"][perm]}
        tr = CTRTrainer(params, lambda p, b: fused(p, b)[0], cfg, fused_fn=fused)
        tr.warmup_fullbatch_scan(dense, epochs)

        def one():
            tr.reset(params)
            t0 = time.perf_counter()
            losses = tr.fit_fullbatch_scan(dense, epochs)
            jax.block_until_ready(tr.params)
            dt = time.perf_counter() - t0
            assert losses[-1] < losses[0], "diverged"
            return dt

        dt = _best_of(one)
        ex_s = epochs * n_rows / dt
        base_ex_s = 100 * 1000 / FFM_BASE_S[k]
        out.append({
            "metric": f"ffm_k{k}_train_examples_per_sec",
            "value": round(ex_s, 1),
            "unit": "examples/s",
            "vs_baseline": round(ex_s / base_ex_s, 3) if comparable else None,
        })
        print(json.dumps(out[-1]), flush=True)
    return out


def bench_nn(steps):
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.data import load_dense_csv
    from lightctr_tpu.models import cnn
    from lightctr_tpu.models.dl_trainer import ClassifierTrainer

    dense_path, comparable = _dense_data()
    ds = load_dense_csv(dense_path)
    # pre-transfer data + minibatch schedules once, outside the timed region
    # (same methodology as the FM/FFM cells)
    feats = jax.device_put(jnp.asarray(ds.features))
    labels = jax.device_put(jnp.asarray(ds.labels.astype(np.int32)))
    jax.block_until_ready((feats, labels))
    rng = np.random.default_rng(1)
    cfg = TrainConfig(learning_rate=0.1, minibatch_size=50)

    out = []
    for batch in (50, 100, 200, 400):
        params = cnn.init(jax.random.PRNGKey(0), hidden=100, n_classes=10)
        tr = ClassifierTrainer(params, cnn.logits, cfg, n_classes=10)
        idx = jax.device_put(jnp.asarray(
            rng.integers(0, len(ds.features), size=(steps, batch)).astype(np.int32)
        ))
        jax.block_until_ready(idx)
        tr.warmup_steps_scan(feats, labels, steps, batch)

        def one():
            tr.reset(params)
            t0 = time.perf_counter()
            losses = tr.fit_steps_scan(feats, labels, steps, batch, idx=idx)
            jax.block_until_ready(tr.params)
            dt = time.perf_counter() - t0
            assert np.isfinite(losses[-1]), "diverged"
            return dt

        dt = _best_of(one)
        ex_s = steps * batch / dt
        base_ex_s = 5000 * batch / NN_BASE_S[batch]
        out.append({
            "metric": f"nn_batch{batch}_train_examples_per_sec",
            "value": round(ex_s, 1),
            "unit": "examples/s",
            "vs_baseline": round(ex_s / base_ex_s, 3) if comparable else None,
        })
        print(json.dumps(out[-1]), flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="1/10th workload")
    ap.add_argument("--only", choices=["fm", "ffm", "nn"])
    ap.add_argument(
        "--out", default=None,
        help="output JSON (default BENCH_MATRIX.json for full runs; partial "
             "or --quick runs default to BENCH_MATRIX_partial.json so they "
             "never clobber the full-matrix artifact)",
    )
    args = ap.parse_args()
    out_path = args.out or (
        "BENCH_MATRIX.json" if not args.quick and args.only is None
        else "BENCH_MATRIX_partial.json"
    )
    scale = 10 if args.quick else 1

    from lightctr_tpu.utils.compile_cache import configure_compile_cache
    from lightctr_tpu.utils.devicecheck import require_tpu

    device = require_tpu("bench_matrix")
    configure_compile_cache()

    results = []
    if args.only in (None, "fm"):
        results += bench_fm(1000 // scale)
    if args.only in (None, "ffm"):
        results += bench_ffm(100 // scale)
    if args.only in (None, "nn"):
        results += bench_nn(5000 // scale)

    payload = {
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices())},
        "quick": args.quick,
        "results": results,
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {out_path} ({len(results)} cells)", file=sys.stderr)


if __name__ == "__main__":
    main()
