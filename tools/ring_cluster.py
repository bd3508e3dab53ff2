"""Ring-AllReduce cluster: the reference's ring deployment as REAL processes.

The reference's second distributed mode is a ring of workers exchanging
gradient segments neighbor-to-neighbor (``ring_collect.h:48-218``,
deployed by ``build_ring.sh``, benchmarked in ``4_node_ring.png``).  The
repo's explicit ``ppermute`` ring (``dist/collectives.py``) is proven on
the single-process virtual mesh; THIS tool proves it across OS process
boundaries: two processes (2 local CPU devices each) join via
``jax.distributed``, build one 4-member global ring, and train
data-parallel FM with every gradient exchange running through the
explicit reduce-scatter/all-gather ring program — exact, with 16-bit-coded
hops (the reference's primary fp16 wire policy), and with int8-coded hops
(its QuantileCompress extreme; the reference compresses all its ring wire
traffic, ``buffer.h:140-149``).

Parity oracle: a single-process run of the identical schedule (same init,
same full-batch steps, plain mean gradients).  The exact ring must match
it to float tolerance; the int8 ring must still converge to the same AUC
neighborhood (quantization noise accumulates once per reduce hop).

Run:  python -m tools.ring_cluster [--epochs 60] [--out RING_CLUSTER.json]

Host-side tool: the launcher pins the CPU platform
(``utils.devicecheck.pin_cpu_platform``) before it starts a worker, the
workers inherit the pin, and nothing here touches an accelerator — a chip
belongs to one process at a time, so a launcher that held it would starve
its own children.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

# 2 procs x 2 devices = 4-member ring by default; RING_PROCS/RING_DEVS
# scale the topology (e.g. RING_PROCS=4 -> an 8-member ring of real
# processes, the reference's N-node build_ring.sh scaled up)
N_PROC = int(os.environ.get("RING_PROCS", "2"))
LOCAL_DEVICES = int(os.environ.get("RING_DEVS", "2"))
RING = N_PROC * LOCAL_DEVICES
# codec range: "dynamic" (the default) measures the ring-global gradient
# magnitude per call (one scalar pmax) so the table TRACKS the gradient
# scale through training — any fixed range turns late-training small
# gradients into pure bucket noise (measured on this workload: fixed 0.5
# normal-table int8 lands logloss 0.082 vs 0.023 dynamic).  A float value
# pins a fixed range instead; it must bound the largest per-member mean
# gradient.  Override via RING_CRANGE.
_crange_env = os.environ.get("RING_CRANGE", "dynamic")
CRANGE = _crange_env if _crange_env == "dynamic" else float(_crange_env)
# codec table shape: "normal" concentrates bucket resolution near zero,
# where gradients live — the reference's QuantileCompress ships exactly
# such CDF tables (quantile_compress.h:38-107); "uniform" is the naive
# fixed-step comparison.  Override via RING_CMODE.
CMODE = os.environ.get("RING_CMODE", "normal")


# ---------------------------------------------------------------------------
# worker process (``--worker``): one ring member pair


def worker_main(pid: int, port: int, data_path: str, out_dir: str,
                epochs: int, compress_bits: int, lr: float):
    if os.environ.get("LIGHTCTR_RING_DEBUG"):
        import faulthandler

        faulthandler.dump_traceback_later(120, exit=True)

    def dbg(msg):
        if os.environ.get("LIGHTCTR_RING_DEBUG"):
            print(f"[ring w{pid}] {msg}", file=sys.stderr, flush=True)

    # env (JAX_PLATFORMS/XLA_FLAGS) is set by the
    # coordinator BEFORE this interpreter started; jax imports are safe here
    import jax

    jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import optax
    from jax import shard_map
    from jax.experimental import multihost_utils
    from jax.flatten_util import ravel_pytree
    from jax.sharding import Mesh, PartitionSpec as P

    from lightctr_tpu import TrainConfig, optim
    from lightctr_tpu.data import load_libffm
    from lightctr_tpu.dist import initialize_multihost
    from lightctr_tpu.dist.collectives import _ring_all_reduce_local
    from lightctr_tpu.models import fm
    from lightctr_tpu.ops import losses as losses_lib

    initialize_multihost(coordinator_address=f"127.0.0.1:{port}",
                         num_processes=N_PROC, process_id=pid)
    assert jax.device_count() == RING
    mesh = Mesh(np.asarray(jax.devices()).reshape(RING), ("data",))

    ds, _ = load_libffm(data_path).compact()
    arrays = ds.batch_dict()
    n_rows = (len(arrays["labels"]) // RING) * RING
    arrays = {k: v[:n_rows] for k, v in arrays.items()}

    params = fm.init(jax.random.PRNGKey(0), ds.feature_cnt, 8)
    cfg = TrainConfig(learning_rate=lr, lambda_l2=0.001)
    tx = optim.adagrad(cfg.learning_rate)
    opt_state = tx.init(params)

    def loss_fn(p, batch):
        z, l2 = fm.logits_with_l2(p, batch)
        # l2 here covers only THIS member's shard; the ring averages the
        # member grads (x 1/RING), so scale by RING to recover the
        # single-process coefficient lambda * l2_full / n_rows exactly
        return (losses_lib.logistic_loss(z, batch["labels"],
                                         reduction="mean")
                + cfg.lambda_l2 * l2 * RING / n_rows)

    bits = compress_bits if compress_bits > 0 else None
    # int8 hops run with ERROR FEEDBACK (EF-SGD): each member carries its
    # per-segment quantization error into the next step's encode, so the
    # codec's bias becomes a delayed contribution instead of a loss — how
    # the reference's fully-coded ring wire still lands ~1.0 accuracy
    # (4_node_ring.png, quantile_compress.h:38-107).  16-bit hops stay
    # plain: the fp16-policy comparison point is already parity-grade.
    use_ef = (bits is not None and bits <= 8
              and os.environ.get("RING_EF", "1") != "0")

    def local(p_s, opt_s, res_s, batch_shard):
        # every ring member holds its OWN param replica (stacked leaves,
        # leading dim 1 per device — exactly the reference's N independent
        # workers): grads stay per-member and the EXPLICIT neighbor ring
        # does the averaging (ring_collect.h:114-218 over lax.ppermute).
        # Replicated (unvarying) params would not work here: shard_map
        # autodiff inserts an implicit psum for them, pre-reducing the
        # gradient before the ring ever ran.
        p = jax.tree_util.tree_map(lambda x: x[0], p_s)
        opt = jax.tree_util.tree_map(lambda x: x[0], opt_s)
        g = jax.grad(loss_fn)(p, batch_shard)
        flat, unravel = ravel_pytree(g)
        length = flat.shape[0]
        padded = ((length + RING - 1) // RING) * RING
        if padded != length:
            flat = jnp.pad(flat, (0, padded - length))
        mode = CMODE if (bits is not None and bits <= 8) else "uniform"
        if use_ef:
            flat, new_res = _ring_all_reduce_local(
                flat, "data", RING, True,
                compress_bits=bits, compress_range=CRANGE,
                residual=res_s[0], compress_mode=mode,
            )
        else:
            flat = _ring_all_reduce_local(
                flat, "data", RING, True,
                compress_bits=bits, compress_range=CRANGE,
                compress_mode=mode,
            )
            new_res = res_s[0]
        g = unravel(flat[:length])
        upd, new_opt = tx.update(g, opt, p)
        new_p = optax.apply_updates(p, upd)
        expand = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        return expand(new_p), expand(new_opt), new_res[None]

    step = jax.jit(shard_map(
        local, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P("data")),
        out_specs=(P("data"), P("data"), P("data")),
    ))

    def replicate(tree):
        # one stacked copy per LOCAL device; globally a [RING, ...] array
        # sharded over the ring axis — each member its own replica
        return jax.tree_util.tree_map(
            lambda x: multihost_utils.host_local_array_to_global_array(
                np.tile(np.asarray(x)[None],
                        (LOCAL_DEVICES,) + (1,) * np.asarray(x).ndim),
                mesh, P("data")
            ),
            tree,
        )

    # this process contributes its HALF of every row-dimension array
    half = n_rows // N_PROC

    def shard_batch(tree):
        return jax.tree_util.tree_map(
            lambda x: multihost_utils.host_local_array_to_global_array(
                np.asarray(x[pid * half:(pid + 1) * half]), mesh, P("data")
            ),
            tree,
        )

    dbg("distributed up; building global arrays")
    gp = replicate(params)
    gopt = replicate(opt_state)
    gbatch = shard_batch(arrays)
    dbg("global arrays built")
    # per-member EF residual carry: zeros [RING, padded_grad_len] sharded
    # over the ring (unused-but-threaded when EF is off)
    flat_len = sum(int(np.prod(np.asarray(v).shape)) for v in params.values())
    padded_len = ((flat_len + RING - 1) // RING) * RING if use_ef else 1
    gres = multihost_utils.host_local_array_to_global_array(
        np.zeros((LOCAL_DEVICES, padded_len), np.float32), mesh, P("data")
    )

    losses = []
    t0 = time.perf_counter()
    for e in range(epochs):
        gp, gopt, gres = step(gp, gopt, gres, gbatch)
        if (e + 1) % 8 == 0:
            # bound the async-dispatch depth: two processes racing dozens
            # of un-awaited multi-output collective programs can deadlock
            # the cross-process execution queues (observed at 60 epochs x
            # 3 outputs); an occasional sync keeps them in lockstep
            jax.block_until_ready(gres)
        if e == 0:
            dbg("first step dispatched")
    jax.block_until_ready(gp)
    dbg("steps done")
    wall = time.perf_counter() - t0

    if pid == 0:
        final = jax.tree_util.tree_map(
            lambda x: np.asarray(
                multihost_utils.global_array_to_host_local_array(
                    x, mesh, P("data")
                )
            )[0],  # all replicas identical after the averaged ring
            gp,
        )
        np.savez(os.path.join(out_dir, f"ring_params_b{compress_bits}.npz"),
                 **final)
        with open(os.path.join(out_dir,
                               f"ring_meta_b{compress_bits}.json"),
                  "w") as f:
            json.dump({"wall_s": round(wall, 2), "epochs": epochs,
                       "rows": n_rows, "ring": RING,
                       "error_feedback": use_ef}, f)
    # all processes must stay alive until proc 0 finished its fetch
    multihost_utils.sync_global_devices("ring_cluster_done")


# ---------------------------------------------------------------------------
# coordinator


def run(data_path=None, epochs=60, lr=0.1, out="RING_CLUSTER.json",
        workdir=None, variants=(0, 16, 8)):
    """variants: which codec widths to launch (0 = exact).  Tests run
    (0,) alone — the cross-process bit-parity claim — to stay fast."""
    import tempfile

    workdir = workdir or tempfile.mkdtemp(prefix="ring_cluster_")
    from lightctr_tpu.data.synth import resolve_libffm

    data_path = resolve_libffm(data_path, workdir)

    def launch(compress_bits):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        # MERGE XLA_FLAGS (don't overwrite): the in-process oracle runs
        # with the user's flags, so the workers must too or the parity
        # assert compares different XLA configs
        base_flags = os.environ.get("XLA_FLAGS", "")
        import re

        base_flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", "", base_flags
        ).strip()
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS=(base_flags + " " if base_flags else "")
            + f"--xla_force_host_platform_device_count={LOCAL_DEVICES}",
        )
        env["PYTHONPATH"] = REPO_ROOT + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
        )
        # logs go to files, not PIPEs: a worker that fills a 64KB pipe
        # buffer would block before the end-of-run barrier and deadlock
        # the sequential reaping below
        logs = [open(os.path.join(
            workdir, f"ring_worker_b{compress_bits}_{i}.log"), "w")
            for i in range(N_PROC)]
        procs = [
            subprocess.Popen(
                [sys.executable, "-m", "tools.ring_cluster", "--worker",
                 "--pid", str(i), "--port", str(port), "--data", data_path,
                 "--workdir", workdir, "--epochs", str(epochs),
                 "--compress-bits", str(compress_bits), "--lr", str(lr)],
                env=env, cwd=REPO_ROOT,
                stdout=logs[i], stderr=subprocess.STDOUT,
            )
            for i in range(N_PROC)
        ]
        try:
            for i, p in enumerate(procs):
                try:
                    p.wait(timeout=600)
                except subprocess.TimeoutExpired:
                    raise RuntimeError(f"ring worker {i} timed out")
                if p.returncode != 0:
                    logs[i].flush()
                    tail = open(logs[i].name).read()[-2000:]
                    raise RuntimeError(
                        f"ring worker {i} failed ({p.returncode}):\n{tail}"
                    )
        finally:
            # never leak a live worker: a failed/timed-out member's peers
            # sit in jax.distributed retries otherwise
            for p in procs:
                if p.poll() is None:
                    p.kill()
            for f in logs:
                f.close()
        with open(os.path.join(workdir,
                               f"ring_meta_b{compress_bits}.json")) as f:
            meta = json.load(f)
        params = dict(np.load(os.path.join(
            workdir, f"ring_params_b{compress_bits}.npz"
        )))
        return params, meta

    # -- cluster runs: exact ring; 16-bit-coded hops (the reference's
    # primary fp16 wire policy, buffer.h:140-149); int8 hops (its
    # QuantileCompress extreme — noisier by construction)
    if 0 not in variants:
        raise ValueError("variants must include 0 (the exact ring is the "
                         "parity oracle every other variant compares to)")
    from lightctr_tpu.utils.devicecheck import pin_cpu_platform

    pin_cpu_platform(1)  # the launcher too, before any worker starts
    results = {b: launch(b) for b in variants}
    exact_params, exact_meta = results[0]

    # -- single-process oracle: identical schedule, plain mean gradients
    import jax
    import jax.numpy as jnp
    import optax

    from lightctr_tpu import TrainConfig, optim
    from lightctr_tpu.data import load_libffm
    from lightctr_tpu.models import fm
    from lightctr_tpu.ops import losses as losses_lib
    from lightctr_tpu.ops.activations import sigmoid
    from lightctr_tpu.ops.metrics import auc_exact, logloss

    ds, _ = load_libffm(data_path).compact()
    arrays = ds.batch_dict()
    n_rows = (len(arrays["labels"]) // RING) * RING
    arrays = {k: jnp.asarray(v[:n_rows]) for k, v in arrays.items()}

    params = fm.init(jax.random.PRNGKey(0), ds.feature_cnt, 8)
    cfg = TrainConfig(learning_rate=lr, lambda_l2=0.001)
    tx = optim.adagrad(cfg.learning_rate)
    opt_state = tx.init(params)

    def loss_fn(p, batch):
        z, l2 = fm.logits_with_l2(p, batch)
        return (losses_lib.logistic_loss(z, batch["labels"],
                                         reduction="mean")
                + cfg.lambda_l2 * l2 / n_rows)

    @jax.jit
    def step(p, opt, batch):
        g = jax.grad(loss_fn)(p, batch)
        upd, new_opt = tx.update(g, opt, p)
        return optax.apply_updates(p, upd), new_opt

    for _ in range(epochs):
        params, opt_state = step(params, opt_state, arrays)
    single = {k: np.asarray(v) for k, v in params.items()}

    def evaluate(p):
        z = fm.logits(
            jax.tree_util.tree_map(jnp.asarray, dict(p)), arrays
        )
        probs = np.asarray(sigmoid(z))
        labels = np.asarray(arrays["labels"])
        return {
            "logloss": float(logloss(jnp.asarray(probs),
                                     arrays["labels"])),
            "auc": float(auc_exact(probs, labels.astype(np.int32))),
        }

    exact_diff = max(
        float(np.max(np.abs(exact_params[k] - single[k])))
        for k in single
    )
    report = {
        "topology": f"{N_PROC} OS processes x {LOCAL_DEVICES} devices = "
                    f"{RING}-member ring (jax.distributed over localhost)",
        "schedule": "explicit reduce-scatter/all-gather ring over "
                    "lax.ppermute (ring_collect.h counterpart), "
                    "full-batch FM adagrad",
        "epochs": epochs, "rows": n_rows,
        "exact_ring": {**exact_meta, **evaluate(exact_params),
                       "max_param_diff_vs_single": exact_diff},
        "single_process": evaluate(single),
    }
    if 16 in results:
        report["int16_ring"] = {**results[16][1],
                                **evaluate(results[16][0])}
    if 8 in results:
        report["int8_ring"] = {**results[8][1],
                               **evaluate(results[8][0])}
    print(json.dumps(report, indent=1))
    assert exact_diff < 1e-4, f"exact ring diverged: {exact_diff}"
    if 16 in results:
        # 16-bit hops: the fp16-policy counterpart — parity-grade
        assert abs(report["int16_ring"]["auc"]
                   - report["single_process"]["auc"]) < 0.01
    if 8 in results:
        if report["int8_ring"].get("error_feedback"):
            # 8-bit hops + error feedback + dynamic range: the codec's
            # bias is carried, not lost — the int8 ring must land in the
            # exact ring's AUC neighborhood (the reference's fully-coded
            # wire bar)
            assert abs(report["int8_ring"]["auc"]
                       - report["single_process"]["auc"]) < 0.01, \
                report["int8_ring"]["auc"]
        else:
            # RING_EF=0 A/B baseline: memoryless codec noise feeds the
            # accumulator — converges, but slower by construction
            assert report["int8_ring"]["auc"] > 0.75, \
                report["int8_ring"]["auc"]
    if out:
        with open(out, "w") as f:
            json.dump(report, f, indent=1)
    return report


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--pid", type=int, default=0)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--data", default=None)
    ap.add_argument("--workdir", default=None)
    ap.add_argument("--epochs", type=int, default=60)
    ap.add_argument("--compress-bits", type=int, default=0)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--out", default="RING_CLUSTER.json")
    args = ap.parse_args()
    if args.worker:
        worker_main(args.pid, args.port, args.data, args.workdir,
                    args.epochs, args.compress_bits, args.lr)
    else:
        run(data_path=args.data, epochs=args.epochs, lr=args.lr,
            out=args.out, workdir=args.workdir)


if __name__ == "__main__":
    main()
