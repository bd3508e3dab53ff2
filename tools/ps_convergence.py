"""PS-mode end-to-end convergence: N worker PROCESSES × shared-memory PS.

The counterpart of the reference's 4-node PS benchmark
(``/root/reference/benchmark/4_node_ps.png``; protocol
``distribut/paramserver.h:127-210``): several worker processes train
Wide&Deep on the reference dataset against one ``ShmAsyncParamServer``,
asynchronously pushing Adagrad updates with atomic float-CAS — then the
result is evaluated against a single-process run of the same schedule.

Layout on the PS (one row per feature id, dim = 1 + factor_dim):
  row[0]  = wide weight      (the reference keeps W in the PS sparse table,
                              distributed_algo_abst.h:203-212)
  row[1:] = embedding vector (the PS tensor table, ibid:210-226)
fusing the two pulls the reference makes per key into one round trip.  The
deep MLP (fc1/fc2) is stored as dim-sized chunks under ``DENSE_BASE`` keys —
dense blobs sharded as PS rows — preloaded by the coordinator
(``preload`` = master syncInitializer) so every process starts identically.

Workers:
  - hold a strided row shard (worker ``w`` owns rows ``w::n_workers`` — the
    proc_file_split.py partition);
  - per minibatch: dedup touched fids, PULL rows + dense chunks, rewrite the
    batch's ids to positions, run ONE jitted value_and_grad on the compact
    tables (static shapes, so each worker compiles exactly once), PUSH
    per-key row grads + dense chunk grads;
  - SSP-gated: a pull too far ahead of the slowest worker is withheld
    (retried), a push too far behind is dropped — paramserver.h:201-205
    semantics via the shared ledger.

Run:  python -m tools.ps_convergence --workers 4 --epochs 30
Emits PS_CONVERGENCE.json: per-worker loss curves + final PS-trained
metrics vs the single-process baseline (the loss/accuracy-parity artifact).

Host-side tool: the launcher pins the CPU platform
(``utils.devicecheck.pin_cpu_platform``) before it starts a worker, the
workers inherit the pin, and nothing here touches an accelerator — a chip
belongs to one process at a time, so a launcher that held it would starve
its own children.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing as mp
import os
import sys
import time
from typing import Dict

import numpy as np

DENSE_BASE = 1 << 30


# ---------------------------------------------------------------------------
# shared model plumbing (host side)


def _dense_template(params) -> Dict[str, tuple]:
    """{leaf_name: shape} for the MLP leaves, in a fixed order."""
    return {
        "fc1.w": tuple(params["fc1"]["w"].shape),
        "fc1.b": tuple(params["fc1"]["b"].shape),
        "fc2.w": tuple(params["fc2"]["w"].shape),
        "fc2.b": tuple(params["fc2"]["b"].shape),
    }


def _flatten_dense(params) -> np.ndarray:
    return np.concatenate(
        [
            np.asarray(params["fc1"]["w"]).reshape(-1),
            np.asarray(params["fc1"]["b"]).reshape(-1),
            np.asarray(params["fc2"]["w"]).reshape(-1),
            np.asarray(params["fc2"]["b"]).reshape(-1),
        ]
    ).astype(np.float32)


def _unflatten_dense(vec: np.ndarray, template: Dict[str, tuple]):
    out = {}
    ofs = 0
    for name, shape in template.items():
        n = int(np.prod(shape))
        out[name] = vec[ofs : ofs + n].reshape(shape)
        ofs += n
    return {
        "fc1": {"w": out["fc1.w"], "b": out["fc1.b"]},
        "fc2": {"w": out["fc2.w"], "b": out["fc2.b"]},
    }


def _dense_chunks(vec: np.ndarray, row_dim: int) -> Dict[int, np.ndarray]:
    n_chunks = (len(vec) + row_dim - 1) // row_dim
    padded = np.zeros(n_chunks * row_dim, np.float32)
    padded[: len(vec)] = vec
    return {
        DENSE_BASE + i: padded[i * row_dim : (i + 1) * row_dim]
        for i in range(n_chunks)
    }


def _pull_retry(ps, keys, epoch, worker_id=None, max_wait_s: float = 30.0):
    """Pull with SSP-withheld retry (the reference worker blocks on the PS
    reply the same way, pull.h:50-67)."""
    t0 = time.time()
    while True:
        rows = ps.pull(keys, worker_epoch=epoch, worker_id=worker_id)
        if rows is not None:
            return rows
        if time.time() - t0 > max_wait_s:
            raise TimeoutError("SSP pull withheld for too long")
        time.sleep(0.002)


def _pull_rows_retry(ps, keys_sorted, epoch, worker_id=None,
                     max_wait_s: float = 30.0):
    """Array-form pull with SSP retry -> [n, dim] rows in ``keys_sorted``
    order.  Rides the vectorized path of whichever PS it's given:
    PSClient/ShardedPSClient.pull_arrays (wire) or
    ShmAsyncParamServer.pull_batch (one native get/add crossing)."""
    t0 = time.time()
    use_arrays = hasattr(ps, "pull_arrays")
    use_batch = hasattr(ps, "pull_batch")
    while True:
        if use_arrays:
            out = ps.pull_arrays(keys_sorted, worker_epoch=epoch,
                                 worker_id=worker_id)
            if out is not None:
                return out[1]
        elif use_batch:
            rows = ps.pull_batch(keys_sorted, worker_epoch=epoch,
                                 worker_id=worker_id)
            if rows is not None:
                return rows
        else:
            d = ps.pull(keys_sorted.tolist(), worker_epoch=epoch,
                        worker_id=worker_id)
            if d is not None:
                return np.stack([d[int(k)] for k in keys_sorted])
        if time.time() - t0 > max_wait_s:
            raise TimeoutError("SSP pull withheld for too long")
        time.sleep(0.002)


def _push_rows(ps, worker_id, keys_sorted, rows, epoch) -> bool:
    """Array-form push of rows[i] -> keys_sorted[i]."""
    if hasattr(ps, "push_arrays"):
        return ps.push_arrays(worker_id, keys_sorted, rows, worker_epoch=epoch)
    if hasattr(ps, "push_batch"):
        return ps.push_batch(worker_id, keys_sorted, rows, worker_epoch=epoch)
    return ps.push(
        worker_id,
        {int(k): rows[i] for i, k in enumerate(keys_sorted)},
        worker_epoch=epoch,
    )


# ---------------------------------------------------------------------------
# worker process


def _worker(base, worker_id, n_workers, payload, out_dir, cfg):
    from lightctr_tpu.utils.devicecheck import pin_cpu_platform

    pin_cpu_platform(1)

    import jax
    import jax.numpy as jnp

    from lightctr_tpu.embed.shm_ps import ShmAsyncParamServer
    from lightctr_tpu.models import widedeep
    from lightctr_tpu.ops import losses as losses_lib

    D = cfg["factor_dim"]
    row_dim = 1 + D
    B = cfg["batch_size"]
    template = {k: tuple(v) for k, v in cfg["dense_template"]}
    dense_len = sum(int(np.prod(s)) for s in template.values())

    if cfg.get("transport") == "tcp":
        # multi-node form: wire-coded pull/push to the PS service
        from lightctr_tpu.dist.ps_server import PSClient

        ps = PSClient(tuple(cfg["address"]), row_dim)
    else:
        ps = ShmAsyncParamServer.open(
            base, n_workers=n_workers, updater=cfg["updater"],
            learning_rate=cfg["lr"], staleness_threshold=cfg["staleness"],
        )

    data = payload  # the coordinator ships this worker's shard only
    n = len(data["labels"])
    if n < B:
        raise ValueError(f"worker shard has {n} rows < batch size {B}")
    if int(data["fids"].max()) >= DENSE_BASE:
        # the sparse/dense key split relies on DENSE_BASE dwarfing every
        # fid (keeps all_keys sorted); fail loud, not silently misaligned
        raise ValueError("feature id >= DENSE_BASE; raise DENSE_BASE")

    P = data["fids"].shape[1]
    FLD = data["rep_fids"].shape[1]
    U_w, U_e = B * P, B * FLD

    @jax.jit
    def grads_fn(wide_rows, embed_rows, fc1, fc2, batch):
        def loss(wr, er, f1, f2):
            params = {"w": wr, "embed": er, "fc1": f1, "fc2": f2}
            z = widedeep.logits(params, batch)
            return losses_lib.logistic_loss(z, batch["labels"], reduction="mean")

        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))(
            wide_rows, embed_rows, fc1, fc2
        )

    from lightctr_tpu.data.batching import minibatches

    curve = []
    for epoch in range(cfg["epochs"]):
        ep_losses = []
        for mb in minibatches(
            data, B, seed=cfg["seed"] + worker_id * 1000 + epoch
        ):
            fids = mb["fids"]
            rep = mb["rep_fids"]

            uw = np.unique(fids.reshape(-1))
            ue = np.unique(rep.reshape(-1))
            # pad with an id that was REALLY pulled (edge-repeat): a pad of 0
            # would KeyError whenever feature 0 is absent from the batch
            uw_pad = np.pad(uw, (0, U_w - len(uw)), mode="edge")
            ue_pad = np.pad(ue, (0, U_e - len(ue)), mode="edge")

            sparse_keys = np.union1d(uw, ue)
            n_dense = (dense_len + row_dim - 1) // row_dim
            dense_keys = DENSE_BASE + np.arange(n_dense, dtype=np.int64)
            # DENSE_BASE dwarfs every fid, so concat stays sorted
            all_keys = np.concatenate([sparse_keys, dense_keys])
            rows = _pull_rows_retry(ps, all_keys, epoch, worker_id)

            iw = np.searchsorted(sparse_keys, uw_pad)
            ie = np.searchsorted(sparse_keys, ue_pad)
            wide_rows = rows[iw, 0]
            embed_rows = rows[ie, 1:]
            dvec = rows[len(sparse_keys):].reshape(-1)[:dense_len]
            mlp = _unflatten_dense(dvec, template)

            batch = {
                "fids": np.searchsorted(uw_pad[: len(uw)], fids).astype(np.int32),
                "rep_fids": np.searchsorted(ue_pad[: len(ue)], rep).astype(np.int32),
                "vals": mb["vals"],
                "mask": mb["mask"],
                "rep_mask": mb["rep_mask"],
                "labels": mb["labels"],
            }
            loss, (g_w, g_e, g_fc1, g_fc2) = grads_fn(
                jnp.asarray(wide_rows), jnp.asarray(embed_rows),
                jax.tree_util.tree_map(jnp.asarray, mlp["fc1"]),
                jax.tree_util.tree_map(jnp.asarray, mlp["fc2"]),
                {k: jnp.asarray(v) for k, v in batch.items()},
            )
            ep_losses.append(float(loss))

            g_w, g_e = np.asarray(g_w), np.asarray(g_e)
            # one [n_keys, row_dim] grad block: wide grads in col 0, embed
            # grads in cols 1:, dense chunk grads appended.  Grads of padded
            # (edge-repeated) rows are dropped exactly as before — no batch
            # position maps past len(uw)/len(ue), so they are identically 0.
            G = np.zeros((len(all_keys), row_dim), np.float32)
            # iw/ie prefixes already hold searchsorted(sparse_keys, uw/ue)
            G[iw[: len(uw)], 0] = g_w[: len(uw)]
            G[ie[: len(ue)], 1:] = g_e[: len(ue)]
            g_dense = _flatten_dense({"fc1": g_fc1, "fc2": g_fc2})
            pad = n_dense * row_dim - dense_len
            G[len(sparse_keys):] = np.pad(g_dense, (0, pad)).reshape(
                n_dense, row_dim
            )
            _push_rows(ps, worker_id, all_keys, G, epoch)
        curve.append(float(np.mean(ep_losses)))

    with open(os.path.join(out_dir, f"worker_{worker_id}.json"), "w") as f:
        json.dump(
            {
                "worker": worker_id,
                "loss_curve": curve,
                "withheld_pulls": ps.withheld_pulls,
                "dropped_pushes": ps.dropped_pushes,
            },
            f,
        )
    ps.close()


# ---------------------------------------------------------------------------
# coordinator


def run(
    data_path: str = None,
    n_workers: int = 4,
    epochs: int = 30,
    batch_size: int = 50,
    factor_dim: int = 8,
    lr: float = 0.1,
    updater: str = "adagrad",
    staleness: int = 10,
    seed: int = 0,
    workdir: str = None,
    arrays: Dict[str, np.ndarray] = None,
    field_cnt: int = None,
    feature_cnt: int = None,
    transport: str = "shm",
) -> dict:
    """Returns the convergence/parity report (and leaves worker JSONs in
    ``workdir``).  ``arrays`` overrides ``data_path`` for synthetic tests.
    ``transport``: "shm" = one-host shared-memory PS; "tcp" = the
    multi-node form — workers talk wire-coded pull/push (varint keys +
    fp16 rows, dist/ps_server.py) to a PS service over sockets."""
    import tempfile

    import jax

    from lightctr_tpu.embed.shm_ps import ShmAsyncParamServer
    from lightctr_tpu.models import widedeep

    if transport not in ("shm", "tcp"):
        raise ValueError(f"unknown transport {transport!r}")

    if arrays is None:
        from lightctr_tpu.data import load_libffm
        from lightctr_tpu.data.synth import resolve_libffm

        ds, _ = load_libffm(resolve_libffm(data_path, workdir)).compact()
        feature_cnt, field_cnt = ds.feature_cnt, ds.field_cnt
        rep, rep_mask = widedeep.field_representatives(
            ds.fids, ds.fields, ds.mask, field_cnt
        )
        arrays = widedeep.make_batch(ds, rep, rep_mask)

    D = factor_dim
    row_dim = 1 + D
    params0 = widedeep.init(
        jax.random.PRNGKey(seed), feature_cnt, field_cnt, D
    )
    template = _dense_template(params0)
    dense_vec = _flatten_dense(params0)

    workdir = workdir or tempfile.mkdtemp(prefix="ps_conv_")
    base = os.path.join(workdir, "ps")
    payload = {k: np.asarray(v) for k, v in arrays.items()}
    n_chunks = (len(dense_vec) + row_dim - 1) // row_dim
    service = None
    extra_cfg = {"transport": transport}
    if transport == "tcp":
        from lightctr_tpu.dist.ps_server import ParamServerService
        from lightctr_tpu.embed.async_ps import AsyncParamServer

        ps = AsyncParamServer(
            dim=row_dim, updater=updater, learning_rate=lr,
            n_workers=n_workers, staleness_threshold=staleness, seed=seed,
        )
        service = ParamServerService(ps)
        extra_cfg["address"] = list(service.address)
    else:
        capacity = 2 * (feature_cnt + n_chunks + 16)
        ps = ShmAsyncParamServer.create(
            base, capacity=capacity, dim=row_dim, n_workers=n_workers,
            updater=updater, learning_rate=lr, staleness_threshold=staleness,
            seed=seed,
        )
    try:
        return _run_with_ps(
            ps=ps, base=base, workdir=workdir, payload=payload,
            params0=params0, template=template, dense_vec=dense_vec,
            n_workers=n_workers, epochs=epochs, batch_size=batch_size,
            D=D, row_dim=row_dim, n_chunks=n_chunks, lr=lr,
            updater=updater, staleness=staleness, seed=seed,
            feature_cnt=feature_cnt, extra_cfg=extra_cfg,
        )
    finally:
        # close even when a worker dies mid-run: the mmap handles / the
        # listening socket (and a waiting SSP puller) must not outlive the
        # failed attempt
        if service is not None:
            service.close()
        else:
            ps.close()


def _run_with_ps(
    *, ps, base, workdir, payload, params0, template, dense_vec,
    n_workers, epochs, batch_size, D, row_dim, n_chunks, lr,
    updater, staleness, seed, feature_cnt, extra_cfg=None,
):
    import jax

    from lightctr_tpu import TrainConfig
    from lightctr_tpu.models import widedeep
    from lightctr_tpu.models.ctr_trainer import CTRTrainer
    from lightctr_tpu.ops import metrics as metrics_lib
    from lightctr_tpu.ops.activations import sigmoid

    # master syncInitializer: deterministic start for every process
    w0 = np.asarray(params0["w"])
    e0 = np.asarray(params0["embed"])
    rows = np.concatenate([w0[:, None], e0], axis=1).astype(np.float32)
    ps.preload({fid: rows[fid] for fid in range(feature_cnt)})
    ps.preload(_dense_chunks(dense_vec, row_dim))

    cfg = {
        "factor_dim": D, "batch_size": batch_size, "epochs": epochs,
        "lr": lr, "updater": updater, "staleness": staleness, "seed": seed,
        "dense_template": [(k, list(v)) for k, v in template.items()],
        **(extra_cfg or {}),
    }

    ctx = mp.get_context("spawn")
    # ship each worker ONLY its strided shard (proc_file_split.py partition);
    # contiguous copies so no process keeps the full buffers alive via views
    from lightctr_tpu.data.batching import shard_for_hosts

    procs = [
        ctx.Process(
            target=_worker,
            args=(
                base, w, n_workers,
                {
                    k: np.ascontiguousarray(v)
                    for k, v in shard_for_hosts(payload, w, n_workers).items()
                },
                workdir, cfg,
            ),
        )
        for w in range(n_workers)
    ]
    t0 = time.time()
    for p in procs:
        p.start()
    for p in procs:
        p.join()
    wall = time.time() - t0
    for p in procs:
        if p.exitcode != 0:
            raise RuntimeError(f"worker exited with {p.exitcode}")

    # reconstruct the PS-trained model
    final = _pull_retry(ps, list(range(feature_cnt)), epochs)
    w_fin = np.stack([final[k] for k in range(feature_cnt)])
    dense_keys = [DENSE_BASE + i for i in range(n_chunks)]
    pulled_dense = _pull_retry(ps, dense_keys, epochs)
    dvec = np.concatenate(
        [pulled_dense[k] for k in dense_keys]
    )[: len(dense_vec)]
    ps_params = {
        "w": w_fin[:, 0],
        "embed": w_fin[:, 1:],
        **_unflatten_dense(dvec, template),
    }

    import jax.numpy as jnp

    def eval_params(params):
        z = widedeep.logits(
            jax.tree_util.tree_map(jnp.asarray, params),
            {k: jnp.asarray(v) for k, v in payload.items()},
        )
        probs = sigmoid(z)
        labels = jnp.asarray(payload["labels"])
        return {
            "logloss": float(metrics_lib.logloss(probs, labels)),
            "accuracy": float(
                metrics_lib.accuracy(
                    (probs > 0.5).astype(jnp.int32), labels.astype(jnp.int32)
                )
            ),
            "auc": float(metrics_lib.auc_histogram(probs, labels.astype(jnp.int32))),
        }

    # single-process baseline: same model/optimizer/schedule, one process
    cfg_tr = TrainConfig(learning_rate=lr, seed=seed)
    tr = CTRTrainer(params0, widedeep.logits, cfg_tr)
    hist = tr.fit(payload, epochs=epochs, batch_size=batch_size)

    curves = []
    for w in range(n_workers):
        with open(os.path.join(workdir, f"worker_{w}.json")) as f:
            curves.append(json.load(f))

    ev_ps = eval_params(ps_params)
    ev_single = eval_params(tr.params)
    report = {
        "config": {
            "n_workers": n_workers, "epochs": epochs,
            "batch_size": batch_size, "factor_dim": D, "lr": lr,
            "updater": updater, "staleness": staleness,
            "rows": int(len(payload["labels"])), "feature_cnt": int(feature_cnt),
            "transport": (extra_cfg or {}).get("transport", "shm"),
        },
        "wall_time_s": round(wall, 2),
        "workers": curves,
        "single_loss_curve": [float(x) for x in hist["loss"]],
        "final_ps": ev_ps,
        "final_single": ev_single,
        "parity": {
            k: round(abs(ev_ps[k] - ev_single[k]), 5) for k in ev_ps
        },
    }
    return report


def main():
    from lightctr_tpu.utils.devicecheck import pin_cpu_platform

    pin_cpu_platform(1)

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--data", default=None,
        help="libffm file (default: $LIGHTCTR_DATA, the reference dataset "
             "when mounted, else synthetic)",
    )
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=30)
    ap.add_argument("--batch-size", type=int, default=50)
    ap.add_argument("--factor-dim", type=int, default=8)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--updater", default="adagrad")
    ap.add_argument(
        "--transport", choices=("shm", "tcp"), default="shm",
        help="shm = one-host shared-memory PS; tcp = wire-coded PS service",
    )
    ap.add_argument("--out", default="PS_CONVERGENCE.json")
    args = ap.parse_args()

    report = run(
        data_path=args.data, n_workers=args.workers, epochs=args.epochs,
        batch_size=args.batch_size, factor_dim=args.factor_dim, lr=args.lr,
        updater=args.updater, transport=args.transport,
    )
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps({
        "final_ps": report["final_ps"],
        "final_single": report["final_single"],
        "parity": report["parity"],
        "wall_time_s": report["wall_time_s"],
    }))


if __name__ == "__main__":
    main()
