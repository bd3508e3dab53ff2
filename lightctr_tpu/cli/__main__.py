"""Command-line entry point.

Replaces the reference's compile-time ``-D`` role/model selection
(``main.cpp:80-255``, ``Makefile:20-41``) with one binary and flags — the
recommended configs from ``main.cpp:56-62`` are the per-model defaults.

Examples
--------
    python -m lightctr_tpu.cli fm    --data train_sparse.csv --epochs 200
    python -m lightctr_tpu.cli ffm   --data train_sparse.csv --factor 4
    python -m lightctr_tpu.cli nfm   --data train_sparse.csv --hidden 32
    python -m lightctr_tpu.cli widedeep --data train_sparse.csv
    python -m lightctr_tpu.cli cnn   --data train_dense.csv --epochs 8
    python -m lightctr_tpu.cli rnn   --data train_dense.csv
    python -m lightctr_tpu.cli vae   --data train_dense.csv
    python -m lightctr_tpu.cli gbm   --data train_dense.csv --n-classes 10
    python -m lightctr_tpu.cli gmm   --data train_cluster.csv --clusters 10
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="lightctr_tpu", description=__doc__)
    sub = p.add_subparsers(dest="model", required=True)

    def common(sp, lr, batch):
        sp.add_argument("--data", required=True)
        sp.add_argument("--eval-data")
        sp.add_argument("--epochs", type=int, default=10)
        sp.add_argument("--lr", type=float, default=lr)
        sp.add_argument("--batch-size", type=int, default=batch)
        sp.add_argument("--seed", type=int, default=0)
        sp.add_argument("--ckpt-dir")
        return sp

    def scoreable(sp, predictor="FM_Predict"):
        # only models with per-row scores get the flag — elsewhere it would
        # be silently meaningless
        sp.add_argument("--dump-scores", help="write per-row pCTR scores to this"
                        f" file ({predictor}'s optional score dump)")
        return sp

    def positive_int(v):
        n = int(v)
        if n < 1:
            raise argparse.ArgumentTypeError(f"must be >= 1, got {n}")
        return n

    for name in ("fm", "ffm", "nfm", "widedeep", "deepfm", "dcn"):
        sp = scoreable(common(sub.add_parser(name), lr=0.1, batch=50))  # main.cpp:56-59
        sp.add_argument("--factor", type=int, default=8)
        sp.add_argument("--l2", type=float, default=0.001)
        if name == "nfm":
            sp.add_argument("--hidden", type=int, default=32)
        if name in ("widedeep", "deepfm", "dcn"):
            sp.add_argument("--hidden", type=int, default=50)
        if name == "dcn":
            sp.add_argument("--n-cross", type=positive_int, default=3)
        sp.add_argument("--full-batch", action="store_true",
                        help="train full-batch per epoch (the reference FM mode)")
        sp.add_argument("--dp", action="store_true",
                        help="data-parallel over every visible device "
                             "(mesh on 'data'; implies --full-batch)")
        sp.add_argument("--compress-bits", type=int, choices=(8, 16),
                        help="wire-compress the DP gradient ring; 8-bit "
                             "rides error feedback + a dynamic table "
                             "range (implies --dp)")

    sp = common(sub.add_parser("cnn"), lr=0.1, batch=10)     # main.cpp:60
    sp.add_argument("--hidden", type=int, default=200)
    sp.add_argument("--n-classes", type=int, default=10)
    sp.add_argument("--optimizer", default="rmsprop")
    sp = common(sub.add_parser("rnn"), lr=0.03, batch=10)    # main.cpp:61
    sp.add_argument("--hidden", type=int, default=50)
    sp.add_argument("--n-classes", type=int, default=10)
    sp.add_argument("--optimizer", default="adagrad")
    sp = common(sub.add_parser("vae"), lr=0.1, batch=10)     # main.cpp:58
    sp.add_argument("--hidden", type=int, default=60)
    sp.add_argument("--gauss", type=int, default=20)

    sp = scoreable(common(sub.add_parser("gbm"), lr=0.6, batch=0), predictor="GBM_Predict")
    sp.add_argument("--n-trees", type=int, default=10)
    sp.add_argument("--max-depth", type=int, default=6)
    sp.add_argument("--n-classes", type=int, default=1)

    # GBM leaf-index -> FTRL-LR stacked model (BASELINE config 5)
    sp = scoreable(common(sub.add_parser("stack"), lr=0.6, batch=0))
    sp.add_argument("--n-trees", type=int, default=10)
    sp.add_argument("--max-depth", type=int, default=6)
    sp.add_argument("--lr-steps", type=positive_int, default=200)

    sp = common(sub.add_parser("gmm"), lr=0.0, batch=0)
    sp.add_argument("--clusters", type=int, default=10)

    # topic model on raw text, one document per line (TEST_TM)
    sp = common(sub.add_parser("plsa"), lr=0.0, batch=0)
    sp.add_argument("--topics", type=int, default=8)
    sp.add_argument("--vocab-size", type=int, default=5000)
    sp.add_argument("--top-words", type=int, default=10)

    # sequence CTR: lines of "label id id id ..." (behavior sequences)
    sp = scoreable(common(sub.add_parser("seqctr"), lr=0.01, batch=64))
    sp.add_argument("--dim", type=int, default=32)
    sp.add_argument("--heads", type=int, default=4)
    sp.add_argument("--layers", type=int, default=2)
    sp.add_argument("--max-len", type=int, default=128)
    sp.add_argument("--full-batch", action="store_true")

    # sequence language model: lines of "id id id ..." (one document a
    # line), packed into sequences; the hybrid linear-attention MoE model
    # of models/kimi_linear.py at its tiny preset
    sp = common(sub.add_parser("seqlm"), lr=0.05, batch=1)
    sp.add_argument("--seq-len", type=positive_int, default=64)

    # word2vec on raw text (TEST_EMB pipeline: train -> quantize -> cluster)
    sp = common(sub.add_parser("embed"), lr=0.3, batch=256)
    sp.add_argument("--dim", type=int, default=100)
    sp.add_argument("--window", type=int, default=6)
    sp.add_argument("--vocab-size", type=int, default=5000)
    sp.add_argument("--mode", choices=["negative", "hierarchical"], default="negative")
    sp.add_argument("--out")
    sp.add_argument("--quantize", action="store_true")
    sp.add_argument("--cluster", type=int, default=0)
    return p


def _dump_scores(path: str, probs, report: dict) -> None:
    np.savetxt(path, probs, fmt="%.6g")
    report["scores"] = path


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    import jax

    from lightctr_tpu import TrainConfig
    from lightctr_tpu.data import load_dense_csv, load_libffm

    cfg = TrainConfig(
        learning_rate=args.lr,
        minibatch_size=max(1, getattr(args, "batch_size", 1) or 1),
        lambda_l2=getattr(args, "l2", 0.0),
        seed=args.seed,
    )
    report = {"model": args.model}

    if args.model in ("fm", "ffm", "nfm", "widedeep", "deepfm", "dcn"):
        from lightctr_tpu.models import deepfm, fm, ffm, nfm, widedeep
        from lightctr_tpu.models.ctr_trainer import CTRTrainer

        ds = load_libffm(args.data)
        key = jax.random.PRNGKey(args.seed)
        fused = None
        if args.model == "fm":
            params, logits = fm.init(key, ds.feature_cnt, args.factor), fm.logits
            fused = fm.logits_with_l2
        elif args.model == "ffm":
            params, logits = (
                ffm.init(key, ds.feature_cnt, ds.field_cnt, args.factor), ffm.logits,
            )
            fused = ffm.logits_with_l2
        elif args.model == "nfm":
            params, logits = (
                nfm.init(key, ds.feature_cnt, args.factor, args.hidden), nfm.logits,
            )
            fused = nfm.logits_with_l2
        elif args.model == "deepfm":
            params, logits = (
                deepfm.init(key, ds.feature_cnt, ds.field_cnt, args.factor, args.hidden),
                deepfm.logits,
            )
            fused = deepfm.logits_with_l2
        elif args.model == "dcn":
            params, logits = (
                deepfm.dcn_init(key, ds.feature_cnt, ds.field_cnt, args.factor,
                                n_cross=args.n_cross, hidden=args.hidden),
                deepfm.dcn_logits,
            )
            fused = deepfm.dcn_logits_with_l2
        else:
            params, logits = (
                widedeep.init(key, ds.feature_cnt, ds.field_cnt, args.factor, args.hidden),
                widedeep.logits,
            )
        batch = ds.batch_dict()
        if args.model in ("widedeep", "deepfm", "dcn"):
            rep, rep_mask = widedeep.field_representatives(ds.fids, ds.fields, ds.mask, ds.field_cnt)
            batch = widedeep.make_batch(ds, rep, rep_mask)
        mesh = None
        ndev = 1
        if args.dp or args.compress_bits:
            from lightctr_tpu.core.mesh import local_mesh

            mesh = local_mesh()
            ndev = mesh.shape["data"]
            n = (len(batch["labels"]) // ndev) * ndev
            if n == 0:
                raise SystemExit(
                    f"--dp: dataset has {len(batch['labels'])} rows but the "
                    f"mesh has {ndev} devices — nothing to shard"
                )
            if n != len(batch["labels"]):
                # sharded batches must split evenly over the mesh
                batch = {k: v[:n] for k, v in batch.items()}
            report["parallel"] = {
                "devices": ndev,
                "compress_bits": args.compress_bits,
            }
        tr = CTRTrainer(
            params, logits, cfg, fused_fn=fused, mesh=mesh,
            compress_bits=args.compress_bits,
            compress_range="dynamic" if args.compress_bits else 1.0,
        )
        hist = tr.fit(
            batch,
            epochs=args.epochs,
            # DP shards the batch over the mesh: full-batch keeps every
            # step evenly divisible
            batch_size=None if (args.full_batch or mesh is not None)
            else cfg.minibatch_size,
        )
        report["train"] = tr.evaluate(batch)
        report["final_loss"] = hist["loss"][-1]
        report["wall_time_s"] = round(hist["wall_time_s"], 3)
        if args.eval_data:
            ev = load_libffm(args.eval_data, feature_cnt=ds.feature_cnt, field_cnt=ds.field_cnt)
            evb = ev.batch_dict()
            if args.model in ("widedeep", "deepfm", "dcn"):
                rep, rep_mask = widedeep.field_representatives(ev.fids, ev.fields, ev.mask, ds.field_cnt)
                evb = widedeep.make_batch(ev, rep, rep_mask)
            if mesh is not None:  # eval shards over the mesh too
                ne = (len(evb["labels"]) // ndev) * ndev
                if ne != len(evb["labels"]):
                    evb = {k: v[:ne] for k, v in evb.items()}
            report["eval"] = tr.evaluate(evb)
        if args.ckpt_dir:
            from lightctr_tpu import ckpt

            report["checkpoint"] = ckpt.save(args.ckpt_dir, args.epochs, {
                "params": tr.params, "opt_state": tr.opt_state,
            })
        if getattr(args, "dump_scores", None):
            target = evb if args.eval_data else batch
            _dump_scores(args.dump_scores, tr.predict_proba(target), report)

    elif args.model in ("cnn", "rnn"):
        from lightctr_tpu import optim
        from lightctr_tpu.models import cnn, rnn
        from lightctr_tpu.models.dl_trainer import ClassifierTrainer

        ds = load_dense_csv(args.data)
        key = jax.random.PRNGKey(args.seed)
        if args.model == "cnn":
            params, logits = cnn.init(key, hidden=args.hidden, n_classes=args.n_classes), cnn.logits
        else:
            params, logits = rnn.init(key, hidden=args.hidden, n_classes=args.n_classes), rnn.logits
        opt = optim.get(args.optimizer, learning_rate=args.lr)
        tr = ClassifierTrainer(params, logits, cfg, n_classes=args.n_classes, optimizer=opt)
        hist = tr.fit(ds.features, ds.labels, epochs=args.epochs, batch_size=cfg.minibatch_size)
        report["train"] = tr.evaluate(ds.features, ds.labels)
        report["final_loss"] = hist["loss"][-1]
        report["wall_time_s"] = round(hist["wall_time_s"], 3)

    elif args.model == "vae":
        from lightctr_tpu.models import vae

        ds = load_dense_csv(args.data)
        params = vae.init(jax.random.PRNGKey(args.seed), ds.features.shape[1],
                          hidden=args.hidden, gauss_cnt=args.gauss)
        tr = vae.VAETrainer(params, cfg)
        hist = tr.fit(ds.features, epochs=args.epochs, batch_size=cfg.minibatch_size)
        report["final_loss"] = hist["loss"][-1]
        report["wall_time_s"] = round(hist["wall_time_s"], 3)

    elif args.model == "gbm":
        from lightctr_tpu.models import gbm

        ds = load_dense_csv(args.data)
        model = gbm.GBMModel(gbm.GBMConfig(
            n_trees=args.n_trees, max_depth=args.max_depth,
            n_classes=args.n_classes, seed=args.seed,
            shrinkage=args.lr,
        ))
        y = ds.labels if args.n_classes > 1 else (ds.labels > 0).astype(np.float32)
        hist = model.fit(ds.features, y)
        report["final_loss"] = hist[-1]
        report["train"] = model.evaluate(ds.features, y)
        if getattr(args, "dump_scores", None):
            _dump_scores(args.dump_scores, model.predict_proba(ds.features), report)

    elif args.model == "stack":
        from lightctr_tpu.models import gbm
        from lightctr_tpu.models.stacking import GBMLRStack

        ds = load_dense_csv(args.data)
        stack = GBMLRStack(
            gbm.GBMConfig(
                n_trees=args.n_trees, max_depth=args.max_depth,
                seed=args.seed, shrinkage=args.lr,
            ),
            lr_steps=args.lr_steps,
        )
        y = (ds.labels > 0).astype(np.float32)
        hist = stack.fit(ds.features, y)
        report["final_loss"] = hist["lr_loss"][-1]
        report["train"] = stack.evaluate(ds.features, y)
        if getattr(args, "dump_scores", None):
            _dump_scores(args.dump_scores, stack.predict_proba(ds.features), report)

    elif args.model == "gmm":
        from lightctr_tpu.models import gmm

        raw = np.loadtxt(args.data, delimiter=",", dtype=np.float32)
        params = gmm.init_from_data(jax.random.PRNGKey(args.seed), args.clusters, raw)
        params, hist = gmm.fit(params, raw, epochs=args.epochs)
        report["final_loglik"] = hist[-1]
        report["cluster_sizes"] = np.bincount(
            gmm.predict(params, raw), minlength=args.clusters
        ).tolist()

    elif args.model == "seqctr":
        from lightctr_tpu import optim
        from lightctr_tpu.models import attention_ctr
        from lightctr_tpu.models.ctr_trainer import CTRTrainer

        def parse_seq_file(path, t=None):
            labels, seqs = [], []
            with open(path) as f:
                for lineno, line in enumerate(f, 1):
                    parts = line.split()
                    if not parts:
                        continue
                    try:
                        labels.append(float(parts[0]))
                        row = [int(tok) for tok in parts[1:]]
                    except ValueError as e:
                        raise ValueError(f"{path}:{lineno}: {e}") from None
                    if any(i < 0 for i in row):
                        raise ValueError(
                            f"{path}:{lineno}: negative token id "
                            "(ids must be >= 0)"
                        )
                    seqs.append(row)
            if not seqs:
                raise ValueError(f"{path}: no sequence rows")
            if t is None:
                t = min(args.max_len, max(len(s) for s in seqs))
                if t == 0:
                    raise ValueError(
                        f"{path}: every row is a bare label (no token ids)"
                    )
            n = len(seqs)
            ids = np.zeros((n, t), np.int32)
            seq_mask = np.zeros((n, t), np.float32)
            for i, s in enumerate(seqs):
                s = s[:t]
                ids[i, : len(s)] = s
                seq_mask[i, : len(s)] = 1.0
            return {"seq_ids": ids, "seq_mask": seq_mask,
                    "labels": np.asarray(labels, np.float32)}, t

        batch, t = parse_seq_file(args.data)
        vocab = int(batch["seq_ids"].max()) + 1
        params, logits = attention_ctr.build(
            jax.random.PRNGKey(args.seed), vocab, dim=args.dim,
            n_heads=args.heads, n_layers=args.layers, max_len=t,
        )
        tr = CTRTrainer(params, logits, cfg, optimizer=optim.adam(args.lr))
        hist = tr.fit(
            batch, epochs=args.epochs,
            batch_size=None if args.full_batch else cfg.minibatch_size,
        )
        report["train"] = tr.evaluate(batch)
        report["final_loss"] = hist["loss"][-1]
        report["wall_time_s"] = round(hist["wall_time_s"], 3)
        report["vocab"] = vocab
        if getattr(args, "dump_scores", None):
            _dump_scores(args.dump_scores, tr.predict_proba(batch), report)
        if args.eval_data:
            evb, _ = parse_seq_file(args.eval_data, t)
            # fold held-out ids into the trained vocabulary (hashing trick,
            # same policy as the libFFM loader)
            evb["seq_ids"] = (evb["seq_ids"] % vocab).astype(np.int32)
            report["eval"] = tr.evaluate(evb)
        if args.ckpt_dir:
            from lightctr_tpu import ckpt

            report["checkpoint"] = ckpt.save(args.ckpt_dir, args.epochs, {
                "params": tr.params, "opt_state": tr.opt_state,
            })

    elif args.model == "seqlm":
        import dataclasses

        from lightctr_tpu import obs
        from lightctr_tpu.data import ingest
        from lightctr_tpu.models import kimi_linear
        from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer

        with open(args.data) as f:
            docs = [[int(tok) for tok in line.split()]
                    for line in f if line.strip()]
        if any(t < 0 for d in docs for t in d):
            raise ValueError(f"{args.data}: negative token id")
        rows = ingest.pack_documents(docs, args.seq_len)
        spec = dataclasses.replace(
            kimi_linear.Spec(), vocab=int(rows["fids"].max()) + 1)
        params, logits = kimi_linear.build(jax.random.PRNGKey(args.seed), spec)
        tr = SparseTableCTRTrainer(
            params, logits, cfg.replace(loss="softmax_xent"),
            sparse_tables={"embed": ["tokens"]})
        tr.telemetry = obs.MetricsRegistry()
        n, b = len(rows["labels"]), cfg.minibatch_size
        losses = []
        for _ in range(args.epochs):
            for i in range(0, max(n - b, 0) + 1, b):      # whole batches
                losses.append(float(tr.train_step(ingest.sequence_batch(
                    {k: v[i:i + b] for k, v in rows.items()}))))
        tr.flush_health()
        counters = tr.telemetry.snapshot()["counters"]

        def total(name):
            return sum(v for k, v in counters.items() if k.startswith(name))

        report.update(
            documents=len(docs), sequences=n, vocab=spec.vocab,
            first_loss=losses[0], final_loss=losses[-1],
            tokens=int(total("trainer_seq_tokens_total")),
            held_share=total("trainer_moe_held_assignments_total")
            / max(1.0, total("trainer_moe_assignments_total")))
        if args.ckpt_dir:
            from lightctr_tpu import ckpt

            report["checkpoint"] = ckpt.save(args.ckpt_dir, args.epochs, {
                "params": tr.params, "opt_state": tr.opt_state,
            })

    elif args.model == "plsa":
        from lightctr_tpu.data import text as text_lib
        from lightctr_tpu.models import plsa

        with open(args.data) as f:
            docs = [text_lib.tokenize(line) for line in f if line.strip()]
        words, counts, w2i = text_lib.build_vocab(docs, max_size=args.vocab_size)
        m = text_lib.doc_term_matrix(docs, w2i)
        params = plsa.init(jax.random.PRNGKey(args.seed), m.shape[0], args.topics, m.shape[1])
        params, hist = plsa.fit(params, m, epochs=args.epochs)
        report["final_loglik"] = hist[-1]
        report["topics"] = plsa.topic_keywords(params, words, top_k=args.top_words)

    elif args.model == "embed":
        from lightctr_tpu.data import text as text_lib
        from lightctr_tpu.models import embedding, export

        with open(args.data) as f:
            docs_tok = [text_lib.tokenize(line) for line in f if line.strip()]
        words, counts, w2i = text_lib.build_vocab(docs_tok, max_size=args.vocab_size)
        docs = text_lib.docs_to_ids(docs_tok, w2i)
        centers, contexts, mask = embedding.cbow_pairs(docs, args.window, counts=counts,
                                                       seed=args.seed)
        tr = embedding.Word2VecTrainer(len(words), args.dim, cfg, counts, mode=args.mode)
        hist = tr.fit(centers, contexts, mask, epochs=args.epochs,
                      batch_size=cfg.minibatch_size)
        report["final_loss"] = hist[-1]
        report["n_pairs"] = int(len(centers))
        if args.out:
            export.save_embeddings_text(args.out, words, tr.normalized_embeddings())
            report["embeddings"] = args.out
        if args.quantize:
            _, codes = tr.quantize()
            report["pq_codes_shape"] = list(codes.shape)
        if args.cluster:
            clusters = tr.cluster(n_clusters=args.cluster)
            report["cluster_sizes"] = np.bincount(clusters, minlength=args.cluster).tolist()

    print(json.dumps(report))
    return 0


def cli() -> int:
    """The console entry point: :func:`main` behind the persistent
    compilation cache (kept out of ``main`` so that tests driving it
    in-process configure no cache)."""
    from lightctr_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    return main()


if __name__ == "__main__":
    sys.exit(cli())
