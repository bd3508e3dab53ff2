"""Real-TPU gates for the round-3 features.

Same pattern as test_compiled_kernels.py: the virtual-CPU suite already
checks numerics; these run the identical programs through the real XLA:TPU
lowering (single chip — collectives degenerate to 1-member rings there, so
these are compile+execute gates, not multi-chip behavior tests; the
multi-chip behavior is covered on the virtual mesh and by dryrun_multichip).

    python -m pytest tests_tpu -q
"""

import jax
import jax.numpy as jnp
import numpy as np


def test_compressed_ring_trainer_compiles_on_chip():
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.core.mesh import MeshSpec, make_mesh
    from lightctr_tpu.models import fm
    from lightctr_tpu.models.ctr_trainer import CTRTrainer

    rng = np.random.default_rng(0)
    n_dev = len(jax.devices())
    mesh = make_mesh(MeshSpec(data=n_dev))
    params = fm.init(jax.random.PRNGKey(0), 2048, 4)
    # the production int8 configuration: EF residual (default-on at 8
    # bits) + dynamic range — the round-5 codec that matches the exact
    # ring's accuracy must lower through real XLA:TPU (pmax + table build
    # + searchsorted codec + residual carry, one jitted program)
    tr = CTRTrainer(
        params, fm.logits, TrainConfig(learning_rate=0.1),
        fused_fn=fm.logits_with_l2, mesh=mesh,
        compress_bits=8, compress_range="dynamic",
    )
    assert tr.error_feedback
    batch = {
        "fids": rng.integers(0, 2048, size=(16 * n_dev, 8)).astype(np.int32),
        "fields": np.zeros((16 * n_dev, 8), np.int32),
        "vals": np.ones((16 * n_dev, 8), np.float32),
        "mask": np.ones((16 * n_dev, 8), np.float32),
        "labels": (np.arange(16 * n_dev) % 2).astype(np.float32),
    }
    l0 = last = None
    for _ in range(4):
        last = float(tr.train_step(batch))
        l0 = last if l0 is None else l0
    assert np.isfinite(last) and last < l0, (l0, last)


def test_sparse_sharded_trainer_compiles_on_chip():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from lightctr_tpu import TrainConfig
    from lightctr_tpu.core.mesh import MeshSpec, make_mesh
    from lightctr_tpu.models import widedeep
    from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer

    rng = np.random.default_rng(1)
    n_dev = len(jax.devices())
    embed_ax = 2 if n_dev % 2 == 0 else 1
    mesh = make_mesh(MeshSpec(data=n_dev // embed_ax, embed=embed_ax))
    n, f, field_cnt, nnz, dim = 32 * n_dev, 4096, 4, 6, 8
    fids = rng.integers(1, f, size=(n, nnz)).astype(np.int32)
    fields = rng.integers(0, field_cnt, size=(n, nnz)).astype(np.int32)
    mask = np.ones((n, nnz), np.float32)
    rep, rep_mask = widedeep.field_representatives(fids, fields, mask, field_cnt)
    batch = {
        "fids": fids, "fields": fields, "vals": np.ones((n, nnz), np.float32),
        "mask": mask, "labels": (rng.random(n) > 0.5).astype(np.float32),
        "rep_fids": rep, "rep_mask": rep_mask,
    }
    params = widedeep.init(jax.random.PRNGKey(0), f, field_cnt, dim)
    sh = {
        "w": NamedSharding(mesh, P("embed")),
        "embed": NamedSharding(mesh, P("embed", None)),
        "fc1": {"w": NamedSharding(mesh, P()), "b": NamedSharding(mesh, P())},
        "fc2": {"w": NamedSharding(mesh, P()), "b": NamedSharding(mesh, P())},
    }
    tr = SparseTableCTRTrainer(
        params, widedeep.logits, TrainConfig(learning_rate=0.1),
        sparse_tables={"w": ["fids"], "embed": ["rep_fids"]},
        mesh=mesh, param_shardings=sh,
    )
    l0 = last = None
    for _ in range(4):
        last = float(tr.train_step(batch))
        l0 = last if l0 is None else l0
    assert np.isfinite(last) and last < l0, (l0, last)


def test_deepfm_dcn_compile_on_chip():
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.models import deepfm, widedeep
    from lightctr_tpu.models.ctr_trainer import CTRTrainer

    rng = np.random.default_rng(2)
    n, f, field_cnt, nnz, dim = 64, 1024, 4, 5, 8
    fids = rng.integers(1, f, size=(n, nnz)).astype(np.int32)
    fields = rng.integers(0, field_cnt, size=(n, nnz)).astype(np.int32)
    mask = np.ones((n, nnz), np.float32)
    rep, rep_mask = widedeep.field_representatives(fids, fields, mask, field_cnt)
    batch = {
        "fids": fids, "fields": fields, "vals": np.ones((n, nnz), np.float32),
        "mask": mask, "labels": (rng.random(n) > 0.5).astype(np.float32),
        "rep_fids": rep, "rep_mask": rep_mask,
    }
    cfg = TrainConfig(learning_rate=0.1)
    for init_fn, logit_fn, fused in (
        (lambda k: deepfm.init(k, f, field_cnt, dim), deepfm.logits,
         deepfm.logits_with_l2),
        (lambda k: deepfm.dcn_init(k, f, field_cnt, dim, n_cross=2),
         deepfm.dcn_logits, deepfm.dcn_logits_with_l2),
    ):
        tr = CTRTrainer(init_fn(jax.random.PRNGKey(0)), logit_fn, cfg,
                        fused_fn=fused)
        losses = tr.fit_fullbatch_scan(batch, 10)
        assert np.isfinite(losses[-1]) and losses[-1] < losses[0], losses
