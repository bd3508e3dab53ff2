"""On-chip certification for the ENTIRE model zoo (VERDICT r3 weak #4).

One command — ``python -m pytest tests_tpu -q`` — must certify that every
model family compiles, steps, and learns on the real chip the moment
hardware answers (the role of the reference's per-model TEST_* harnesses in
``main.cpp:140-254``).  The virtual-CPU suite already proves numerics; these
gates prove the real XLA:TPU lowering of each family.  All data is
synthetic, so the gates run in any checkout.

Each gate asserts loss decreases (or the family's analog: log-likelihood
rises, perplexity falls, accuracy beats chance) — a compile-only check
would pass on a model that diverges on-device.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _rng():
    return np.random.default_rng(0)


def _sparse_batch(rng, n=256, f=512, nnz=8, fields=None):
    fl = fields or 1
    return {
        "fids": rng.integers(0, f, size=(n, nnz)).astype(np.int32),
        "fields": (np.tile(np.arange(nnz) % fl, (n, 1))).astype(np.int32),
        "vals": np.ones((n, nnz), np.float32),
        "mask": np.ones((n, nnz), np.float32),
        "labels": (rng.random(n) > 0.5).astype(np.float32),
    }


def _images(rng, n=128, classes=10):
    """Learnable image data with SPATIAL structure (conv/recurrent models
    need it): class k is a bright patch at a class-specific position."""
    labels = rng.integers(0, classes, n).astype(np.int32)
    imgs = np.zeros((n, 28, 28), np.float32)
    for i, c in enumerate(labels):
        r, col = (c // 5) * 10 + 2, (c % 5) * 5 + 1
        imgs[i, r:r + 8, col:col + 4] = 1.0
    imgs += 0.1 * rng.standard_normal(imgs.shape).astype(np.float32)
    return np.clip(imgs, 0.0, 1.0).reshape(n, 784), labels


# -- CTR family --------------------------------------------------------------


def _rep_batch(rng, f=512, fl=4, n=256, nnz=8):
    """Sparse batch augmented with field representatives (what the deep CTR
    heads consume — deepfm.py:51-57)."""
    from lightctr_tpu.models import widedeep

    arrays = _sparse_batch(rng, n=n, f=f, nnz=nnz, fields=fl)
    rep, rep_mask = widedeep.field_representatives(
        arrays["fids"], arrays["fields"], arrays["mask"], fl
    )
    return {**arrays, "rep_fids": rep, "rep_mask": rep_mask}


@pytest.mark.parametrize("family", ["fm", "nfm", "deepfm", "dcn"])
def test_ctr_family_trains_on_chip(family):
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.models import deepfm, fm, nfm
    from lightctr_tpu.models.ctr_trainer import CTRTrainer

    rng = _rng()
    batch = _rep_batch(rng)
    cfg = TrainConfig(learning_rate=0.1, lambda_l2=0.001)
    if family == "fm":
        params = fm.init(jax.random.PRNGKey(0), 512, 8)
        tr = CTRTrainer(params, fm.logits, cfg, fused_fn=fm.logits_with_l2)
    elif family == "nfm":
        params = nfm.init(jax.random.PRNGKey(0), 512, 8, 32)
        tr = CTRTrainer(params, nfm.logits, cfg,
                        fused_fn=nfm.logits_with_l2)
    elif family == "deepfm":
        params = deepfm.init(jax.random.PRNGKey(0), 512, 4, 8)
        tr = CTRTrainer(params, deepfm.logits, cfg)
    else:
        params = deepfm.dcn_init(jax.random.PRNGKey(0), 512, 4, 8,
                                 n_cross=2)
        tr = CTRTrainer(params, deepfm.dcn_logits, cfg)
    hist = tr.fit(batch, epochs=8, batch_size=64)
    assert hist["loss"][-1] < hist["loss"][0]


def test_dense_ffm_trains_on_chip():
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.models import ffm
    from lightctr_tpu.models.ctr_trainer import CTRTrainer

    rng = _rng()
    fl = 4
    arrays = _sparse_batch(rng, n=128, f=256, nnz=fl, fields=fl)
    # dense FFM needs field-unique fids (libFFM semantics): fold each fid
    # into its field's disjoint id range
    arrays["fids"] = (
        arrays["fields"] * (256 // fl) + arrays["fids"] % (256 // fl)
    ).astype(np.int32)
    dense, perm, slices = ffm.densify(arrays, 256, fl)
    fused = ffm.make_dense_logits(slices)
    p0 = ffm.init(jax.random.PRNGKey(0), 256, fl, 4)
    params = {"w": p0["w"][perm], "v": p0["v"][perm]}
    tr = CTRTrainer(params, lambda p, b: fused(p, b)[0],
                    TrainConfig(learning_rate=0.1, lambda_l2=0.001),
                    fused_fn=fused)
    losses = tr.fit_fullbatch_scan(
        {k: jnp.asarray(v) for k, v in dense.items()}, 15
    )
    assert losses[-1] < losses[0]


def test_widedeep_trains_on_chip():
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.models import widedeep
    from lightctr_tpu.models.ctr_trainer import CTRTrainer

    rng = _rng()
    fl = 4
    batch = _rep_batch(rng, f=256, fl=fl, n=128, nnz=fl)
    params = widedeep.init(jax.random.PRNGKey(0), 256, fl, 8)
    tr = CTRTrainer(params, widedeep.logits,
                    TrainConfig(learning_rate=0.1))
    hist = tr.fit(batch, epochs=8, batch_size=64)
    assert hist["loss"][-1] < hist["loss"][0]


def test_zero_sharded_step_on_chip():
    """ZeRO-1 sharded weight update compiles and learns on the chip mesh
    (single chip = 1-member shard group; multi-chip behavior is proven on
    the virtual mesh)."""
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.core.mesh import MeshSpec, make_mesh
    from lightctr_tpu.models import fm
    from lightctr_tpu.models.ctr_trainer import CTRTrainer

    rng = _rng()
    batch = _sparse_batch(rng, n=64, f=257, nnz=6)
    params = fm.init(jax.random.PRNGKey(0), 257, 4)
    mesh = make_mesh(MeshSpec(data=len(jax.devices())))
    tr = CTRTrainer(params, fm.logits, TrainConfig(learning_rate=0.1),
                    fused_fn=fm.logits_with_l2, mesh=mesh,
                    zero_sharded=True)
    losses = tr.fit_fullbatch_scan(batch, 15)
    assert losses[-1] < losses[0]


# -- DL family ---------------------------------------------------------------


def test_cnn_lenet_trains_on_chip():
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.models import cnn
    from lightctr_tpu.models.dl_trainer import ClassifierTrainer

    feats, labels = _images(_rng())
    params = cnn.init(jax.random.PRNGKey(0))
    tr = ClassifierTrainer(params, cnn.logits,
                           TrainConfig(learning_rate=0.02), n_classes=10)
    hist = tr.fit(feats, labels, epochs=5)["loss"]
    assert hist[-1] < hist[0]
    acc = tr.evaluate(feats, labels)["accuracy"]
    assert acc > 0.5  # way above 10-class chance


def test_lstm_attention_trains_on_chip():
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.models import rnn
    from lightctr_tpu.models.dl_trainer import ClassifierTrainer

    feats, labels = _images(_rng(), n=96)
    params = rnn.init(jax.random.PRNGKey(0))
    tr = ClassifierTrainer(params, rnn.logits,
                           TrainConfig(learning_rate=0.03), n_classes=10)
    hist = tr.fit(feats, labels, epochs=6)["loss"]
    assert hist[-1] < hist[0]


def test_vae_trains_on_chip():
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.models import vae

    feats, _ = _images(_rng(), n=96)
    params = vae.init(jax.random.PRNGKey(0), 784, hidden=32, gauss_cnt=8)
    tr = vae.VAETrainer(params, TrainConfig(learning_rate=0.01))
    hist = tr.fit(feats, epochs=3, batch_size=32)["loss"]
    assert hist[-1] < hist[0]


# -- trees / EM / topic / embedding -----------------------------------------


def test_gbm_fit_predict_on_chip():
    from lightctr_tpu.models import gbm

    rng = _rng()
    x = rng.standard_normal((256, 10)).astype(np.float32)
    y = (x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.int32)
    model = gbm.GBMModel(gbm.GBMConfig(n_trees=6, max_depth=4, n_bins=16))
    losses = model.fit(x, y)
    assert losses[-1] < losses[0]
    assert model.evaluate(x, y)["accuracy"] > 0.85


def test_gmm_em_on_chip():
    from lightctr_tpu.models import gmm

    rng = _rng()
    x = np.concatenate([
        rng.standard_normal((80, 4)) + 4.0,
        rng.standard_normal((80, 4)) - 4.0,
    ]).astype(np.float32)
    params = gmm.init_from_data(jax.random.PRNGKey(0), 2, x)
    params, hist = gmm.fit(params, x, epochs=10)
    assert hist[-1] > hist[0]  # log-likelihood rises


def test_plsa_em_on_chip():
    from lightctr_tpu.models import plsa

    rng = _rng()
    counts = rng.integers(0, 5, size=(30, 50)).astype(np.float32)
    params = plsa.init(jax.random.PRNGKey(0), 30, 4, 50)
    params, hist = plsa.fit(params, counts, epochs=10)
    assert hist[-1] > hist[0]  # log-likelihood rises


def test_word2vec_trains_on_chip():
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.models import embedding

    rng = _rng()
    docs = [rng.integers(0, 40, size=25).astype(np.int32)
            for _ in range(30)]
    counts = np.bincount(np.concatenate(docs), minlength=40) + 1
    centers, contexts, mask = embedding.cbow_pairs(docs, window=3)
    tr = embedding.Word2VecTrainer(40, 8, TrainConfig(learning_rate=0.3),
                                   counts, mode="negative")
    hist = tr.fit(centers, contexts, mask, epochs=3, batch_size=64)
    assert hist[-1] < hist[0]
