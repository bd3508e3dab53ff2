"""Self-attention sequence CTR model: masking, learning, trainer interop."""

import jax
import jax.numpy as jnp
import numpy as np

from lightctr_tpu import TrainConfig, optim
from lightctr_tpu.models import attention_ctr
from lightctr_tpu.models.ctr_trainer import CTRTrainer


def seq_batch(rng, n=256, t=20, vocab=100):
    """Label depends on whether 'purchase-intent' items (ids < 10) appear."""
    ids = rng.integers(10, vocab, size=(n, t)).astype(np.int32)
    lengths = rng.integers(5, t + 1, size=n)
    mask = (np.arange(t)[None, :] < lengths[:, None]).astype(np.float32)
    y = (rng.random(n) < 0.5).astype(np.float32)
    for i in range(n):
        if y[i] == 1:  # plant signal items inside the valid prefix
            pos = rng.integers(0, lengths[i], size=2)
            ids[i, pos] = rng.integers(0, 10, size=2)
    ids[mask == 0] = 0
    return {"seq_ids": ids, "seq_mask": mask, "labels": y}


def test_padding_mask_invariance(rng):
    params, logits = attention_ctr.build(jax.random.PRNGKey(0), 50, dim=16, n_heads=2)
    b = seq_batch(rng, n=8, t=12, vocab=50)
    jb = {k: jnp.asarray(v) for k, v in b.items()}
    z1 = np.asarray(logits(params, jb))
    # garbage in padded slots must not change anything
    ids2 = b["seq_ids"].copy()
    ids2[b["seq_mask"] == 0] = 7
    jb2 = dict(jb, seq_ids=jnp.asarray(ids2))
    z2 = np.asarray(logits(params, jb2))
    np.testing.assert_allclose(z1, z2, rtol=1e-4, atol=1e-5)


def test_learns_sequence_signal(rng):
    batch = seq_batch(rng)
    params, logits = attention_ctr.build(jax.random.PRNGKey(0), 100, dim=32, n_heads=4)
    tr = CTRTrainer(params, logits, TrainConfig(learning_rate=0.01),
                    optimizer=optim.adam(0.003))
    hist = tr.fit(batch, epochs=30, batch_size=64)
    ev = tr.evaluate(batch)
    assert hist["loss"][-1] < hist["loss"][0]
    assert ev["auc"] > 0.9, ev


def test_seqctr_cli(tmp_path):
    import json
    import os
    import subprocess
    import sys

    path = str(tmp_path / "seq.txt")
    rng = np.random.default_rng(0)
    with open(path, "w") as f:
        for _ in range(150):
            t = rng.integers(5, 15)
            ids = rng.integers(10, 60, size=t)
            y = int(rng.random() < 0.5)
            if y:
                ids[rng.integers(0, t, 2)] = rng.integers(1, 10, 2)
            f.write(f"{y} " + " ".join(map(str, ids)) + "\n")
    from pathlib import Path

    repo_root = str(Path(__file__).resolve().parents[1])
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "lightctr_tpu.cli", "seqctr", "--data", path,
         "--epochs", "10", "--dim", "16", "--heads", "2", "--batch-size", "32"],
        capture_output=True, text=True, env=env, timeout=300, cwd=repo_root,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rep = json.loads(out.stdout.strip().splitlines()[-1])
    assert rep["train"]["auc"] > 0.8, rep


def test_rejects_bad_head_count():
    import pytest

    with pytest.raises(ValueError, match="divisible"):
        attention_ctr.build(jax.random.PRNGKey(0), 10, dim=10, n_heads=4)


def test_rejects_overlong_sequence(rng):
    import pytest

    params, logits = attention_ctr.build(
        jax.random.PRNGKey(0), 20, dim=8, n_heads=2, max_len=16
    )
    b = {
        "seq_ids": jnp.zeros((2, 32), jnp.int32),
        "seq_mask": jnp.ones((2, 32)),
        "labels": jnp.zeros((2,)),
    }
    with pytest.raises(ValueError, match="max_len"):
        logits(params, b)
