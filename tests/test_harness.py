"""Graph DAG API, checkpointing, text tooling, native components, heartbeat,
CLI."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightctr_tpu import ckpt, graph, optim
from lightctr_tpu.data import text
from lightctr_tpu.dist.bootstrap import HeartbeatMonitor


def test_dag_unit_test_parity(rng):
    """The reference's -DDAG test: sigma(w*x + b) with logistic loss trains
    for 20 steps with decreasing loss (main.cpp:80-116)."""
    g = graph.Graph()
    x = g.add_node(graph.source("x"))
    w = g.add_node(graph.trainable("w", jnp.zeros((4,))))
    b = g.add_node(graph.trainable("b", jnp.zeros(())))
    wx = g.add_node(graph.matmul(x, w))
    z = g.add_node(graph.add(wx, b))
    p = g.add_node(graph.activation(z, "sigmoid"))
    loss_id = g.add_node(graph.logistic_loss_node(p, label_name="y"))

    w_true = rng.normal(size=(4,)).astype(np.float32)
    X = rng.normal(size=(64, 4)).astype(np.float32)
    y = (1 / (1 + np.exp(-X @ w_true)) > rng.random(64)).astype(np.float32)
    feeds = {"x": jnp.asarray(X), "y": jnp.asarray(y)}

    step, opt_state = g.compile_train_step(loss_id, optim.sgd(0.5))
    params = g.init_params()
    losses = []
    for _ in range(20):
        params, opt_state, loss = step(params, opt_state, feeds)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses  # "Pass All DAG UnitTest!"
    fwd = g.compile_forward(p)
    probs = np.asarray(fwd(params, feeds))
    assert probs.shape == (64,) and np.all((probs > 0) & (probs < 1))


def test_checkpoint_roundtrip(tmp_path, rng):
    state = {
        "params": {"w": jnp.asarray(rng.normal(size=(8, 3)).astype(np.float32))},
        "step": jnp.asarray(7),
    }
    ckpt.save(str(tmp_path), 7, state)
    assert ckpt.latest_step(str(tmp_path)) == 7
    out = ckpt.restore(str(tmp_path), like=state)
    np.testing.assert_allclose(
        np.asarray(out["params"]["w"]), np.asarray(state["params"]["w"])
    )


def test_checkpointer_retention(tmp_path):
    c = ckpt.Checkpointer(str(tmp_path), keep=2, every=1)
    for s in range(5):
        c.maybe_save(s, {"x": jnp.asarray(float(s))})
    steps = sorted(
        int(d.split("_")[1]) for d in os.listdir(tmp_path) if d.startswith("step_")
    )
    assert steps == [3, 4]
    out = c.restore_latest(like={"x": jnp.asarray(0.0)})
    assert float(out["x"]) == 4.0


def test_text_tooling(tmp_path):
    docs = [text.tokenize("the cat sat on the mat"), text.tokenize("the dog")]
    words, counts, w2i = text.build_vocab(docs, max_size=10)
    assert words[0] == "the" and counts[0] == 3
    m = text.doc_term_matrix(docs, w2i)
    assert m.shape == (2, len(words))
    assert m[0, w2i["cat"]] == 1 and m[0, w2i["the"]] == 2
    path = str(tmp_path / "vocab.txt")
    text.save_vocab(path, words, counts)
    from lightctr_tpu.models.embedding import load_vocab

    words2, counts2 = load_vocab(path)
    assert words2 == words and np.array_equal(counts2, counts)
    ids = text.docs_to_ids(docs, w2i)
    assert ids[0].dtype == np.int32 and len(ids[0]) == 6


def test_native_parser_matches_python(tmp_path):
    from lightctr_tpu import native

    if not native.available():
        pytest.skip("no g++")
    p = str(tmp_path / "data.csv")
    with open(p, "w") as f:
        f.write("1 0:5:1.5 2:7:0.25\n0 1:3:1\n")
    fields, fids, vals, mask, labels = native.parse_libffm_native(p)
    np.testing.assert_array_equal(fields, [[0, 2], [1, 0]])
    np.testing.assert_array_equal(fids, [[5, 7], [3, 0]])
    np.testing.assert_allclose(vals, [[1.5, 0.25], [1.0, 0.0]])
    np.testing.assert_array_equal(labels, [1, 0])
    # malformed file raises with line number
    bad = str(tmp_path / "bad.csv")
    with open(bad, "w") as f:
        f.write("1 0:5:1\n0 junk\n")
    with pytest.raises(ValueError, match="bad.csv:2"):
        native.parse_libffm_native(bad)


def test_shm_kv_concurrent_adds(tmp_path):
    import threading

    from lightctr_tpu import native

    if not native.available():
        pytest.skip("no g++")
    p = str(tmp_path / "kv.bin")
    kv = native.ShmKV.create(p, 256, 2)

    def worker():
        for _ in range(500):
            kv.add(11, np.asarray([1.0, -1.0], np.float32))

    ts = [threading.Thread(target=worker) for _ in range(4)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    # float-CAS adds must not lose updates
    np.testing.assert_allclose(kv.get(11), [2000.0, -2000.0])
    kv.close()


def test_heartbeat_monitor():
    t = [0.0]
    deaths = []
    mon = HeartbeatMonitor(on_dead=deaths.append, clock=lambda: t[0])
    mon.beat("w1")
    mon.beat("w2")
    assert mon.check() == {"w1": "alive", "w2": "alive"}
    t[0] = 12.0
    mon.beat("w2")
    assert mon.check() == {"w1": "stale", "w2": "alive"}
    t[0] = 21.0
    st = mon.check()
    assert st["w1"] == "dead" and deaths == ["w1"]
    # returning node re-registers (master.h:80-82)
    mon.beat("w1")
    assert mon.check()["w1"] == "alive"


def test_cli_fm_end_to_end(tmp_path):
    """Drive the CLI binary like a user (replacing the -D ifdef tree)."""
    data = str(tmp_path / "train.csv")
    rng = np.random.default_rng(0)
    with open(data, "w") as f:
        for i in range(120):
            fids = rng.integers(1, 50, size=5)
            label = int(fids.sum() % 2)
            f.write(f"{label} " + " ".join(f"0:{fid}:1" for fid in fids) + "\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "lightctr_tpu.cli", "fm", "--data", data,
         "--epochs", "5", "--full-batch", "--factor", "4"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    report = json.loads(out.stdout.strip().splitlines()[-1])
    assert report["model"] == "fm" and "train" in report
    assert np.isfinite(report["final_loss"])
