"""The Mellum 2 tower (``nn/gqa.py``, the softmax router of ``nn/moe.py``,
``models/mellum.py``) against the benchmark's plain reference
(``benchmarks/models/mellum.py``: positions counted against every earlier
token, the full masked softmax, a loop over the held experts), at a tiny
preset: hidden 64, 4 query heads over 2 KV heads of 16, a window of 8, 8
experts of which 2 are held, 4 a token, vocabulary 64, 48 tokens in three
documents of 13, 22 and 13 (longer than the window, on no block edge)."""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightctr_tpu import TrainConfig, obs
from lightctr_tpu.data import ingest
from lightctr_tpu.models import mellum
from lightctr_tpu.models.ctr_trainer import CTRTrainer
from lightctr_tpu.nn import gqa, mla, moe

from benchmarks.harness.reference import flat_leaves as flat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = 48
SEG = np.repeat([0, 1, 2], [13, 22, 13]).astype(np.int32)


def tiny_cfg(**over):
    """The shipped configuration file cut to the preset."""
    cfg = json.load(open(os.path.join(
        REPO, "benchmarks", "configs", "mellum2-12b-a2.5b-ep4.json")))
    cfg.update(
        hidden=64, dim=16, vocab=64, batch=T, sequences=1, num_attention_heads=4,
        num_key_value_heads=2, sliding_window=8, moe_intermediate_size=32,
        experts_routed_over=8, num_experts=2, num_experts_per_tok=4,
        gqa_query_block=16, moe_tile_rows=8, learning_rate=0.01)
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def ref():
    from benchmarks.models import mellum as model

    return model


@pytest.fixture(scope="module")
def setup(ref):
    cfg = tiny_cfg()
    params = jax.jit(lambda k: ref.init_params(cfg, k))(jax.random.PRNGKey(3))
    rng = np.random.default_rng(0)
    rows = {"fids": rng.integers(0, 64, (1, T)).astype(np.int32),
            "fields": SEG[None], "mask": np.ones((1, T), np.float32)}
    return cfg, params, ingest.sequence_batch(rows)


def ref_batch(batch):
    return {"tokens": jnp.asarray(batch["tokens"]),
            "segments": jnp.asarray(batch["segment_ids"]),
            "targets": jnp.asarray(np.roll(batch["tokens"], -1, axis=1))}


def masked_softmax(q, k, v, seg, window=0):
    """The whole [T, T] mask, key heads repeated to the query heads."""
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    n = q.shape[0]
    i, j = np.arange(n)[:, None], np.arange(n)[None]
    keep = (seg[:, None] == seg[None]) & (j <= i)
    if window:
        keep = keep & (i - j < window)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(q.shape[-1])
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(
        jnp.where(keep[None], scores, -jnp.inf), axis=-1), v)


def qkv(key, n=T, heads=4, kv_heads=2, d=16):
    ks = jax.random.split(key, 3)
    return (jax.random.normal(ks[0], (n, heads, d)),
            jax.random.normal(ks[1], (n, kv_heads, d)),
            jax.random.normal(ks[2], (n, kv_heads, d)))


# -- attention ------------------------------------------------------------------


@pytest.mark.parametrize("window, block", [(5, 8), (8, 16), (8, 48), (16, 8), (100, 16)])
def test_window_attention_is_the_masked_softmax(window, block):
    q, k, v = qkv(jax.random.PRNGKey(0))
    got = gqa.window_attention(q, k, v, jnp.asarray(SEG), window, block)
    np.testing.assert_allclose(got, masked_softmax(q, k, v, SEG, window), atol=2e-6)


def test_grouped_segment_attention_is_the_masked_softmax():
    q, k, v = qkv(jax.random.PRNGKey(1))
    got = mla.segment_attention(q, k, v, jnp.asarray(SEG), 16)
    np.testing.assert_allclose(got, masked_softmax(q, k, v, SEG), atol=2e-6)


def test_the_window_boundary_is_hfs_1024():
    """A key 1,023 back is seen, one 1,024 back is not (``i - j < W``), at
    the published window and query block."""
    n, i = 1536, 1535
    q, k, _ = qkv(jax.random.PRNGKey(2), n=n, heads=1, kv_heads=1, d=4)
    seg = jnp.zeros((n,), jnp.int32)
    for back, seen in ((1023, True), (1024, False)):
        v = jnp.zeros((n, 1, 1)).at[i - back].set(1.0)
        o = gqa.window_attention(q, k, v, seg, 1024, 512)
        assert (float(o[i, 0, 0]) > 0) == seen, back
    assert int(gqa.attended_pairs(seg, 1024)) == 1024 * 1025 // 2 + 512 * 1024


def test_a_window_as_long_as_the_sequence_is_the_full_layer(setup):
    _, params, _ = setup
    p = params["layer1"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, T, 64))
    cos, sin = gqa.rope_table(gqa.positions(jnp.asarray(SEG))[None],
                              gqa.default_inv_freq(16, 5e5))
    kw = dict(heads=4, kv_heads=2, block=16)
    np.testing.assert_allclose(
        gqa.mixer(p, x, SEG[None], cos, sin, window=T, **kw),
        gqa.mixer(p, x, SEG[None], cos, sin, window=0, **kw), atol=2e-6)


def test_yarn_at_the_published_numbers_is_the_closed_form(ref):
    cfg = json.load(open(os.path.join(
        REPO, "benchmarks", "configs", "mellum2-12b-a2.5b-ep4.json")))
    y = cfg["rope_parameters"]["full_attention"]
    assert gqa.yarn_range(128, 500000, 8192, 32, 1) == (18, 35)
    j = np.arange(64)
    base = 500000.0 ** (-2 * j / 128)
    ramp = np.clip((j - 18) / 17, 0, 1)
    want = base / 16 * ramp + base * (1 - ramp)
    spec = ref.spec_of(cfg)
    got, factor = spec.rope(mellum.FULL)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    assert factor == y["attention_factor"] == 1.2772588722239782
    np.testing.assert_allclose(got[:18], base[:18], rtol=1e-12)        # kept
    np.testing.assert_allclose(got[35:], base[35:] / 16, rtol=1e-12)   # interpolated
    np.testing.assert_allclose(ref.inv_freq(ref.sizes(cfg), mellum.FULL)[0], want, rtol=1e-12)
    window, one = spec.rope(mellum.WINDOW)
    np.testing.assert_allclose(window, base, rtol=1e-12)
    assert one == 1.0


def test_positions_restart_at_each_document(setup):
    """The second document's output is what it gives alone at the start of
    a sequence: its positions restart and nothing before it is seen."""
    _, params, _ = setup
    p = params["layer4"]["attn"]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, T, 64))
    spec = mellum.Spec()

    def attn(x, seg):
        cos, sin = gqa.rope_table(gqa.positions(seg), *spec.rope(mellum.FULL))
        return gqa.mixer(p, x, seg, cos, sin, heads=4, kv_heads=2, block=16)

    assert gqa.positions(jnp.asarray(SEG)).tolist() == (
        list(range(13)) + list(range(22)) + list(range(13)))
    whole = attn(x, SEG[None])
    alone = attn(x[:, 13:35], np.zeros((1, 22), np.int32))
    np.testing.assert_allclose(whole[:, 13:35], alone, atol=2e-6)


# -- the router ------------------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer_under_the_softmax_router(ref):
    """The four shares of two experts each, weighted over all the picked
    experts, add up to the whole layer of 8 as the reference computes it."""
    whole_cfg = tiny_cfg(num_experts=8)
    p = ref.init_params(whole_cfg, jax.random.PRNGKey(9))["layer2"]["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(10), (T, 64))
    whole = ref._moe(p, x, ref.sizes(whole_cfg), ref.knobs("f32"))
    total, held = 0.0, 0
    for first in (0, 2, 4, 6):
        share = dict(p, experts={k: v[first:first + 2] for k, v in p["experts"].items()})
        y, stats = moe.ffn(share, x, top_k=4, first=first, tile=8, router="softmax")
        total = total + y
        held += int(stats[1])
    assert held == T * 4
    np.testing.assert_allclose(total, whole, atol=1e-5)


def test_the_sigmoid_router_is_what_it_was():
    """Kimi Linear's path: the router PR 36 wrote, to the last bit."""
    x = jax.random.normal(jax.random.PRNGKey(0), (T, 16))
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    bias = jnp.zeros((8,)).at[3].set(0.5)
    s = jax.nn.sigmoid(x @ w)
    _, idx = jax.lax.top_k(s + bias, 4)
    picked = jnp.take_along_axis(s, idx, axis=-1)
    got_idx, got_w = moe.route(x, w, bias, 4, 2.446)
    assert (got_idx == idx).all()
    assert (got_w == 2.446 * picked / jnp.sum(picked, axis=-1, keepdims=True)).all()


def _kimi_ffn_as_pr39_wrote_it(p, x, *, top_k, scaling, first=0, tile=moe.TILE):
    flat = x.reshape(-1, x.shape[-1])
    held = p["experts"]
    with jax.named_scope("seq/moe"):
        with jax.named_scope("seq/moe/route"):
            s = jax.nn.sigmoid(flat @ p["router"])
            _, idx = jax.lax.top_k(s + p["router_bias"], top_k)
            picked = jnp.take_along_axis(s, idx, axis=-1)
            weights = scaling * picked / jnp.sum(picked, axis=-1, keepdims=True)
            tok, wt, expert, n_tiles, counts = moe.tile_plan(
                idx, weights, first, held["w_gate"].shape[0], tile)
        y = moe.swiglu(p["shared"], flat)
        with jax.named_scope("seq/moe/experts"):
            y = y + moe.grouped_experts(flat, wt, tok, expert, n_tiles,
                                        held["w_gate"], held["w_up"],
                                        held["w_down"])
    stats = jnp.stack([jnp.int32(idx.size), jnp.sum(counts), jnp.max(counts)])
    return y.reshape(x.shape), stats


def _kimi_block_as_pr39_wrote_it(q, k, v, seg_q, seg_k, first):
    scores = jnp.einsum("qhd,khd->hqk", q, k) * q.shape[-1] ** -0.5
    pos_q = first + jnp.arange(q.shape[0])
    keep = ((seg_q[:, None] == seg_k[None, :])
            & (jnp.arange(k.shape[0])[None, :] <= pos_q[:, None]))
    probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v)


def test_kimis_expert_layer_and_attention_trace_as_they_did():
    """The generalised router, optional shared expert and grouped attention
    core leave Kimi Linear's program as it was: the same equations, in the
    same order, forward and backward."""
    from lightctr_tpu.models import kimi_linear

    p = kimi_linear.init(jax.random.PRNGKey(0), kimi_linear.Spec())["layer2"]["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, T, 64))
    kw = dict(top_k=4, scaling=2.446, tile=8)

    def jaxprs(fn, *args):
        loss = lambda *a: jnp.sum(fn(*a)[0] if isinstance(fn(*a), tuple) else fn(*a))
        return str(jax.make_jaxpr(fn)(*args)), str(jax.make_jaxpr(jax.grad(loss))(*args))

    assert jaxprs(lambda p, x: moe.ffn(p, x, **kw), p, x) == jaxprs(
        lambda p, x: _kimi_ffn_as_pr39_wrote_it(p, x, **kw), p, x)
    q, k, v = qkv(jax.random.PRNGKey(2), heads=2, kv_heads=2)
    seg = jnp.asarray(SEG)
    assert jaxprs(lambda q, k, v: mla._block(q[16:32], k[:32], v[:32], seg[16:32],
                                             seg[:32], 16), q, k, v) == jaxprs(
        lambda q, k, v: _kimi_block_as_pr39_wrote_it(q[16:32], k[:32], v[:32],
                                                     seg[16:32], seg[:32], 16), q, k, v)


# -- the stack, the loss, the trainer ------------------------------------------


def test_logits_loss_and_every_leafs_gradient_are_the_references(ref, setup):
    cfg, params, batch = setup
    logits = mellum.make_logits(ref.spec_of(cfg))
    dev = {k: jnp.asarray(v) for k, v in batch.items()}
    z, _ = jax.jit(logits)(params, dev)
    with jax.default_matmul_precision("highest"):
        want_z = ref.reference_logits(params, {"tokens": dev["tokens"][0],
                                               "segments": dev["segment_ids"][0]}, cfg)
    np.testing.assert_allclose(z[0], want_z, atol=5e-6)
    tr = CTRTrainer(params, logits, TrainConfig(lambda_l2=0.0, loss="softmax_xent"))
    (loss, counts), grads = jax.jit(jax.value_and_grad(
        tr._make_loss_fn(), has_aux=True))(params, dev)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p, b: ref.reference_loss(p, b, cfg)))(params, ref_batch(batch))
    assert abs(float(loss) - float(want)) < 2e-6 * float(want)
    want_grads = flat(want_grads)
    for name, g in flat(grads).items():
        scale = float(jnp.max(jnp.abs(want_grads[name]))) + 1e-6
        np.testing.assert_allclose(g, want_grads[name], atol=2e-5 * scale + 1e-7,
                                   err_msg=name)
    # the softmax loss's own three, then the layers' routing, then the pairs
    # of each kind and those the full layer's core multiplies (blocks of
    # 16: all six under the diagonal)
    assert counts[:3].tolist() == [T, 45, 3]
    assert counts[-3:].tolist() == [76 + 148 + 76, 91 + 253 + 91, 6 * 16 * 16]


@pytest.fixture(scope="module")
def trained(ref, setup):
    """Three steps of the sparse trainer on one batch, read as the benchmark's
    runner reads them, with the registry its counters went to."""
    cfg, params, batch = setup
    with obs.override(True):
        tr = ref.build_trainer(cfg, copy.deepcopy(params))
        tr.telemetry = reg = obs.MetricsRegistry()
        got = {"loss": []}
        for i in range(3):
            got["loss"].append(float(tr.train_step(batch)))
            if i == 0:
                accum = dict(tr.opt_state["dense"].accum, **tr.opt_state["accum"])
                got["grad_norm"] = {k: float(jnp.sqrt(jnp.sum(v)))
                                    for k, v in flat(accum).items()}
        tr.flush_health()
    got["change_norm"] = {k: float(jnp.sqrt(jnp.sum((v - flat(params)[k]) ** 2)))
                          for k, v in flat(tr.params).items()}
    return got, reg.snapshot()


def test_three_train_steps_are_three_reference_steps(ref, setup, trained):
    from benchmarks.harness import reference

    cfg, params, batch = setup
    got, _ = trained
    want = ref.reference_steps(cfg, lambda: copy.deepcopy(params), [ref_batch(batch)] * 3)
    numbers = reference.compare(got, want)
    assert numbers["loss_gap"] < 1e-5, numbers
    assert numbers["grad_norm_gap"] < 1e-4 and numbers["change_norm_gap"] < 1e-4, numbers
    assert got["loss"][2] < got["loss"][0]


@pytest.mark.parametrize("variant", ["bf16", "no_segment_reset", "window_off",
                                     "yarn_off", "absent_experts_renormalised"])
def test_the_control_and_each_planted_fault_read_far_from_the_reference(
        ref, setup, variant):
    from benchmarks.harness import reference

    cfg, params, batch = setup
    steps = [ref_batch(batch)] * 3
    want = ref.reference_steps(cfg, lambda: copy.deepcopy(params), steps)
    bad = ref.reference_steps(cfg, lambda: copy.deepcopy(params), steps, variant)
    assert reference.compare(bad, want)["grad_norm_gap"] > 1e-3


def test_the_counters_off_the_health_vector_are_a_host_count(trained):
    counters = trained[1]["counters"]
    assert counters["trainer_seq_tokens_total"] == 3 * T
    assert counters["trainer_seq_documents_total"] == 3 * 3
    window = obs.labeled("trainer_seq_attn_pairs_total", kind="window")
    full = obs.labeled("trainer_seq_attn_pairs_total", kind="full")
    # the pairs one layer of each kind attends, a step: sum over documents
    # of L (L + 1) / 2, and under the window of 8 at most 8 a token
    assert counters[full] == 3 * sum(n * (n + 1) // 2 for n in (13, 22, 13))
    assert counters[window] == 3 * sum(36 + (n - 8) * 8 for n in (13, 22, 13))
    assert counters[obs.labeled("trainer_seq_attn_visited_pairs_total", kind="full")] == (
        3 * int(mla.visited_pairs(jnp.asarray(SEG), 16)))
    for layer in ("1", "2", "3", "4"):
        assert counters[obs.labeled("trainer_moe_assignments_total", layer=layer)] == 3 * T * 4


def test_metrics_report_seq_prints_the_attention_pairs(tmp_path, trained, capsys):
    from tools import metrics_report

    path = tmp_path / "snap.json"
    path.write_text(json.dumps(trained[1]))
    assert metrics_report.main(["--seq", str(path), "--held-experts", "2"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["attention_pairs"] == {"full": 3 * 435, "window": 3 * 300,
                                         "window_over_full": 300 / 435}
    # the full layer's core at blocks of 16 visits the 6 blocks under the
    # diagonal: 1,536 pairs for the 435 its mask holds
    assert report["attention_visited"] == {
        "full": {"pairs": 3 * 1536, "over_needed": 1536 / 435}}
    assert sorted(report["moe_layers"]) == ["1", "2", "3", "4"]
