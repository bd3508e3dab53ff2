"""The sequence tower (``nn/kda.py``, ``nn/mla.py``, ``nn/moe.py``,
``models/kimi_linear.py``, the softmax loss of the CTR trainers) against the
benchmark's plain reference (``benchmarks/models/kimi_linear.py``: the
recurrence a token at a time, the full masked softmax, a loop over the held
experts), at a tiny preset: hidden 64, 2 heads of 16, 8 experts of which 2
are held, 4 a token, vocabulary 64, 48 tokens in three documents whose
boundaries (13, 35) fall on no chunk edge (16, 32)."""

import copy
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightctr_tpu import TrainConfig, obs
from lightctr_tpu.data import ingest
from lightctr_tpu.models import kimi_linear
from lightctr_tpu.models.ctr_trainer import CTRTrainer
from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer
from lightctr_tpu.nn import kda, mla, moe

from benchmarks.harness.reference import flat_leaves as flat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = 48
SEG = np.repeat([0, 1, 2], [13, 22, 13]).astype(np.int32)


def tiny_cfg(**over):
    """The shipped configuration file cut to the preset."""
    cfg = json.load(open(os.path.join(
        REPO, "benchmarks", "configs", "kimi-linear-48b-a3b-ep16.json")))
    cfg.update(
        hidden=64, dim=16, vocab=64, batch=T, sequences=1,
        linear_attn_config=dict(cfg["linear_attn_config"], num_heads=2, head_dim=16),
        num_attention_heads=2, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
        moe_intermediate_size=32, experts_routed_over=8, num_experts=2,
        num_experts_per_token=4, kda_gate_rank=16, kda_chunk=16,
        mla_query_block=16, moe_tile_rows=8, mixer_head_groups=2,
        dense_ffn_block_rows=16, learning_rate=0.01)
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def ref():
    from benchmarks.models import kimi_linear as model

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


def kda_inputs(key, heads=2, d=16, scale=8.0):
    ks = jax.random.split(key, 5)
    q = kda.l2_norm(jax.random.normal(ks[0], (T, heads, d)))
    k = kda.l2_norm(jax.random.normal(ks[1], (T, heads, d)))
    v = jax.random.normal(ks[2], (T, heads, d))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (T, heads, d))) * scale
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (T, heads)))
    return q, k, v, g, beta


# -- KDA ------------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_kda_is_the_token_recurrence(ref, chunk):
    x = kda_inputs(jax.random.PRNGKey(0))
    got = kda.chunked_delta_rule(*(a[None] for a in x), SEG[None], chunk=chunk)[0]
    want = ref._recurrence(*x, jnp.asarray(SEG))
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_chunked_kda_gradients_are_the_recurrences(ref):
    x = kda_inputs(jax.random.PRNGKey(1))
    w = jax.random.normal(jax.random.PRNGKey(2), (T, 2, 16))
    seg = jnp.asarray(SEG)
    got = jax.grad(lambda *a: jnp.sum(w * kda.chunked_delta_rule(
        *(z[None] for z in a), seg[None], chunk=16)[0]), argnums=range(5))(*x)
    want = jax.grad(lambda *a: jnp.sum(w * ref._recurrence(*a, seg)),
                    argnums=range(5))(*x)
    for g, r in zip(got, want):
        assert np.all(np.isfinite(g))
        np.testing.assert_allclose(g, r, atol=5e-6)


def test_a_decay_too_strong_for_a_factored_form_stays_finite(ref):
    """exp(-G) over a chunk overflows float32 at the published A_log range;
    the pairwise form takes differences first."""
    q, k, v, g, beta = kda_inputs(jax.random.PRNGKey(4), scale=40.0)
    got = kda.chunked_delta_rule(*(a[None] for a in (q, k, v, g, beta)),
                                 SEG[None], chunk=16)[0]
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, ref._recurrence(q, k, v, g, beta, jnp.asarray(SEG)),
                               atol=2e-6)


def test_state_and_convolution_stop_at_a_document_boundary():
    """What precedes a boundary does not reach what follows it."""
    p = kimi_linear.init(jax.random.PRNGKey(0), kimi_linear.Spec())["layer1"]["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(1), (1, T, 64))
    mix = jax.jit(lambda x, seg: kda.mixer(p, x, seg, heads=2, eps=1e-5, chunk=16))
    y = mix(x, SEG[None])
    x2 = x.at[:, :13].set(jax.random.normal(jax.random.PRNGKey(2), (1, 13, 64)))
    y2 = mix(x2, SEG[None])
    np.testing.assert_allclose(y[:, 13:], y2[:, 13:], atol=1e-6)   # rounding alone
    assert float(jnp.max(jnp.abs(y[:, :13] - y2[:, :13]))) > 1e-3
    one = mix(x, np.zeros_like(SEG)[None])
    assert float(jnp.max(jnp.abs(one[:, 13:] - y[:, 13:]))) > 1e-3


# -- MLA ------------------------------------------------------------------------


@pytest.mark.parametrize("block", [16, 20, 48])
def test_blocked_segment_attention_is_the_full_masked_softmax(block):
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (T, 2, 24))
    k = jax.random.normal(ks[1], (T, 2, 24))
    v = jax.random.normal(ks[2], (T, 2, 16))
    got = mla.segment_attention(q, k, v, jnp.asarray(SEG), block)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(24.0)
    keep = (SEG[:, None] == SEG[None, :]) & (np.arange(T)[None] <= np.arange(T)[:, None])
    want = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(
        jnp.where(keep[None], scores, -jnp.inf), axis=-1), v)
    np.testing.assert_allclose(got, want, atol=2e-6)


@pytest.mark.parametrize("kind, layer", [("kda", "layer1"), ("mla", "layer4")])
def test_a_mixer_in_head_groups_is_the_references(ref, setup, kind, layer):
    cfg, params, _ = setup
    p = params[layer]["mixer"]
    x = jax.random.normal(jax.random.PRNGKey(6), (T, 64))
    z = ref.sizes(cfg)
    if kind == "kda":
        want = ref._kda(p, x, jnp.asarray(SEG), z)
        got = [kda.mixer(p, x[None], SEG[None], heads=2, eps=1e-5, chunk=16,
                         groups=g)[0] for g in (1, 2)]
    else:
        want = ref._mla(p, x, jnp.asarray(SEG), z)
        got = [mla.mixer(p, x[None], SEG[None], heads=2, d_nope=16, d_pe=8,
                         eps=1e-5, block=16, groups=g)[0] for g in (1, 2)]
    for y in got:
        np.testing.assert_allclose(y, want, atol=5e-6)


# -- the expert layer -----------------------------------------------------------


def moe_layer(ref, setup):
    cfg, params, _ = setup
    return ref.sizes(cfg), params["layer2"]["ffn"], jax.random.normal(
        jax.random.PRNGKey(7), (T, 64))


def test_expert_layer_and_its_gradient_are_the_references(ref, setup):
    z, p, x = moe_layer(ref, setup)
    w = jax.random.normal(jax.random.PRNGKey(8), (T, 64))

    def program(p, x):
        return jnp.sum(w * moe.ffn(p, x, top_k=4, scaling=2.446, first=0, tile=8)[0])

    def plain(p, x):
        return jnp.sum(w * ref._moe(p, x, z, "f32"))

    np.testing.assert_allclose(moe.ffn(p, x, top_k=4, scaling=2.446, tile=8)[0],
                               ref._moe(p, x, z, "f32"), atol=5e-6)
    got = jax.grad(program, argnums=(0, 1))(p, x)
    want = jax.grad(plain, argnums=(0, 1))(p, x)
    for (name, g), r in zip(flat({"p": got[0], "x": {"x": got[1]}}).items(),
                            flat({"p": want[0], "x": {"x": want[1]}}).values()):
        np.testing.assert_allclose(g, r, atol=1e-5, err_msg=name)
    assert float(jnp.max(jnp.abs(got[0]["router"]))) > 0     # the weights carry it


def test_routing_is_dropless_with_every_token_sent_to_one_held_expert(ref, setup):
    """A router that sends all 48 tokens to held expert 1 (and to three absent
    ones): six tiles of 8 rows, all computed, none dropped."""
    z, p, x = moe_layer(ref, setup)
    bias = jnp.zeros((8,)).at[jnp.array([1, 5, 6, 7])].set(10.0)
    p = dict(p, router_bias=bias)
    y, stats = moe.ffn(p, x, top_k=4, scaling=2.446, first=0, tile=8)
    assert stats.tolist() == [T * 4, T, T]
    np.testing.assert_allclose(y, ref._moe(p, x, z, "f32"), atol=5e-6)
    idx, wts = moe.route(x, p["router"], bias, 4, 2.446)
    *_, n_tiles, counts = moe.tile_plan(idx, wts, 0, 2, 8)
    assert int(n_tiles) == 6 and counts.tolist() == [0, T]


def test_the_shares_add_up_to_the_uncut_layer(ref, setup):
    """The share test: the routed parts that the four shares of two experts
    compute, with the shared expert counted once, are the whole layer of 8
    experts as the reference computes it uncut."""
    cfg, _, _ = setup
    whole_cfg = tiny_cfg(num_experts=8)
    p = ref.init_params(whole_cfg, jax.random.PRNGKey(9))["layer3"]["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(10), (T, 64))
    whole = ref._moe(p, x, ref.sizes(whole_cfg), "f32")
    shared = moe.swiglu(p["shared"], x)
    total, held = shared, 0
    for first in (0, 2, 4, 6):
        share = dict(p, experts={k: v[first:first + 2] for k, v in p["experts"].items()})
        y, stats = moe.ffn(share, x, top_k=4, scaling=2.446, first=first, tile=8)
        total = total + (y - shared)
        held += int(stats[1])
    assert held == T * 4                       # every assignment is somebody's
    np.testing.assert_allclose(total, whole, atol=1e-5)


# -- the stack, the loss, the trainer ------------------------------------------


def test_the_stacks_loss_and_every_leafs_gradient_are_the_references(ref, setup):
    cfg, params, batch = setup
    logits = kimi_linear.make_logits(ref.spec_of(cfg))
    tr = CTRTrainer(params, logits, TrainConfig(lambda_l2=0.0, loss="softmax_xent"))
    (loss, counts), grads = jax.jit(jax.value_and_grad(
        tr._make_loss_fn(), has_aux=True))(
        params, {k: jnp.asarray(v) for k, v in batch.items()})
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p, b: ref.reference_loss(p, b, cfg)))(params, ref_batch(batch))
    assert abs(float(loss) - float(want)) < 2e-6 * float(want)
    want_grads = flat(want_grads)
    for name, g in flat(grads).items():
        scale = float(jnp.max(jnp.abs(want_grads[name]))) + 1e-6
        np.testing.assert_allclose(g, want_grads[name], atol=2e-5 * scale + 1e-7,
                                   err_msg=name)
    assert counts[:3].tolist() == [T, 45, 3]


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


@pytest.mark.parametrize("variant, failing", [
    ("bf16", "loss_gap"), ("no_segment_reset", "grad_norm_gap"),
    ("absent_experts_renormalised", "grad_norm_gap"), ("half_targets", "grad_norm_gap")])
def test_the_control_and_each_planted_fault_read_far_from_the_reference(
        ref, setup, variant, failing):
    """On two layers (KDA over a dense FFN, KDA over the expert layer): a
    variant's reference step compiles in a few seconds."""
    from benchmarks.harness import reference

    _, _, batch = setup
    cfg = tiny_cfg(num_hidden_layers=2)
    params = jax.jit(lambda k: ref.init_params(cfg, k))(jax.random.PRNGKey(3))
    steps = [ref_batch(batch)] * 3
    want = ref.reference_steps(cfg, lambda: copy.deepcopy(params), steps)
    bad = ref.reference_steps(cfg, lambda: copy.deepcopy(params), steps, variant)
    assert reference.compare(bad, want)[failing] > 1e-3


def test_softmax_loss_is_the_masked_mean_of_the_cross_entropy():
    z = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 7))
    targets = jnp.asarray(np.random.default_rng(0).integers(0, 7, (2, 5)), jnp.int32)
    mask = jnp.asarray([[1, 1, 0, 1, 0], [0, 1, 1, 1, 1]], jnp.float32)
    tr = CTRTrainer({"z": z}, lambda p, b: p["z"],
                    TrainConfig(lambda_l2=0.0, loss="softmax_xent"))
    loss, counts = tr._make_loss_fn()(
        {"z": z}, {"targets": targets, "target_mask": mask})
    logp = np.asarray(jax.nn.log_softmax(z, axis=-1))
    want = -sum(logp[b, t, int(targets[b, t])] for b in range(2) for t in range(5)
                if mask[b, t]) / 7
    assert abs(float(loss) - want) < 1e-6
    assert counts.tolist() == [10, 7, 2]
    assert float(tr.train_step({"targets": targets, "target_mask": mask})) == pytest.approx(want, abs=1e-6)


def test_logistic_path_numbers_are_what_they_were():
    """The binary path is not touched: mean log-loss plus lambda_l2 * l2 / n,
    to the last bit of a formula written out here."""
    z = jnp.asarray([0.3, -1.2, 2.0, 0.0])
    y = jnp.asarray([1.0, 0.0, 1.0, 0.0])
    tr = CTRTrainer({"z": z}, lambda p, b: p["z"], TrainConfig(lambda_l2=0.5),
                    l2_fn=lambda p, b: jnp.sum(p["z"] ** 2))
    assert tr.cfg.loss == "logistic"
    got = tr._make_loss_fn()({"z": z}, {"labels": y})
    per = jnp.maximum(z, 0) - y * z + jnp.log1p(jnp.exp(-jnp.abs(z)))
    want = (jnp.sum(per) + 0.5 * jnp.sum(z ** 2)) / 4
    assert float(got) == pytest.approx(float(want), rel=1e-7)


@pytest.mark.parametrize("kw, err", [
    (dict(cfg=TrainConfig(loss="hinge")), "must be one of"),
    (dict(cfg=TrainConfig(loss="softmax_xent"), quality_bins=16), "one-program step"),
])
def test_a_loss_the_step_cannot_build_is_refused(kw, err):
    with pytest.raises(ValueError, match=err):
        CTRTrainer({"z": jnp.zeros(3)}, lambda p, b: p["z"], **kw)


def test_softmax_loss_is_refused_on_the_hybrid_exchange():
    from lightctr_tpu.core.mesh import MeshSpec, make_mesh

    params, logits = kimi_linear.build(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="one-program step"):
        SparseTableCTRTrainer(params, logits, TrainConfig(loss="softmax_xent"),
                              sparse_tables={"embed": ["tokens"]},
                              mesh=make_mesh(MeshSpec(data=2)))


def test_the_counters_off_the_health_vector_are_a_host_count(ref, setup, trained):
    cfg, params, batch = setup
    counters = trained[1]["counters"]
    assert counters["trainer_seq_tokens_total"] == 3 * T
    assert counters["trainer_seq_targets_total"] == 3 * int(batch["target_mask"].sum())
    assert counters["trainer_seq_documents_total"] == 3 * 3
    # the router's picks of the first step, counted on the host from the
    # initial weights
    logits = jax.jit(kimi_linear.make_logits(ref.spec_of(cfg)))
    _, stats = logits(params, {k: jnp.asarray(v) for k, v in batch.items()})
    for row, layer in zip(np.asarray(stats).reshape(4, 3), ("2", "3", "4", "5")):
        name = obs.labeled("trainer_moe_assignments_total", layer=layer)
        assert counters[name] == 3 * T * 4 and row[0] == T * 4
        held = counters[obs.labeled("trainer_moe_held_assignments_total", layer=layer)]
        most = counters[obs.labeled("trainer_moe_expert_tokens_max", layer=layer)]
        assert row[1] <= held <= 3 * T * 2 and row[2] <= most <= held
    idx, _ = moe.route(jax.random.normal(jax.random.PRNGKey(0), (T, 64)),
                       params["layer2"]["ffn"]["router"], jnp.zeros(8), 4, 2.446)
    plan = moe.tile_plan(idx, jnp.ones(idx.shape), 0, 2, 8)
    host = np.bincount(np.asarray(idx).reshape(-1), minlength=8)[:2]
    assert plan[-1].tolist() == host.tolist()


def test_metrics_report_seq_prints_held_share_and_load(tmp_path, trained, capsys):
    from tools import metrics_report

    path = tmp_path / "snap.json"
    path.write_text(json.dumps(trained[1]))
    assert metrics_report.main(["--seq", str(path), "--held-experts", "2"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["sequences"] == {"tokens": 3 * T, "targets": 3 * 45, "documents": 9,
                                   "tokens_per_document": 16.0}
    assert sorted(report["moe_layers"]) == ["2", "3", "4", "5"]
    for entry in report["moe_layers"].values():
        assert entry["assignments"] == 3 * T * 4
        assert 0 < entry["held_share"] < 1 and 1.0 <= entry["max_over_mean"] <= 2.0


# -- packed rows ----------------------------------------------------------------


def test_pack_documents_cuts_at_every_seq_len_and_renumbers():
    rows = ingest.pack_documents([[1, 2, 3], [4, 5, 6, 7, 8], [9]], 4)
    assert rows["fids"].tolist() == [[1, 2, 3, 4], [5, 6, 7, 8], [9, 0, 0, 0]]
    assert rows["mask"].tolist() == [[1, 1, 1, 1], [1, 1, 1, 1], [1, 0, 0, 0]]
    b = ingest.sequence_batch(rows)
    assert b["segment_ids"].tolist() == [[0, 0, 0, 1], [0, 0, 0, 0], [0, -1, -1, -1]]
    assert b["targets"].tolist() == [[2, 3, 0, 0], [6, 7, 8, 0], [0, 0, 0, 0]]
    assert b["target_mask"].tolist() == [[1, 1, 0, 0], [1, 1, 1, 0], [0, 0, 0, 0]]
    with pytest.raises(ValueError):
        ingest.pack_documents([[]], 4)


def test_cli_trains_the_tiny_preset(tmp_path, capsys):
    from lightctr_tpu.cli.__main__ import main

    rng = np.random.default_rng(0)
    path = tmp_path / "docs.txt"
    path.write_text("\n".join(
        " ".join(str(3 + (i * 7 + j) % 50) for j in range(int(n)))
        for i, n in enumerate(rng.integers(5, 40, 12))))
    assert main(["seqlm", "--data", str(path), "--epochs", "6", "--seq-len", "32",
                 "--lr", "0.05"]) == 0
    report = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert report["model"] == "seqlm" and report["documents"] == 12
    assert report["final_loss"] < report["first_loss"]
    assert report["held_share"] > 0
