"""Mellum 2 (huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct): the
benchmark's own initialiser, the plain float32 reference with its loss,
gradient and dense Adagrad, the work counts, and the adapter that builds the
program under test.

Block, every layer: ``h = x + Attn(RMSNorm(x))``, ``y = h + MoE(RMSNorm(h))``;
a final RMSNorm, then ``logits = y W_head`` over the vocabulary slice.

Attention (H query heads over Hk KV heads of d; query head h reads KV head
h // (H / Hk); no biases).  ``pos_t`` is the number of tokens before t in
t's own document:

    q = x Wq,  k = x Wk,  v = x Wv
    rot(x)_t = [x1 c - x2 s | x2 c + x1 s],  x = [x1 | x2] halves,  c, s = f cos(pos_t w), f sin(pos_t w)
    a_ij = softmax_j(rot(q)_i rot(k)_j / sqrt(d))  over j: same document, j <= i, i - j < W (window layers)
    Attn(x) = concat_h(a v) Wo

``w`` is ``theta^(-2j/d)`` with f = 1 in a sliding_attention layer; in a
full_attention layer YaRN's (HF ``_compute_yarn_parameters``, truncated):
``w_j = (b_j / factor) r_j + b_j (1 - r_j)``, ``b_j = theta^(-2j/d)``, ``r_j``
the ramp from ``low = floor(corr(beta_fast))`` to ``high = ceil(corr(beta_slow))``,
``corr(n) = d ln(original / (2 pi n)) / (2 ln theta)``, and f the attention factor.

MoE (no shared expert):

    p = softmax(x Wr) over all E experts;  E(t) = top-k of p;  w_e = p_e / sum_{e' in E(t)} p_e'
    MoE(x) = sum_{e in E(t), e held here} w_e Expert_e(x),   Expert = Wdown(silu(x Wgate) * x Wup)

Loss: the mean over the target positions of the softmax cross-entropy of token
t+1 under the slice's logits; a document's last token and the sequence's last
have no target.

Only ``build_trainer`` / ``aot_trainer`` / ``spec_of`` import the program.
The reference is the equations above as they stand: the positions counted a
token at a time against every earlier token, the full ``[T, T]`` mask a block
of queries at a time, one KV head at a time, the expert sum a loop over the
held experts each over all tokens, the head and loss a block of tokens at a
time.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import numpy as np

from benchmarks.models.kimi_linear import (  # noqa: F401  (the adapter's)
    _nested, _rms, _swiglu, aot_batch, feed_layout, reference_view)

#: table leaf -> the batch field whose ids index it (reference's view)
TABLES = {"embed": "tokens"}
VARIANTS = ("f32", "bf16", "no_segment_reset", "window_off", "yarn_off",
            "absent_experts_renormalised")
WINDOW, FULL = "sliding_attention", "full_attention"
REF_QUERY_BLOCK = 256
REF_ROW_BLOCK = 2048  # tokens of the head and loss at once


def sizes(cfg: Dict) -> Dict:
    n = cfg["num_hidden_layers"]
    if any(m != "sparse" for m in cfg["mlp_layer_types"][:n]):
        raise ValueError("every layer's MLP is routed")
    return {
        "hidden": cfg["hidden"], "vocab": cfg["vocab"],
        "kinds": tuple(cfg["layer_types"][:n]),
        "heads": cfg["num_attention_heads"], "kv_heads": cfg["num_key_value_heads"],
        "dim": cfg["dim"], "window": cfg["sliding_window"],
        "rope": cfg["rope_parameters"], "expert_width": cfg["moe_intermediate_size"],
        "n_experts": cfg["experts_routed_over"], "held": cfg["num_experts"],
        "first_held": cfg["first_expert_held"], "top_k": cfg["num_experts_per_tok"],
        "eps": cfg["rms_norm_eps"],
        "tokens": cfg["batch"] // cfg["sequences"], "sequences": cfg["sequences"],
    }


# -- weights --------------------------------------------------------------------


def _leaf_shapes(cfg: Dict) -> Dict:
    """``{leaf path: (shape, fan_in or a rule's name)}`` in the program's tree."""
    z = sizes(cfg)
    d, f = z["hidden"], z["expert_width"]
    hq, hk = z["heads"] * z["dim"], z["kv_heads"] * z["dim"]
    out = {"embed": ((z["vocab"], d), "table"), "final_norm": ((d,), "one"),
           "head": ((d, z["vocab"]), d)}
    for i in range(1, len(z["kinds"]) + 1):
        p = f"layer{i}."
        out[p + "norm1"] = out[p + "norm2"] = ((d,), "one")
        out[p + "attn.wq"] = ((d, hq), d)
        out[p + "attn.wk"] = out[p + "attn.wv"] = ((d, hk), d)
        out[p + "attn.wo"] = ((hq, d), hq)
        out[p + "ffn.router"] = ((d, z["n_experts"]), d)
        out[p + "ffn.experts.w_gate"] = ((z["held"], d, f), d)
        out[p + "ffn.experts.w_up"] = ((z["held"], d, f), d)
        out[p + "ffn.experts.w_down"] = ((z["held"], f, d), f)
    return out


def init_params(cfg: Dict, key) -> Dict:
    """Matrices ~ N(0, 1 / fan_in), ``wo`` and ``w_down`` over ``2 *
    residual_init_layers`` besides (the scaled initialisation of deep
    residual stacks; ``kimi_linear.init_params`` says why the router needs
    it), norm weights one, the embedding N(0, 1), all by index hash.
    float32.  They come from ``cfg['weight_seed']``; ``key``, the run's
    ``--seed``, is not read: the weights decide the routing, the routing how
    many tokens the held experts take, and the step's time follows that
    (2.6 us a held assignment, 125k-138k a step over nine draws: the rate
    spread 0.0121, PERF.md section 6, PR 40).  ``--seed`` then picks the
    replay's order alone."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.common import prng_key
    from benchmarks.harness.initializers import hashed_normal

    key = prng_key(cfg["weight_seed"])
    flat = {}
    for n, (path, (shape, rule)) in enumerate(sorted(_leaf_shapes(cfg).items())):
        if rule == "one":
            flat[path] = jnp.ones(shape, jnp.float32)
            continue
        v = hashed_normal(jax.random.fold_in(key, n), math.prod(shape[:-1]),
                          shape[-1]).reshape(shape)
        if rule != "table":
            v = v / jnp.sqrt(float(rule))
        if path.endswith((".wo", "w_down")):
            v = v / jnp.sqrt(2.0 * cfg["residual_init_layers"])
        flat[path] = v
    return _nested(flat)


def param_specs(cfg: Dict) -> Dict:
    """PartitionSpec axes per leaf: the embedding's rows over ``embed``, the
    rest replicated (no cell runs this model on a mesh)."""
    return _nested({path: ("embed", None) if path == "embed" else ()
                    for path in _leaf_shapes(cfg)})


def state_bytes(cfg: Dict, training: bool) -> int:
    n = sum(math.prod(shape) for shape, _ in _leaf_shapes(cfg).values())
    return 4 * n * (2 if training else 1)


# -- plain reference -------------------------------------------------------------


def knobs(variant: str) -> Dict[str, bool]:
    """The planted faults as switches of the one f32 (or bf16) reference,
    so that a calibration compiles it twice and not once a fault: each is
    True where the reference is sound."""
    return {"segment_reset": variant != "no_segment_reset",
            "window": variant != "window_off", "yarn": variant != "yarn_off",
            "renormalise_over_picked": variant != "absent_experts_renormalised"}


def inv_freq(z: Dict, kind: str, yarn: bool = True):
    """``(w [d/2] float64, f)`` of a layer kind, from ``rope_parameters``
    (the default rope's where ``yarn`` is False)."""
    p, d = z["rope"][kind], z["dim"]
    base = float(p["rope_theta"]) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if p["rope_type"] == "default" or not yarn:
        return base, 1.0
    if p["rope_type"] != "yarn":
        raise ValueError(f"rope_type {p['rope_type']!r}")

    def corr(n):
        return (d * math.log(p["original_max_position_embeddings"] / (2 * math.pi * n))
                / (2 * math.log(p["rope_theta"])))

    low = max(math.floor(corr(p["beta_fast"])), 0)
    high = min(math.ceil(corr(p["beta_slow"])), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return base / p["factor"] * ramp + base * (1.0 - ramp), p["attention_factor"]


def _positions(seg):
    """``pos_t = #{j < t: seg_j == seg_t}``, a block of tokens at a time."""
    import jax
    import jax.numpy as jnp

    n = seg.shape[0]
    block = REF_QUERY_BLOCK if n % REF_QUERY_BLOCK == 0 else n
    t = jnp.arange(n)

    def rows(args):
        seg_b, t_b = args
        return jnp.sum((seg[None] == seg_b[:, None]) & (t[None] < t_b[:, None]),
                       axis=1, dtype=jnp.int32)

    return jax.lax.map(rows, (seg.reshape(-1, block), t.reshape(-1, block))).reshape(n)


def _attention(p, x, seg, pos, z, kind, on):
    import jax
    import jax.numpy as jnp

    n, d = x.shape[0], z["dim"]
    g = z["heads"] // z["kv_heads"]
    (w, f), (w0, f0) = inv_freq(z, kind), inv_freq(z, kind, yarn=False)
    w = jnp.where(on["yarn"], jnp.asarray(w, jnp.float32), jnp.asarray(w0, jnp.float32))
    f = jnp.where(on["yarn"], jnp.float32(f), jnp.float32(f0))
    angle = pos[:, None].astype(jnp.float32) * w
    c = (jnp.cos(angle) * f).astype(x.dtype)[:, None]
    s = (jnp.sin(angle) * f).astype(x.dtype)[:, None]

    def rot(y):                                            # [T, heads, d]
        y1, y2 = y[..., :d // 2], y[..., d // 2:]
        return jnp.concatenate([y1 * c - y2 * s, y2 * c + y1 * s], axis=-1)

    window = z["window"] if kind == WINDOW else 0
    block = REF_QUERY_BLOCK if n % REF_QUERY_BLOCK == 0 else n
    t = jnp.arange(n)

    @jax.checkpoint
    def kv_head(wq, wk, wv, wo):
        q = rot((x @ wq).reshape(n, g, d))
        k = rot((x @ wk).reshape(n, 1, d))[:, 0]
        v = x @ wv

        @jax.checkpoint
        def rows(args):
            q_b, seg_b, t_b = args
            scores = jnp.einsum("qhd,kd->hqk", q_b, k) / jnp.sqrt(jnp.asarray(d, x.dtype))
            keep = (seg_b[:, None] == seg[None, :]) & (t[None, :] <= t_b[:, None])
            if window:
                keep = keep & ((t_b[:, None] - t[None, :] < window) | jnp.logical_not(on["window"]))
            a = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,kd->qhd", a, v)

        o = jax.lax.map(rows, (q.reshape(-1, block, g, d), seg.reshape(-1, block),
                               t.reshape(-1, block)))
        return o.reshape(n, g * d) @ wo

    out = 0.0
    for h in range(z["kv_heads"]):
        q_cols, kv_cols = slice(h * g * d, (h + 1) * g * d), slice(h * d, (h + 1) * d)
        out = out + kv_head(p["wq"][:, q_cols], p["wk"][:, kv_cols],
                            p["wv"][:, kv_cols], p["wo"][q_cols])
    return out


def _moe(p, x, z, on):
    import jax
    import jax.numpy as jnp

    s = jax.nn.softmax(x @ p["router"], axis=-1)
    _, picked = jax.lax.top_k(s, z["top_k"])
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], picked].set(True)
    held = jnp.arange(s.shape[1])
    held = (held >= z["first_held"]) & (held < z["first_held"] + z["held"])
    over = chosen & (held | on["renormalise_over_picked"])
    norm = jnp.sum(jnp.where(over, s, 0), axis=-1, keepdims=True)
    weight = jnp.where(chosen, s / jnp.where(norm == 0, 1, norm), 0)

    @jax.checkpoint
    def add_expert(y, e):
        one = {k: v[e] for k, v in p["experts"].items()}
        return y + weight[:, z["first_held"] + e, None] * _swiglu(one, x), None

    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(x), jnp.arange(z["held"]))
    return y


def reference_hidden(params: Dict, batch: Dict, cfg: Dict, on: Dict):
    """[T, hidden] after the final RMSNorm, of one packed sequence:
    ``batch['tokens']`` [T] (rows of ``params['embed']``), ``batch['segments']``
    [T]; ``on``: :func:`knobs`' switches (bools, or traced)."""
    import jax
    import jax.numpy as jnp

    z = sizes(cfg)
    seg = jnp.where(on["segment_reset"], batch["segments"], 0)
    pos = _positions(seg)

    def layer(kind, p, x):
        x = x + _attention(p["attn"], _rms(x, p["norm1"], z["eps"]), seg, pos, z,
                           kind, on)
        return x + _moe(p["ffn"], _rms(x, p["norm2"], z["eps"]), z, on)

    x = params["embed"][batch["tokens"]]
    for i, kind in enumerate(z["kinds"], 1):
        x = jax.checkpoint(layer, static_argnums=(0,))(kind, params[f"layer{i}"], x)
    return _rms(x, params["final_norm"], z["eps"])


def reference_logits(params: Dict, batch: Dict, cfg: Dict, variant: str = "f32"):
    """[T, vocab] logits of one packed sequence (whole: for small sizes)."""
    return reference_hidden(params, batch, cfg, knobs(variant)) @ params["head"]


def reference_loss(params: Dict, batch: Dict, cfg: Dict, variant: str = "f32",
                   on=None):
    """Mean over the target positions of all the batch's sequences of the
    softmax cross-entropy of the next token of the same document.  ``batch``:
    ``tokens`` [B, T] (rows of the compacted table), ``segments`` [B, T],
    ``targets`` [B, T] (ids in the slice, so columns of the head).  The
    precision is bfloat16 for ``bf16``; the switches ``on`` (default: the
    variant's :func:`knobs`)."""
    import jax
    import jax.numpy as jnp

    on = knobs(variant) if on is None else on
    dtype = jnp.bfloat16 if variant == "bf16" else jnp.float32
    p = jax.tree_util.tree_map(lambda x: x.astype(dtype), params)
    total, count = 0.0, 0.0
    for b in range(batch["tokens"].shape[0]):
        seg = batch["segments"][b]
        y = reference_hidden(p, {"tokens": batch["tokens"][b], "segments": seg},
                             cfg, on)
        has = jnp.concatenate([seg[1:] == seg[:-1], jnp.zeros((1,), bool)])
        n = seg.shape[0]
        block = REF_ROW_BLOCK if n % REF_ROW_BLOCK == 0 else n

        @jax.checkpoint
        def rows(args):
            y_b, t_b, has_b = args
            logp = jax.nn.log_softmax(y_b @ p["head"], axis=-1)
            picked = jnp.take_along_axis(logp, t_b[:, None], axis=-1)[:, 0]
            return jnp.sum(jnp.where(has_b, picked, 0).astype(jnp.float32))

        total = total - jnp.sum(jax.lax.map(rows, (
            y.reshape(-1, block, y.shape[-1]), batch["targets"][b].reshape(-1, block),
            has.reshape(-1, block))))
        count = count + jnp.sum(has)
    return total / count


@functools.lru_cache(maxsize=None)
def _reference_step(cfg_items, precision: str):
    """One jitted reference step a precision (``f32`` or ``bf16``), the
    faults' switches its argument: a calibration reads several seeds and
    all the faults in one process, and compiles two steps."""
    import json

    import jax
    import jax.numpy as jnp

    cfg = json.loads(cfg_items)
    lr, eps = float(cfg["learning_rate"]), float(cfg["adagrad_eps"])

    def step(params, accum, batch, on):
        loss, g = jax.value_and_grad(reference_loss)(params, batch, cfg, precision, on)
        g = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), g)
        accum = jax.tree_util.tree_map(lambda a, x: a + x * x, accum, g)
        params = jax.tree_util.tree_map(
            lambda p, x, a: p - lr * x * jax.lax.rsqrt(a + eps), params, g, accum)
        gnorm = jax.tree_util.tree_map(lambda x: jnp.sqrt(jnp.sum(x * x)), g)
        return params, accum, loss, gnorm

    return jax.jit(step, donate_argnums=(0, 1))


def reference_steps(cfg: Dict, params0, batches: Sequence[Dict],
                    variant: str = "f32") -> Dict:
    """Forward, loss, ``jax.grad`` and dense Adagrad over ``batches`` from
    ``params0()``, as ``kimi_linear.reference_steps`` (which says why the
    initial weights are made twice).  Returns per-step losses, the first
    gradient's norm per leaf and the change's norm per leaf, as
    ``benchmarks/harness/reference.compare`` takes them."""
    import json

    import jax
    import jax.numpy as jnp

    from benchmarks.harness.reference import flat_leaves

    if variant not in VARIANTS:
        raise ValueError(f"unknown reference variant {variant!r}")
    step = _reference_step(json.dumps(cfg, sort_keys=True),
                           "bf16" if variant == "bf16" else "f32")
    on = {k: jnp.asarray(v) for k, v in knobs(variant).items()}
    norm_of_change = jax.jit(lambda a, b: jax.tree_util.tree_map(
        lambda x, y: jnp.sqrt(jnp.sum((x - y) ** 2)), a, b))
    with jax.default_matmul_precision("highest"):
        params = params0()
        accum = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, first = [], None
        for i, b in enumerate(batches):
            params, accum, loss, gnorm = step(
                params, accum, {k: jnp.asarray(v) for k, v in b.items()}, on)
            losses.append(float(loss))
            if i == 0:
                first = jax.device_get(gnorm)
        del accum
        change = jax.device_get(norm_of_change(params, params0()))
    return {"loss": losses,
            "grad_norm": {k: float(v) for k, v in flat_leaves(first).items()},
            "change_norm": {k: float(v) for k, v in flat_leaves(change).items()}}


# -- work the algorithm needs, from shapes and counts ----------------------------


def window_pairs_of(length: int, window: int) -> int:
    """(query, key) pairs of one document of ``length`` tokens under a
    window of ``window``: ``L (L+1) / 2`` up to the window, then ``W`` a
    token."""
    if length <= window:
        return length * (length + 1) // 2
    return window * (window + 1) // 2 + (length - window) * window


def windowed_pairs(cfg: Dict, spec: Dict) -> float:
    """The exact (query, key) pairs of one window layer a step, over the
    traffic's replayed sequences (``spec``: a traffic file's ``rows``)."""
    from benchmarks.harness import datagen, seqgen

    z = sizes(cfg)
    n = int(spec["distinct_batches"]) * z["sequences"]
    seqs = seqgen.packed_sequences(
        np.random.default_rng(datagen.seed_words(spec["data_seed"])), n, spec,
        tokens=z["tokens"], vocab=z["vocab"])
    total = 0
    for seg in seqs["segments"]:
        runs = np.diff(np.flatnonzero(np.concatenate([[True], seg[1:] != seg[:-1], [True]])))
        total += sum(window_pairs_of(int(L), z["window"]) for L in runs)
    return total / int(spec["distinct_batches"])


def gqa_core_cost(cfg: Dict, pairs: float) -> Dict[str, float]:
    """FLOPs and HBM bytes of one layer's attention core (scores, softmax,
    the value sum; not the projections), forward and backward, at ``pairs``
    (query, key) pairs a step: ``q k`` and ``a v`` are ``2 d`` each a pair
    and query head forward, twice that backward; q, k, v read and o written
    forward, the four and ``do`` read and the three gradients written
    backward."""
    z = sizes(cfg)
    h, hk, d = z["heads"], z["kv_heads"], z["dim"]
    tokens = z["tokens"] * z["sequences"]
    return {"flops": 3.0 * pairs * h * 4 * d,
            "bytes": 4.0 * tokens * d * (6 * h + 6 * hk)}


def train_step_cost(cfg: Dict, distinct: float, held_assignments: float,
                    attended_pairs: float) -> Dict[str, float]:
    """FLOPs and HBM bytes one training step has to spend (forward and
    backward, nothing made twice), whole and by part.  ``distinct``: distinct
    token ids a step; ``held_assignments``: assignments to held experts a step,
    summed over the layers (counted by the program's router);
    ``attended_pairs``: (query, key) pairs of one document a step (from the
    generator's document lengths): a full layer's.  The runner passes no
    windowed count, so a window layer is counted at ``min(attended_pairs,
    tokens * W)``, an upper bound (9% over on ``train-packed16k-repo``, under
    1% of the step's FLOPs).  ``gqa_window_roofline`` takes the exact count
    from the traffic file's ``counted`` (:func:`windowed_pairs` made it) times
    ``gqa_window_flops_per_pair``."""
    z = sizes(cfg)
    shapes = _leaf_shapes(cfg)
    tokens = z["tokens"] * z["sequences"]
    d, f = z["hidden"], z["expert_width"]
    n_matmul = sum(math.prod(s) for p, (s, rule) in shapes.items()
                   if isinstance(rule, int) and ".experts." not in p)
    n_dense = sum(math.prod(s) for p, (s, _) in shapes.items() if p != "embed")
    n_window = z["kinds"].count(WINDOW)
    n_full = len(z["kinds"]) - n_window
    full = gqa_core_cost(cfg, attended_pairs)
    win = gqa_core_cost(cfg, min(attended_pairs, tokens * z["window"]))
    experts_flops = 3 * 2 * held_assignments * 3 * d * f
    experts_bytes = (4 * len(z["kinds"]) * z["held"] * 3 * d * f * 3
                     + 4 * held_assignments * 4 * d)
    row = 4 * d
    gather, apply = distinct * row, 4 * distinct * row
    flops = (3 * 2 * tokens * n_matmul + n_window * win["flops"]
             + n_full * full["flops"] + experts_flops)
    hbm = 20 * n_dense + gather + apply + 2 * 4 * tokens * d * (len(z["kinds"]) + 1)
    return {"flops": float(flops), "gather_bytes": float(gather),
            "apply_bytes": float(apply), "hbm_bytes": float(hbm),
            "moe_experts_flops": float(experts_flops),
            "moe_experts_bytes": float(experts_bytes),
            "gqa_full_flops": float(n_full * full["flops"]),
            "gqa_full_bytes": float(n_full * full["bytes"]),
            "gqa_window_flops_per_pair": float(n_window * gqa_core_cost(cfg, 1.0)["flops"]),
            "gqa_window_bytes": float(n_window * win["bytes"])}


# -- the program under test ------------------------------------------------------


def spec_of(cfg: Dict):
    from lightctr_tpu.models import mellum

    z = sizes(cfg)
    window, full = z["rope"][WINDOW], z["rope"][FULL]
    if window["rope_theta"] != full["rope_theta"]:
        raise ValueError("the program takes one rope_theta for both kinds")
    return mellum.Spec(
        vocab=z["vocab"], hidden=z["hidden"], layers=z["kinds"], heads=z["heads"],
        kv_heads=z["kv_heads"], head_dim=z["dim"], window=z["window"],
        rope_theta=float(window["rope_theta"]),
        yarn=(float(full["factor"]), int(full["original_max_position_embeddings"]),
              float(full["beta_fast"]), float(full["beta_slow"]),
              float(full["attention_factor"])),
        expert_width=z["expert_width"], n_experts=z["n_experts"], top_k=z["top_k"],
        held=(z["first_held"], z["held"]), eps=z["eps"],
        block=cfg["gqa_query_block"], tile=cfg["moe_tile_rows"])


def build_trainer(cfg: Dict, params: Dict, mesh=None, shardings=None):
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.models import mellum
    from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer

    return SparseTableCTRTrainer(
        params, mellum.make_logits(spec_of(cfg)),
        TrainConfig(learning_rate=cfg["learning_rate"],
                    lambda_l2=cfg["lambda_l2"], loss="softmax_xent"),
        sparse_tables={"embed": ["tokens"]},
        mesh=mesh, param_shardings=shardings,
    )


def aot_trainer(cfg: Dict):
    """For ``tools/aot_step.py``: a trainer built on toy weights whose step is
    the real sizes' (``kimi_linear.aot_trainer``'s way).  The toy keeps the
    real row width: the trainer fixes a table's layout (a fused store or
    not) and its apply's row shape from the weights it is built on."""
    import jax

    from lightctr_tpu.models import mellum

    toy = dict(cfg, dim=8, vocab=64, moe_intermediate_size=8, num_experts=1)
    trainer = build_trainer(toy, init_params(toy, jax.random.PRNGKey(0)))
    trainer.logits_fn = mellum.make_logits(spec_of(cfg))
    return trainer

