"""Kimi Linear (arXiv:2510.26692; huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct):
the benchmark's own initialiser, the plain float32 reference with its loss,
gradient and dense Adagrad, the work counts, and the adapter that builds the
program under test.

Block, every layer: ``h = x + Mixer(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``;
a final RMSNorm, then ``logits = y W_head`` over the vocabulary slice.

KDA (H heads, d_k = d_v = d; ``conv`` a depthwise causal convolution of 4 taps
that stops at a document's first token):

    q = l2norm(silu(conv(x Wq)))   k = l2norm(silu(conv(x Wk)))   v = silu(conv(x Wv))
    g = -exp(A_log[h]) softplus((x Wf_down) Wf_up + dt_bias)         alpha = exp(g), per channel
    beta = sigmoid(x Wb)
    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T     S = 0 at a document's first token
    o_t = S_t^T q_t / sqrt(d);   Mixer(x)_t = (RMSNorm_head(o_t) sigmoid((x Wg_down) Wg_up)) Wo

MLA without positions (the ``qk_rope_head_dim`` channels carried, not rotated):

    q = x Wq;  [c | k_pe] = x Wkva;  [k_nope | v] = RMSNorm(c) Wkvb;  k = [k_nope | k_pe (all heads)]
    a = softmax(q k^T / sqrt(d_nope + d_pe) + causal mask inside the token's document);  Mixer(x) = concat_h(a v) Wo

FFN: layer 1 a dense SwiGLU ``Wdown(silu(x Wgate) * x Wup)``; after it

    s = sigmoid(x Wr);  E(t) = top-k of (s + b);  w_e = scaling s_e / sum_{e' in E(t)} s_e'
    FFN(x) = Shared(x) + sum_{e in E(t), e held here} w_e Expert_e(x)

Loss: the mean over the target positions of the softmax cross-entropy of token
t+1 under the slice's logits; a document's last token and the sequence's last
token have no target.

Only ``build_trainer`` / ``feed_layout`` / ``spec_of`` import the program.  The
reference is the equations above as they stand: the recurrence a token at a
time (in runs of 64 tokens made again in the backward pass, so that the states
of 8,192 steps need not be kept), the softmax over whole rows of scores a block
of queries at a time, the expert sum a loop over the held experts each over all
tokens, no chunked form, no kernel; and, so that it fits beside 9 GB of
weights, state and gradients, a quarter of a mixer's heads and 2,048 rows of
the dense FFN at a time.  It reads the generator's columns (token
ids and document numbers) and weights from ``init_params``.

Sizes: ``hidden``, ``dim`` (KDA's head width), ``vocab`` (rows of the slice) and
``batch`` (tokens a step) are the harness's size keys, which its CPU tests cut
(``tests/benchmark/helpers.TINY_SIZES``); the shipped file states them equal to
the catalog's ``hidden_size``, ``linear_attn_config.head_dim`` and
``vocab_size``.  Every other size is read under the catalog's own key.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence

#: table leaf -> the batch field whose ids index it (reference's view)
TABLES = {"embed": "tokens"}
VARIANTS = ("f32", "bf16", "no_segment_reset", "absent_experts_renormalised",
            "half_targets")
REF_RUN = 64          # tokens of the recurrence between two kept states
REF_QUERY_BLOCK = 256
REF_HEAD_GROUPS = 4   # heads of a mixer that are worked at once: a quarter
REF_ROW_BLOCK = 2048  # rows of the dense FFN at once


def sizes(cfg: Dict) -> Dict:
    lin = cfg["linear_attn_config"]
    n = cfg["num_hidden_layers"]
    kinds = tuple("kda" if i in lin["kda_layers"] else "mla"
                  for i in range(1, n + 1))
    if any(k == "mla" and i not in lin["full_attn_layers"]
           for i, k in enumerate(kinds, 1)):
        raise ValueError("a layer is in neither kda_layers nor full_attn_layers")
    return {
        "hidden": cfg["hidden"], "vocab": cfg["vocab"], "kinds": kinds,
        "first_dense": cfg["first_k_dense_replace"],
        "heads": lin["num_heads"], "dim": cfg["dim"],
        "conv": lin["short_conv_kernel_size"], "gate_rank": cfg["kda_gate_rank"],
        "mla_heads": cfg["num_attention_heads"], "kv_rank": cfg["kv_lora_rank"],
        "d_nope": cfg["qk_nope_head_dim"], "d_pe": cfg["qk_rope_head_dim"],
        "d_v": cfg["v_head_dim"], "dense_width": cfg["intermediate_size"],
        "expert_width": cfg["moe_intermediate_size"],
        "n_experts": cfg["experts_routed_over"], "held": cfg["num_experts"],
        "first_held": cfg["first_expert_held"],
        "top_k": cfg["num_experts_per_token"],
        "scaling": cfg["routed_scaling_factor"], "eps": cfg["rms_norm_eps"],
        "tokens": cfg["batch"] // cfg["sequences"], "sequences": cfg["sequences"],
    }


# -- weights --------------------------------------------------------------------


def _leaf_shapes(cfg: Dict) -> Dict:
    """``{leaf path: (shape, fan_in or a rule's name)}`` in the program's tree."""
    z = sizes(cfg)
    d, hd = z["hidden"], z["heads"] * z["dim"]
    out = {"embed": ((z["vocab"], d), "table"), "final_norm": ((d,), "one"),
           "head": ((d, z["vocab"]), d)}

    def swiglu(prefix, width, lead=()):
        out[prefix + ".w_gate"] = (lead + (d, width), d)
        out[prefix + ".w_up"] = (lead + (d, width), d)
        out[prefix + ".w_down"] = (lead + (width, d), width)

    for i, kind in enumerate(z["kinds"], 1):
        p = f"layer{i}."
        out[p + "norm1"] = out[p + "norm2"] = ((d,), "one")
        m = p + "mixer."
        if kind == "kda":
            for w in ("wq", "wk", "wv"):
                out[m + w] = ((d, hd), d)
            for w in ("conv_q", "conv_k", "conv_v"):
                out[m + w] = ((hd, z["conv"]), z["conv"])
            out[m + "f_down"] = out[m + "g_down"] = ((d, z["gate_rank"]), d)
            out[m + "f_up"] = out[m + "g_up"] = ((z["gate_rank"], hd), z["gate_rank"])
            out[m + "a_log"] = ((z["heads"],), "a_log")
            out[m + "dt_bias"] = ((hd,), "dt_bias")
            out[m + "wb"] = ((d, z["heads"]), d)
            out[m + "o_norm"] = ((z["dim"],), "one")
            out[m + "wo"] = ((hd, d), hd)
        else:
            h = z["mla_heads"]
            out[m + "wq"] = ((d, h * (z["d_nope"] + z["d_pe"])), d)
            out[m + "wkva"] = ((d, z["kv_rank"] + z["d_pe"]), d)
            out[m + "kv_norm"] = ((z["kv_rank"],), "one")
            out[m + "wkvb"] = ((z["kv_rank"], h * (z["d_nope"] + z["d_v"])), z["kv_rank"])
            out[m + "wo"] = ((h * z["d_v"], d), h * z["d_v"])
        f = p + "ffn"
        if i <= z["first_dense"]:
            swiglu(f, z["dense_width"])
        else:
            out[f + ".router"] = ((d, z["n_experts"]), d)
            out[f + ".router_bias"] = ((z["n_experts"],), "zero")
            swiglu(f + ".shared", z["expert_width"])
            swiglu(f + ".experts", z["expert_width"], lead=(z["held"],))
    return out


def _nested(flat: Dict) -> Dict:
    out: Dict = {}
    for path, v in flat.items():
        node = out
        *parents, last = path.split(".")
        for k in parents:
            node = node.setdefault(k, {})
        node[last] = v
    return out


def init_params(cfg: Dict, key) -> Dict:
    """Matrices ~ N(0, 1 / fan_in) — those that write into the residual stream
    (``wo``, ``w_down``) over ``2 * residual_init_layers`` besides, the scaled
    initialisation of deep residual stacks (GPT-2, Megatron): without it the
    mixers' outputs, which at random weights are near a document's average
    for every token, dominate the router's input and every token picks the
    same experts —, norm weights one, the selection bias zero, the embedding
    N(0, 1), all by index hash; KDA's decay as the public
    implementation draws it (``A_log = log U(1, 16)``, ``dt_bias`` the inverse
    softplus of a step log-uniform in [1e-3, 1e-1]).  float32.  They come
    from ``cfg['weight_seed']``; ``key``, the run's ``--seed``, is not read:
    the weights decide the routing, and the step's time follows the held
    experts' load (3.2 us a held assignment; the rate spread 0.0043 over
    nine draws, PERF.md section 6).  ``--seed`` then picks the replay's
    order alone."""
    import math

    import jax
    import jax.numpy as jnp

    from benchmarks.harness.common import prng_key
    from benchmarks.harness.initializers import hashed_normal

    key = prng_key(cfg["weight_seed"])
    flat = {}
    for n, (path, (shape, rule)) in enumerate(sorted(_leaf_shapes(cfg).items())):
        k = jax.random.fold_in(key, n)
        if rule == "table" or isinstance(rule, int):
            # (jax.random.normal takes seconds a GB on the chip)
            v = hashed_normal(k, math.prod(shape[:-1]), shape[-1]).reshape(shape)
            if rule != "table":
                v = v / jnp.sqrt(float(rule))
            if path.endswith((".wo", "w_down")):
                # what a block adds to the residual stream starts small
                v = v / jnp.sqrt(2.0 * cfg["residual_init_layers"])
        elif rule == "one":
            v = jnp.ones(shape, jnp.float32)
        elif rule == "zero":
            v = jnp.zeros(shape, jnp.float32)
        elif rule == "a_log":
            v = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif rule == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))
            v = dt + jnp.log(-jnp.expm1(-dt))
        else:
            raise ValueError(f"no rule {rule!r}")
        flat[path] = v
    return _nested(flat)


def param_specs(cfg: Dict) -> Dict:
    """PartitionSpec axes per leaf: the embedding's rows over ``embed``, the
    rest replicated (no cell runs this model on a mesh)."""
    return _nested({path: ("embed", None) if path == "embed" else ()
                    for path in _leaf_shapes(cfg)})


def state_bytes(cfg: Dict, training: bool) -> int:
    import math

    n = sum(math.prod(shape) for shape, _ in _leaf_shapes(cfg).values())
    return 4 * n * (2 if training else 1)


# -- plain reference -------------------------------------------------------------


def _rms(x, w, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * w


def _l2(x):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _conv(x, w, seg):
    """``y_t = sum_j w[:, K-1-j] x_{t-j}`` over the taps in ``t``'s document."""
    import jax.numpy as jnp

    taps = w.shape[1]
    y = jnp.zeros_like(x)
    for j in range(taps):
        shifted = jnp.concatenate([jnp.zeros_like(x[:j]), x[:x.shape[0] - j]])
        shifted_seg = jnp.concatenate(
            [jnp.full((j,), -1, seg.dtype), seg[:seg.shape[0] - j]])
        y = y + jnp.where((shifted_seg == seg)[:, None], shifted, 0) * w[:, taps - 1 - j]
    return y


def _swiglu(p, x):
    import jax

    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def _recurrence(q, k, v, g, beta, seg):
    """The delta rule a token at a time: [T, H, d] each, ``beta`` [T, H]."""
    import jax
    import jax.numpy as jnp

    n_tok, heads, d = q.shape
    run = REF_RUN if n_tok % REF_RUN == 0 else n_tok
    first = seg != jnp.concatenate([jnp.full((1,), -1, seg.dtype), seg[:-1]])

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t, first_t = x
        state = jnp.where(first_t, jnp.zeros_like(state), state)
        state = jnp.exp(g_t)[:, :, None] * state
        seen = jnp.einsum("hk,hkv->hv", k_t, state)
        state = state + (b_t[:, None] * k_t)[:, :, None] * (v_t - seen)[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state)

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = tuple(a.reshape((n_tok // run, run) + a.shape[1:])
               for a in (q, k, v, g, beta, first))
    _, o = jax.lax.scan(tokens, jnp.zeros((heads, d, v.shape[-1]), q.dtype), xs)
    return o.reshape(n_tok, heads, -1) / jnp.sqrt(jnp.asarray(d, q.dtype))


def _by_head_groups(fn, p, by_columns, by_rows, heads):
    """``sum_g fn(weights of head group g)``: heads do not meet before the
    output projection, so a mixer is the sum over groups of heads of what each
    group gives.  One group at a time, made again in the backward pass, so
    that the activations of all heads need not be kept at once."""
    import jax
    import jax.numpy as jnp

    groups = REF_HEAD_GROUPS if heads % REF_HEAD_GROUPS == 0 else 1

    def split(w, axis):
        shape = w.shape[:axis] + (groups, w.shape[axis] // groups) + w.shape[axis + 1:]
        return jnp.moveaxis(w.reshape(shape), axis, 0)

    weights = {k: split(p[k], p[k].ndim - 1) for k in by_columns}
    weights.update({k: split(p[k], 0) for k in by_rows})
    return jnp.sum(jax.lax.map(jax.checkpoint(fn), weights), axis=0)


def _kda(p, x, seg, z):
    import jax
    import jax.numpy as jnp

    n_tok = x.shape[0]

    def split(y):
        return y.reshape(n_tok, -1, z["dim"])

    def group(w):
        q = _l2(split(jax.nn.silu(_conv(x @ w["wq"], w["conv_q"], seg))))
        k = _l2(split(jax.nn.silu(_conv(x @ w["wk"], w["conv_k"], seg))))
        v = split(jax.nn.silu(_conv(x @ w["wv"], w["conv_v"], seg)))
        g = -jnp.exp(w["a_log"])[None, :, None] * split(
            jax.nn.softplus((x @ p["f_down"]) @ w["f_up"] + w["dt_bias"]))
        beta = jax.nn.sigmoid(x @ w["wb"])
        o = _recurrence(q, k, v, g.astype(q.dtype), beta, seg)
        gate = jax.nn.sigmoid(split((x @ p["g_down"]) @ w["g_up"]))
        return (_rms(o, p["o_norm"], z["eps"]) * gate).reshape(n_tok, -1) @ w["wo"]

    return _by_head_groups(
        group, p, ("wq", "wk", "wv", "f_up", "dt_bias", "a_log", "wb", "g_up"),
        ("conv_q", "conv_k", "conv_v", "wo"), z["heads"])


def _mla(p, x, seg, z):
    import jax
    import jax.numpy as jnp

    n_tok, rank = x.shape[0], z["kv_rank"]
    kva = x @ p["wkva"]
    latent = _rms(kva[:, :rank], p["kv_norm"], z["eps"])
    block = REF_QUERY_BLOCK if n_tok % REF_QUERY_BLOCK == 0 else n_tok
    pos = jnp.arange(n_tok)

    def group(w):
        q = (x @ w["wq"]).reshape(n_tok, -1, z["d_nope"] + z["d_pe"])
        kv = (latent @ w["wkvb"]).reshape(n_tok, q.shape[1], -1)
        k = jnp.concatenate(
            [kv[..., :z["d_nope"]],
             jnp.broadcast_to(kva[:, None, rank:], (n_tok, q.shape[1], z["d_pe"]))], -1)
        v = kv[..., z["d_nope"]:]
        scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], x.dtype))

        @jax.checkpoint
        def rows(args):
            q_b, seg_b, pos_b = args
            scores = jnp.einsum("qhd,khd->hqk", q_b, k) * scale
            keep = (seg_b[:, None] == seg[None, :]) & (pos[None, :] <= pos_b[:, None])
            a = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", a, v)

        o = jax.lax.map(rows, tuple(
            a.reshape((n_tok // block, block) + a.shape[1:]) for a in (q, seg, pos)))
        return o.reshape(n_tok, -1) @ w["wo"]

    return _by_head_groups(group, p, ("wq", "wkvb"), ("wo",), z["mla_heads"])


def _dense_ffn(p, x):
    """The dense SwiGLU a block of rows at a time (its 9,216-wide activations
    are made again in the backward pass)."""
    import jax

    block = REF_ROW_BLOCK if x.shape[0] % REF_ROW_BLOCK == 0 else x.shape[0]
    y = jax.lax.map(jax.checkpoint(lambda rows: _swiglu(p, rows)),
                    x.reshape(-1, block, x.shape[-1]))
    return y.reshape(x.shape)


def _moe(p, x, z, variant):
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(x @ p["router"])
    _, picked = jax.lax.top_k(s + p["router_bias"], z["top_k"])
    chosen = jnp.zeros(s.shape, bool).at[
        jnp.arange(s.shape[0])[:, None], picked].set(True)
    held = jnp.arange(s.shape[1])
    held = (held >= z["first_held"]) & (held < z["first_held"] + z["held"])
    over = chosen & held if variant == "absent_experts_renormalised" else chosen
    norm = jnp.sum(jnp.where(over, s, 0), axis=-1, keepdims=True)
    weight = jnp.where(chosen, z["scaling"] * s / jnp.where(norm == 0, 1, norm), 0)

    @jax.checkpoint
    def add_expert(y, e):
        one = {k: v[e] for k, v in p["experts"].items()}
        return y + weight[:, z["first_held"] + e, None] * _swiglu(one, x), None

    y, _ = jax.lax.scan(add_expert, _swiglu(p["shared"], x),
                        jnp.arange(z["held"]))
    return y


def reference_logits(params: Dict, batch: Dict, cfg: Dict, variant: str = "f32"):
    """[T, vocab] logits of one packed sequence: ``batch['tokens']`` [T] (rows
    of ``params['embed']``), ``batch['segments']`` [T]."""
    import jax
    import jax.numpy as jnp

    z = sizes(cfg)
    seg = batch["segments"]
    if variant == "no_segment_reset":
        seg = jnp.zeros_like(seg)

    def layer(i, kind, p, x):
        h = _rms(x, p["norm1"], z["eps"])
        x = x + (_kda if kind == "kda" else _mla)(p["mixer"], h, seg, z)
        h = _rms(x, p["norm2"], z["eps"])
        if i <= z["first_dense"]:
            return x + _dense_ffn(p["ffn"], h)
        return x + _moe(p["ffn"], h, z, variant)

    x = params["embed"][batch["tokens"]]
    for i, kind in enumerate(z["kinds"], 1):
        x = jax.checkpoint(layer, static_argnums=(0, 1))(
            i, kind, params[f"layer{i}"], x)
    return _rms(x, params["final_norm"], z["eps"]) @ params["head"]


def reference_loss(params: Dict, batch: Dict, cfg: Dict, variant: str = "f32"):
    """Mean over the target positions of all the batch's sequences of the
    softmax cross-entropy of the next token of the same document.  ``batch``:
    ``tokens`` [B, T] (rows of the compacted table), ``segments`` [B, T],
    ``targets`` [B, T] (ids in the slice, so columns of the head)."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.bfloat16 if variant == "bf16" else jnp.float32
    p = jax.tree_util.tree_map(lambda x: x.astype(dtype), params)
    total, count = 0.0, 0.0
    for b in range(batch["tokens"].shape[0]):
        seg = batch["segments"][b]
        z = reference_logits(p, {"tokens": batch["tokens"][b], "segments": seg},
                             cfg, variant)
        has = jnp.concatenate([seg[1:] == seg[:-1], jnp.zeros((1,), bool)])
        if variant == "half_targets":
            has = has & (jnp.arange(seg.shape[0]) < seg.shape[0] // 2)
        logp = jax.nn.log_softmax(z, axis=-1)
        picked = jnp.take_along_axis(logp, batch["targets"][b][:, None], axis=-1)[:, 0]
        total = total - jnp.sum(jnp.where(has, picked, 0).astype(jnp.float32))
        count = count + jnp.sum(has)
    return total / count


@functools.lru_cache(maxsize=None)
def _reference_step(cfg_items, variant: str):
    """One jitted reference step a variant (a calibration reads several seeds
    in one process)."""
    import json

    import jax
    import jax.numpy as jnp

    cfg = json.loads(cfg_items)
    lr, eps = float(cfg["learning_rate"]), float(cfg["adagrad_eps"])

    def step(params, accum, batch):
        loss, g = jax.value_and_grad(reference_loss)(params, batch, cfg, variant)
        g = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), g)
        accum = jax.tree_util.tree_map(lambda a, x: a + x * x, accum, g)
        params = jax.tree_util.tree_map(
            lambda p, x, a: p - lr * x * jax.lax.rsqrt(a + eps), params, g, accum)
        gnorm = jax.tree_util.tree_map(lambda x: jnp.sqrt(jnp.sum(x * x)), g)
        return params, accum, loss, gnorm

    return jax.jit(step, donate_argnums=(0, 1))


def reference_steps(cfg: Dict, params0, batches: Sequence[Dict],
                    variant: str = "f32") -> Dict:
    """Forward, loss, ``jax.grad`` and dense Adagrad (``accum += g*g; p -= lr*g
    *rsqrt(accum+eps)``) over ``batches`` from ``params0()`` (the table
    compacted to the touched rows: Adagrad leaves a row with zero gradient
    where it was).  ``params0`` makes the initial weights anew each time it is
    called — once for the steps, which give their buffers up, and once more
    for the change's norm: 3 GB are not kept beside the steps.  Returns
    per-step losses, the first gradient's norm per leaf and the change's norm
    per leaf, as ``benchmarks/harness/reference.compare`` takes them."""
    import json

    import jax
    import jax.numpy as jnp

    from benchmarks.harness.reference import flat_leaves

    if variant not in VARIANTS:
        raise ValueError(f"unknown reference variant {variant!r}")
    step = _reference_step(json.dumps(cfg, sort_keys=True), variant)
    norm_of_change = jax.jit(lambda a, b: jax.tree_util.tree_map(
        lambda x, y: jnp.sqrt(jnp.sum((x - y) ** 2)), a, b))
    with jax.default_matmul_precision("highest"):
        params = params0()
        accum = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, first = [], None
        for i, b in enumerate(batches):
            params, accum, loss, gnorm = step(
                params, accum, {k: jnp.asarray(v) for k, v in b.items()})
            losses.append(float(loss))
            if i == 0:
                first = jax.device_get(gnorm)
        del accum
        change = jax.device_get(norm_of_change(params, params0()))
    return {"loss": losses,
            "grad_norm": {k: float(v) for k, v in flat_leaves(first).items()},
            "change_norm": {k: float(v) for k, v in flat_leaves(change).items()}}


# -- work the algorithm needs, from shapes and counts ----------------------------


def train_step_cost(cfg: Dict, distinct: float, held_assignments: float,
                    attended_pairs: float) -> Dict[str, float]:
    """FLOPs and HBM bytes one training step has to spend (forward and
    backward, nothing made twice), whole and by part.  ``distinct``: distinct
    token ids a step; ``held_assignments``: assignments to held experts a step,
    summed over the routed layers (counted by the program's router);
    ``attended_pairs``: (query, key) pairs of one document a step (from the
    generator's document lengths).  A product of ``n`` weights costs 2 n a
    token forward and twice that backward."""
    import math

    z = sizes(cfg)
    shapes = _leaf_shapes(cfg)
    tokens = z["tokens"] * z["sequences"]
    d, f = z["hidden"], z["expert_width"]
    routed = 3 * d * f                                        # one expert's weights
    n_matmul = sum(
        math.prod(shape) for path, (shape, rule) in shapes.items()
        if isinstance(rule, int) and ".experts." not in path and "conv_" not in path)
    n_dense = sum(math.prod(s) for p, (s, _) in shapes.items() if p != "embed")
    kda_layers = sum(k == "kda" for k in z["kinds"])
    mla_layers = len(z["kinds"]) - kda_layers
    c, dk, heads = 64, z["dim"], z["heads"]
    # a chunk and head, forward: A and P over the pairs below the diagonal,
    # the unit-triangular solve of d_k + d_v columns, P N, and the three
    # C x d x d products with the state
    scan_fwd = (tokens / c) * heads * (
        2 * c * c * dk + c * c * 2 * dk + c * c * dk + 6 * c * dk * dk)
    scan_flops = 3 * scan_fwd * kda_layers
    # q, k, v, g and beta read, o written; backward reads them and do, writes
    # their five gradients
    scan_bytes = kda_layers * 4 * tokens * heads * (3 * (4 * dk + 1) + 2 * dk)
    attn_flops = 3 * mla_layers * attended_pairs * z["mla_heads"] * 2 * (
        z["d_nope"] + z["d_pe"] + z["d_v"])
    experts_flops = 3 * 2 * held_assignments * routed
    # every held expert's weights read forward and backward, their gradient
    # written; an assignment's row read and written at both ends, both passes
    experts_bytes = (4 * (len(z["kinds"]) - z["first_dense"]) * z["held"] * routed * 3
                     + 4 * held_assignments * 4 * d)
    row = 4 * d
    gather, apply = distinct * row, 4 * distinct * row
    flops = (3 * 2 * tokens * n_matmul + scan_flops + attn_flops + experts_flops)
    # a dense weight: read forward, read backward, and Adagrad's read of the
    # accumulator and write of both (the gradient used as it is made)
    hbm = 20 * n_dense + gather + apply + 2 * 4 * tokens * d * (len(z["kinds"]) + 1)
    return {"flops": float(flops), "gather_bytes": float(gather),
            "apply_bytes": float(apply), "hbm_bytes": float(hbm),
            "kda_scan_flops": float(scan_flops), "kda_scan_bytes": float(scan_bytes),
            "moe_experts_flops": float(experts_flops),
            "moe_experts_bytes": float(experts_bytes),
            "mla_attention_flops": float(attn_flops)}


# -- the program under test ------------------------------------------------------


def spec_of(cfg: Dict):
    from lightctr_tpu.models import kimi_linear

    z = sizes(cfg)
    if z["heads"] != z["mla_heads"]:
        raise ValueError("the program takes one head count for both mixers")
    return kimi_linear.Spec(
        vocab=z["vocab"], hidden=z["hidden"], mixers=z["kinds"],
        first_dense=z["first_dense"], heads=z["heads"], kda_head_dim=z["dim"],
        conv=z["conv"], gate_rank=z["gate_rank"], kv_rank=z["kv_rank"],
        d_nope=z["d_nope"], d_pe=z["d_pe"], d_v=z["d_v"],
        dense_width=z["dense_width"], expert_width=z["expert_width"],
        n_experts=z["n_experts"], top_k=z["top_k"],
        held=(z["first_held"], z["held"]), scaling=z["scaling"], eps=z["eps"],
        chunk=cfg["kda_chunk"], attn_block=cfg["mla_query_block"],
        tile=cfg["moe_tile_rows"], head_groups=cfg["mixer_head_groups"],
        ffn_block=min(cfg["dense_ffn_block_rows"], cfg["batch"]))


def feed_layout(cfg: Dict, batch: Dict) -> Dict:
    """The program's own host-side layout of a packed batch, with the
    generator's two columns beside it for the reference (the step reads
    neither)."""
    from lightctr_tpu.data import ingest

    return dict(ingest.sequence_batch(batch), fids=batch["fids"],
                fields=batch["fields"])


def build_trainer(cfg: Dict, params: Dict, mesh=None, shardings=None):
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.models import kimi_linear
    from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer

    return SparseTableCTRTrainer(
        params, kimi_linear.make_logits(spec_of(cfg)),
        TrainConfig(learning_rate=cfg["learning_rate"],
                    lambda_l2=cfg["lambda_l2"], loss="softmax_xent"),
        sparse_tables={"embed": ["tokens"]},
        mesh=mesh, param_shardings=shardings,
    )


def aot_trainer(cfg: Dict):
    """For ``tools/aot_step.py``: a trainer built on toy weights whose step is
    the real sizes' (its closure reads the model off ``logits_fn`` when the
    step is built, and shapes when it is lowered).  The toy keeps the real
    row width: the trainer fixes a table's layout (a fused store or not) and
    its apply's row shape from the weights it is built on."""
    import jax

    from lightctr_tpu.models import kimi_linear

    toy = dict(cfg, dim=8, vocab=64, moe_intermediate_size=8, num_experts=1,
               intermediate_size=8)
    trainer = build_trainer(toy, init_params(toy, jax.random.PRNGKey(0)))
    trainer.logits_fn = kimi_linear.make_logits(spec_of(cfg))
    return trainer


def aot_batch(cfg: Dict) -> Dict:
    """A batch in the generator's columns at the configuration's sizes."""
    import numpy as np

    shape = (cfg["sequences"], cfg["batch"] // cfg["sequences"])
    return {"fids": np.zeros(shape, np.int32), "fields": np.zeros(shape, np.int32),
            "mask": np.ones(shape, np.float32)}


def reference_view(batches: List[Dict]) -> List[Dict]:
    """What the reference may read of a fed batch: the generator's columns
    (token ids and document numbers), not what the program's layout added."""
    return [{"tokens": b["fids"], "segments": b["fields"]} for b in batches]
