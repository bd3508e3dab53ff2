"""A mixture-of-experts language model with window and full attention
mixed: Mellum 2 (huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct), as
a model of the sparse-table trainer.

Pre-norm residual blocks, ``h = x + Attn(RMSNorm(x))``, ``y = h +
MoE(RMSNorm(h))``: the attention is grouped-query with rotary positions
(``nn/gqa.py``), over a window of ``Spec.window`` tokens in a
``"sliding_attention"`` layer and over the whole document in a
``"full_attention"`` one (YaRN's frequencies and attention factor there),
as ``Spec.layers`` says; every FFN is the routed expert layer
(``nn/moe.py``) with the softmax router and no shared expert.  A final
RMSNorm and an untied head give the logits of the next token.

As in ``models/kimi_linear.py``: the token embedding ``params["embed"]``
is a ``sparse_tables`` leaf of ``SparseTableCTRTrainer`` (``{"embed":
["tokens"]}``), the batch is ``data.ingest.sequence_batch``'s (a packed
document's number in ``segment_ids``: positions restart and attention
stops at its edges), the loss ``TrainConfig(loss="softmax_xent")``,
``Spec.held`` the chip's share of each layer's experts, one remat
boundary a layer, and a layer's weight gradients complete before the
layer below begins its backward pass (:func:`grads_at_once`).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from lightctr_tpu.nn import gqa, mla, moe
from lightctr_tpu.nn.kda import rms_norm
from lightctr_tpu.ops.sparse_kernels import expand_rows
from lightctr_tpu.utils.profiling import annotate

WINDOW, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class Spec:
    """The model's static sizes.  The defaults are a tiny preset for the
    tests; ``benchmarks/configs/mellum2-12b-a2.5b-ep4.json`` holds the
    published ones."""

    vocab: int = 64
    hidden: int = 64
    layers: Tuple[str, ...] = (WINDOW, WINDOW, WINDOW, FULL)
    heads: int = 4
    kv_heads: int = 2
    head_dim: int = 16
    window: int = 8
    rope_theta: float = 500000.0
    # YaRN of the full layers: factor, original context, beta_fast,
    # beta_slow, attention factor
    yarn: Tuple[float, int, float, float, float] = (16.0, 8192, 32.0, 1.0,
                                                    1.2772588722239782)
    expert_width: int = 32
    n_experts: int = 8
    top_k: int = 4
    held: Tuple[int, int] = (0, 2)
    eps: float = 1e-6
    block: int = 16
    tile: int = 8

    def rope(self, kind: str):
        """``(inv_freq, factor)`` of a layer kind."""
        if kind == WINDOW:
            return gqa.default_inv_freq(self.head_dim, self.rope_theta), 1.0
        factor, original, fast, slow, scale = self.yarn
        return gqa.yarn_inv_freq(self.head_dim, self.rope_theta, factor,
                                 original, fast, slow), scale


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(float(fan_in))


def init(key: jax.Array, s: Spec) -> Dict:
    """Weights ~ N(0, 1 / fan_in), ``wo`` and ``w_down`` over ``2 *
    layers`` besides (``kimi_linear.init``'s reason), norms one, the
    embedding N(0, 1)."""
    ke, kh, kl = jax.random.split(key, 3)
    small = (2.0 * len(s.layers)) ** -0.5
    d, hq, hk = s.hidden, s.heads * s.head_dim, s.kv_heads * s.head_dim
    params = {"embed": jax.random.normal(ke, (s.vocab, d), jnp.float32),
              "final_norm": jnp.ones((d,), jnp.float32),
              "head": _normal(kh, (d, s.vocab), d)}
    for i in range(1, len(s.layers) + 1):
        k = jax.random.split(jax.random.fold_in(kl, i), 8)
        lead, f = (s.held[1],), s.expert_width
        params[f"layer{i}"] = {
            "norm1": jnp.ones((d,), jnp.float32),
            "attn": {"wq": _normal(k[0], (d, hq), d), "wk": _normal(k[1], (d, hk), d),
                     "wv": _normal(k[2], (d, hk), d),
                     "wo": _normal(k[3], (hq, d), hq) * small},
            "norm2": jnp.ones((d,), jnp.float32),
            "ffn": {"router": _normal(k[4], (d, s.n_experts), d),
                    "experts": {"w_gate": _normal(k[5], lead + (d, f), d),
                                "w_up": _normal(k[6], lead + (d, f), d),
                                "w_down": _normal(k[7], lead + (f, d), f) * small}}}
    return params


def _layer(s: Spec, kind: str, p: Dict, x, seg, cos, sin):
    h = rms_norm(x, p["norm1"], s.eps)
    x = x + gqa.mixer(p["attn"], h, seg, cos, sin, heads=s.heads,
                      kv_heads=s.kv_heads, window=s.window if kind == WINDOW else 0,
                      block=s.block)
    h = rms_norm(x, p["norm2"], s.eps)
    y, stats = moe.ffn(p["ffn"], h, top_k=s.top_k, first=s.held[0],
                       tile=s.tile, router="softmax")
    return x + y, stats


@jax.custom_vjp
def grads_at_once(tree):
    """``tree`` as it is; its backward pass hands on all the cotangents of
    ``tree`` together, through one optimization barrier.  Around a layer's
    weights and input it makes the layer's weight gradients complete before
    the layer below begins its backward pass: XLA otherwise put each weight
    gradient's product into the optimizer's update at the end of the step
    and held its operands, the layer's activations and their cotangents,
    until then (11.03 GB of compiled temporaries at the published sizes
    where the tie leaves 4.77)."""
    return tree


def _grads_at_once_fwd(tree):
    return tree, None


def _grads_at_once_bwd(_, cotangents):
    return (jax.lax.optimization_barrier(cotangents),)


grads_at_once.defvjp(_grads_at_once_fwd, _grads_at_once_bwd)


def make_logits(s: Spec):
    """``logits(params, batch) -> ([B, T, vocab], counts)``.  ``counts``
    are the integers ``logits.step_counts`` names, in its order: per
    layer the router's assignments, those to held experts and the busiest
    held expert's tokens; then, per attention kind in the stack, the
    (query, key) pairs one layer of that kind attends (counted from the
    segment ids), and the pairs a full layer's core multiplies to do so
    (``mla.visited_pairs``)."""
    if any(kind not in (WINDOW, FULL) for kind in s.layers):
        raise ValueError(f"layers are {WINDOW!r} or {FULL!r}, got {s.layers}")
    kinds = tuple(k for k in (WINDOW, FULL) if k in s.layers)

    def logits(params: Dict, batch: Dict[str, jax.Array]):
        seg = batch["segment_ids"]
        pos = gqa.positions(seg)
        tables = {k: gqa.rope_table(pos, *s.rope(k)) for k in kinds}
        x = expand_rows(params["embed"], batch["tokens"])
        stats = []
        for i, kind in enumerate(s.layers, 1):
            p_i, x = grads_at_once((params[f"layer{i}"], x))
            x, st = jax.checkpoint(_layer, static_argnums=(0, 1))(
                s, kind, p_i, x, seg, *tables[kind])
            stats.append(st)
        stats += [gqa.attended_pairs(seg, s.window if k == WINDOW else 0)[None]
                  for k in kinds]
        if FULL in kinds:
            stats.append(mla.visited_pairs(seg, s.block)[None])
        norm, head, x = grads_at_once((params["final_norm"], params["head"], x))
        with annotate("seq/head_loss"):
            z = rms_norm(x, norm, s.eps) @ head
        return z, jnp.concatenate(stats)

    logits.step_counts = tuple(
        (name, {"layer": str(i)}) for i in range(1, len(s.layers) + 1)
        for name in ("trainer_moe_assignments_total",
                     "trainer_moe_held_assignments_total",
                     "trainer_moe_expert_tokens_max")) + tuple(
        ("trainer_seq_attn_pairs_total", {"kind": "window" if k == WINDOW else "full"})
        for k in kinds) + (
        (("trainer_seq_attn_visited_pairs_total", {"kind": "full"}),)
        if FULL in kinds else ())
    return logits


def build(key: jax.Array, s: Spec = Spec()):
    """``(params, logits_fn)`` ready for ``SparseTableCTRTrainer(...,
    TrainConfig(loss="softmax_xent"), sparse_tables={"embed": ["tokens"]})``."""
    return init(key, s), make_logits(s)
