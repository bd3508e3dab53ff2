"""A hybrid linear-attention mixture-of-experts language model: Kimi Linear
(arXiv:2510.26692; huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct),
as a model of the sparse-table trainer.

Pre-norm residual blocks, ``h = x + Mixer(RMSNorm(x))``, ``y = h +
FFN(RMSNorm(h))``: the mixer is Kimi Delta Attention (``nn/kda.py``) or
latent attention without positions (``nn/mla.py``) as ``Spec.mixers``
says, the FFN a dense SwiGLU in the first ``first_dense`` layers and the
routed expert layer (``nn/moe.py``) after them; a final RMSNorm and an
untied head give the logits of the next token.  Next-item prediction over
long behaviour histories is the same model over another vocabulary.

The token embedding ``params["embed"]`` is a ``sparse_tables`` leaf of
``SparseTableCTRTrainer`` (``{"embed": ["tokens"]}``): a step dedups the
batch's token ids, gathers the touched rows, and applies Adagrad to those
rows alone, as it does for a CTR model's tables.  Everything else is a
dense leaf.  ``batch``: ``tokens`` [B, T] int32, ``segment_ids`` [B, T]
int32 (a packed document's number: state, convolutions and attention stop
at its edges), and for the loss ``targets`` / ``target_mask``
(``data.ingest.sequence_batch`` makes all four).  Train with
``TrainConfig(loss="softmax_xent")``.

``Spec.held`` is the chip's share of each layer's experts, ``(first,
count)``: the router keeps all ``n_experts`` outputs.  One remat boundary
a layer (``jax.checkpoint``): the backward pass keeps a layer's input and
makes the rest again.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from lightctr_tpu.nn import kda, mla, moe
from lightctr_tpu.ops.sparse_kernels import expand_rows
from lightctr_tpu.utils.profiling import annotate


@dataclasses.dataclass(frozen=True)
class Spec:
    """The model's static sizes.  The defaults are the tiny preset of the
    tests and the CLI; ``benchmarks/configs/kimi-linear-48b-a3b-ep16.json``
    holds the published ones."""

    vocab: int = 64
    hidden: int = 64
    mixers: Tuple[str, ...] = ("kda", "kda", "kda", "mla", "kda")
    first_dense: int = 1
    heads: int = 2
    kda_head_dim: int = 16
    conv: int = 4
    gate_rank: int = 16
    kv_rank: int = 32
    d_nope: int = 16
    d_pe: int = 8
    d_v: int = 16
    dense_width: int = 128
    expert_width: int = 32
    n_experts: int = 8
    top_k: int = 4
    held: Tuple[int, int] = (0, 2)
    scaling: float = 2.446
    eps: float = 1e-5
    chunk: int = 16
    attn_block: int = 16
    tile: int = 8
    # blocking that changes no result: heads a run of a mixer, rows a
    # block of the dense FFN (0: all at once)
    head_groups: int = 2
    ffn_block: int = 0

    @property
    def moe_layers(self) -> Tuple[int, ...]:
        """1-based numbers of the layers whose FFN is routed."""
        return tuple(range(self.first_dense + 1, len(self.mixers) + 1))


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape, jnp.float32) / jnp.sqrt(float(fan_in))


def _swiglu_init(key, d, f, lead=()):
    kg, ku, kd = jax.random.split(key, 3)
    return {"w_gate": _normal(kg, lead + (d, f), d),
            "w_up": _normal(ku, lead + (d, f), d),
            "w_down": _normal(kd, lead + (f, d), f)}


def _kda_init(key, s: Spec) -> Dict:
    d, hd = s.hidden, s.heads * s.kda_head_dim
    k = jax.random.split(key, 12)
    return {
        "wq": _normal(k[0], (d, hd), d), "wk": _normal(k[1], (d, hd), d),
        "wv": _normal(k[2], (d, hd), d),
        "conv_q": _normal(k[3], (hd, s.conv), s.conv),
        "conv_k": _normal(k[4], (hd, s.conv), s.conv),
        "conv_v": _normal(k[5], (hd, s.conv), s.conv),
        "f_down": _normal(k[6], (d, s.gate_rank), d),
        "f_up": _normal(k[7], (s.gate_rank, hd), s.gate_rank),
        # the decay's rate and step as the public implementation draws
        # them: A in [1, 16), a step of 1e-3 .. 1e-1 through softplus
        "a_log": jnp.log(jax.random.uniform(k[8], (s.heads,), jnp.float32, 1.0, 16.0)),
        "dt_bias": _inv_softplus(jnp.exp(jax.random.uniform(
            k[9], (hd,), jnp.float32, jnp.log(1e-3), jnp.log(1e-1)))),
        "wb": _normal(k[10], (d, s.heads), d),
        "g_down": _normal(k[11], (d, s.gate_rank), d),
        "g_up": _normal(jax.random.fold_in(key, 12), (s.gate_rank, hd), s.gate_rank),
        "o_norm": jnp.ones((s.kda_head_dim,), jnp.float32),
        "wo": _normal(jax.random.fold_in(key, 13), (hd, d), hd),
    }


def _inv_softplus(y):
    return y + jnp.log(-jnp.expm1(-y))


def _mla_init(key, s: Spec) -> Dict:
    d = s.hidden
    k = jax.random.split(key, 4)
    return {
        "wq": _normal(k[0], (d, s.heads * (s.d_nope + s.d_pe)), d),
        "wkva": _normal(k[1], (d, s.kv_rank + s.d_pe), d),
        "kv_norm": jnp.ones((s.kv_rank,), jnp.float32),
        "wkvb": _normal(k[2], (s.kv_rank, s.heads * (s.d_nope + s.d_v)), s.kv_rank),
        "wo": _normal(k[3], (s.heads * s.d_v, d), s.heads * s.d_v),
    }


def _ffn_init(key, s: Spec, layer: int) -> Dict:
    if layer <= s.first_dense:
        return _swiglu_init(key, s.hidden, s.dense_width)
    kr, ks, ke = jax.random.split(key, 3)
    return {"router": _normal(kr, (s.hidden, s.n_experts), s.hidden),
            "router_bias": jnp.zeros((s.n_experts,), jnp.float32),
            "shared": _swiglu_init(ks, s.hidden, s.expert_width),
            "experts": _swiglu_init(ke, s.hidden, s.expert_width,
                                    lead=(s.held[1],))}


def init(key: jax.Array, s: Spec) -> Dict:
    """Weights ~ N(0, 1 / fan_in) — those that write into the residual
    stream (``wo``, ``w_down``) over ``2 * layers`` besides, the scaled
    initialisation of deep residual stacks: at random weights a mixer's
    output is near its document's average for every token, and unscaled
    it weighs on the router's input —, norms one, the embedding N(0, 1)."""
    ke, kh, kl = jax.random.split(key, 3)
    small = (2.0 * len(s.mixers)) ** -0.5

    def residual_writers(tree):
        return {k: residual_writers(v) if isinstance(v, dict)
                else v * small if k in ("wo", "w_down") else v
                for k, v in tree.items()}

    params = {"embed": jax.random.normal(ke, (s.vocab, s.hidden), jnp.float32),
              "final_norm": jnp.ones((s.hidden,), jnp.float32),
              "head": _normal(kh, (s.hidden, s.vocab), s.hidden)}
    for i, kind in enumerate(s.mixers, 1):
        km, kf = jax.random.split(jax.random.fold_in(kl, i))
        params[f"layer{i}"] = residual_writers({
            "norm1": jnp.ones((s.hidden,), jnp.float32),
            "mixer": (_kda_init if kind == "kda" else _mla_init)(km, s),
            "norm2": jnp.ones((s.hidden,), jnp.float32),
            "ffn": _ffn_init(kf, s, i)})
    return params


def _layer(s: Spec, i: int, kind: str, p: Dict, x, seg):
    h = kda.rms_norm(x, p["norm1"], s.eps)
    if kind == "kda":
        x = x + kda.mixer(p["mixer"], h, seg, heads=s.heads, eps=s.eps,
                          chunk=s.chunk, groups=s.head_groups)
    else:
        x = x + mla.mixer(p["mixer"], h, seg, heads=s.heads, d_nope=s.d_nope,
                          d_pe=s.d_pe, eps=s.eps, block=s.attn_block,
                          groups=s.head_groups)
    h = kda.rms_norm(x, p["norm2"], s.eps)
    if i <= s.first_dense:
        with annotate("seq/ffn_dense"):
            y = moe.swiglu(p["ffn"], h.reshape(-1, s.hidden), s.ffn_block)
            return x + y.reshape(x.shape), jnp.zeros((3,), jnp.int32)
    y, stats = moe.ffn(p["ffn"], h, top_k=s.top_k, scaling=s.scaling,
                       first=s.held[0], tile=s.tile)
    return x + y, stats


def make_logits(s: Spec):
    """``logits(params, batch) -> ([B, T, vocab], counts)``.  ``counts``
    are the integers ``logits.step_counts`` names, in its order: per routed
    layer the router's assignments, those to held experts and the busiest
    held expert's tokens.  The sparse trainer carries them on the step's
    health vector (``models/sparse_trainer._StepCounts``)."""
    if any(kind not in ("kda", "mla") for kind in s.mixers):
        raise ValueError(f"mixers are 'kda' or 'mla', got {s.mixers}")

    def logits(params: Dict, batch: Dict[str, jax.Array]):
        seg = batch["segment_ids"]
        x = expand_rows(params["embed"], batch["tokens"])
        stats = []
        for i, kind in enumerate(s.mixers, 1):
            x, st = jax.checkpoint(_layer, static_argnums=(0, 1, 2))(
                s, i, kind, params[f"layer{i}"], x, seg)
            if i > s.first_dense:
                stats.append(st)
        with annotate("seq/head_loss"):
            z = kda.rms_norm(x, params["final_norm"], s.eps) @ params["head"]
        return z, jnp.concatenate(stats) if stats else jnp.zeros((0,), jnp.int32)

    logits.step_counts = tuple(
        (name, {"layer": str(i)}) for i in s.moe_layers
        for name in ("trainer_moe_assignments_total",
                     "trainer_moe_held_assignments_total",
                     "trainer_moe_expert_tokens_max"))
    return logits


def build(key: jax.Array, s: Spec = Spec()):
    """``(params, logits_fn)`` ready for ``SparseTableCTRTrainer(...,
    TrainConfig(loss="softmax_xent"), sparse_tables={"embed": ["tokens"]})``."""
    return init(key, s), make_logits(s)
