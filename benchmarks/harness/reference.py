"""The plain reference of a training cell's first steps, and the comparison
that decides ``correct``.

The reference follows the first ``len(batches)`` steps in straightforward
``jax.numpy`` float32 at ``highest`` matmul precision: forward, logistic
loss (mean over the batch, plus ``lambda_l2`` times the model's penalty),
``jax.grad``, dense Adagrad (``accum += g*g; p -= lr*g*rsqrt(accum+eps)``).
It works on the *compacted* tables — the rows the steps touch, gathered from
the benchmark's own initial weights before the program got its copy — which
is exact: Adagrad leaves a row with zero gradient where it was.  It imports
nothing of the program and is given nothing the program made.

``variant`` plants what a test or a calibration needs in the reference put
in the program's place:

``f32``          the reference itself;
``bf16``         the control: parameters, inputs and arithmetic in bfloat16
                 (the precision below the float32 the configurations state);
``half_batch``   the fault "half of the batch left out, the mean taken over
                 the rest";
``no_exchange``  the fault "the exchange between chips left out": a data
                 shard applies the gradient of its own half of the rows,
                 scaled as if the other half's had been added.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

VARIANTS = ("f32", "bf16", "half_batch", "no_exchange")
#: a leaf whose first reference gradient is under this share of the median
#: leaf's moves by round-off alone and is left out of the change comparison
DEAD_GRADIENT_SHARE = 1e-3


def touched_union(model, batches: Sequence[Dict]) -> Dict[str, np.ndarray]:
    """Per id field, the sorted distinct ids the batches touch."""
    out = {}
    for field in set(model.TABLES.values()):
        out[field] = np.unique(np.concatenate(
            [np.asarray(b[field]).reshape(-1) for b in batches]))
    return out


def compact_batches(model, batches: Sequence[Dict], union: Dict[str, np.ndarray]
                    ) -> List[Dict]:
    """The batches with ids rewritten to positions in the compacted tables;
    only what the reference reads (ids, values, labels)."""
    out = []
    for b in batches:
        c = {"vals": np.asarray(b["vals"], np.float32) * np.asarray(b["mask"], np.float32),
             "labels": np.asarray(b["labels"], np.float32)}
        for field, ids in union.items():
            c[field] = np.searchsorted(ids, np.asarray(b[field])).astype(np.int32)
        out.append(c)
    return out


def flat_leaves(tree: Dict, prefix: str = "") -> Dict[str, np.ndarray]:
    out = {}
    for k, v in tree.items():
        name = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(flat_leaves(v, name + "."))
        else:
            out[name] = v
    return out


def reference_steps(model, cfg: Dict, params0: Dict, batches: Sequence[Dict],
                    variant: str = "f32") -> Dict:
    """Run the steps on compacted ``params0``; returns per-step losses, the
    first gradient's norm per leaf and the change's norm per leaf."""
    import jax
    import jax.numpy as jnp

    if variant not in VARIANTS:
        raise ValueError(f"unknown reference variant {variant!r}")
    lr, eps = float(cfg["learning_rate"]), float(cfg["adagrad_eps"])
    l2 = float(cfg["lambda_l2"])
    dtype = jnp.bfloat16 if variant == "bf16" else jnp.float32

    def loss_fn(params, batch):
        p = jax.tree_util.tree_map(lambda x: x.astype(dtype), params)
        b = dict(batch, vals=batch["vals"].astype(dtype))
        labels = batch["labels"].astype(dtype)
        if variant in ("half_batch", "no_exchange"):
            half = labels.shape[0] // 2
            b = {k: v[:half] for k, v in b.items()}
            labels = labels[:half]
        z = model.reference_logits(p, b, cfg)
        n = batch["labels"].shape[0] if variant == "no_exchange" else z.shape[0]
        # log(1 + e^z) - y z, the stable way
        per_row = jnp.maximum(z, 0) - labels * z + jnp.log1p(jnp.exp(-jnp.abs(z)))
        loss = jnp.sum(per_row)
        if l2 > 0.0:
            loss = loss + l2 * model.reference_penalty(p, b, cfg)
        return (loss / n).astype(jnp.float32)

    @jax.jit
    def step(params, accum, batch):
        loss, g = jax.value_and_grad(loss_fn)(params, batch)
        g = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), g)
        accum = jax.tree_util.tree_map(lambda a, x: a + x * x, accum, g)
        params = jax.tree_util.tree_map(
            lambda p, x, a: p - lr * x * jax.lax.rsqrt(a + eps), params, g, accum)
        gnorm = jax.tree_util.tree_map(lambda x: jnp.sqrt(jnp.sum(x * x)), g)
        return params, accum, loss, gnorm

    with jax.default_matmul_precision("highest"):
        p0 = jax.tree_util.tree_map(lambda x: jnp.asarray(x, jnp.float32), params0)
        params = p0
        accum = jax.tree_util.tree_map(jnp.zeros_like, p0)
        losses, first_gnorm = [], None
        for i, b in enumerate(batches):
            params, accum, loss, gnorm = step(
                params, accum, {k: jnp.asarray(v) for k, v in b.items()})
            losses.append(float(loss))
            if i == 0:
                first_gnorm = jax.device_get(gnorm)
        change = jax.tree_util.tree_map(
            lambda a, b_: float(jnp.sqrt(jnp.sum((a - b_) ** 2))), params, p0)
    return {"loss": losses,
            "grad_norm": {k: float(v) for k, v in flat_leaves(first_gnorm).items()},
            "change_norm": flat_leaves(change)}


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float],
                   leaves: Sequence[str]) -> float:
    """The widest gap between a leaf's norm here and in the reference (the
    gap of norms, not the norm of a difference), against the reference's
    norm of that leaf or of the median leaf, whichever is larger."""
    med = float(np.median([want[k] for k in want]))
    return max(abs(got[k] - want[k]) / max(want[k], med, 1e-30) for k in leaves)


def compare(got: Dict, want: Dict) -> Dict[str, float]:
    """The numbers compared: ``got`` and ``want`` as ``reference_steps``
    returns them (``got`` read from the program's state)."""
    leaves = sorted(want["grad_norm"])
    med = float(np.median([want["grad_norm"][k] for k in leaves]))
    live = [k for k in leaves
            if want["grad_norm"][k] >= DEAD_GRADIENT_SHARE * med]
    return {
        "loss_gap": max(abs(g - w) / abs(w)
                        for g, w in zip(got["loss"], want["loss"])),
        "grad_norm_gap": worst_leaf_gap(got["grad_norm"], want["grad_norm"], leaves),
        "change_norm_gap": worst_leaf_gap(got["change_norm"], want["change_norm"], live),
    }


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """``correct`` and the checks list: each number beside its limit.  A
    number that is not finite fails."""
    checks = {}
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name)
        if value is not None and not np.isfinite(value):
            value = None                  # JSON has no inf; null fails too
        passed = value is not None and value <= limit
        ok = ok and passed
        checks[name] = {"value": value, "limit": limit}
    return {"correct": bool(ok), "checks": checks}
