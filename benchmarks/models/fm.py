"""Factorization machine (Rendle 2010, "Factorization Machines", ICDM),
second order, with the per-occurrence L2 of the LightCTR reference: the
benchmark's own initialiser, plain float32 reference, work counts, and the
adapter that builds the program under test.

    logit = sum_p w[fid_p] x_p + 0.5 * (|sum_p v[fid_p] x_p|^2 - sum_p |v[fid_p] x_p|^2)
    l2    = 0.5 * (sum_p w[fid_p]^2 + sum_p |v[fid_p]|^2)      (summed over the batch)
"""

from __future__ import annotations

from typing import Dict

TABLES = {"w": "fids", "v": "fids"}


def init_params(cfg: Dict, key) -> Dict:
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.initializers import hashed_normal

    v, k = cfg["vocab"], cfg["factors"]
    return {"w": jnp.zeros((v,), jnp.float32),
            "v": hashed_normal(key, v, k) / jnp.sqrt(float(k))}


def param_specs(cfg: Dict) -> Dict:
    return {"w": ("embed",), "v": ("embed", None)}


def state_bytes(cfg: Dict, training: bool) -> int:
    v, k = cfg["vocab"], cfg["factors"]
    return 4 * (v + v * k) * (2 if training else 1)


def reference_logits(params: Dict, batch: Dict, cfg: Dict):
    import jax
    import jax.numpy as jnp

    vals = batch["vals"]
    linear = jnp.sum(params["w"][batch["fids"]] * vals, axis=-1)
    vx = params["v"][batch["fids"]] * vals[..., None]           # [B, P, k]
    s = jnp.sum(vx, axis=1)
    return linear + 0.5 * (jnp.sum(s * s, -1) - jnp.sum(vx * vx, (1, 2)))


def reference_penalty(params: Dict, batch: Dict, cfg: Dict):
    import jax.numpy as jnp

    w = params["w"][batch["fids"]]
    v = params["v"][batch["fids"]]
    return 0.5 * (jnp.sum(w * w) + jnp.sum(v * v))


def train_step_cost(cfg: Dict, distinct: int) -> Dict[str, float]:
    b, p, k = cfg["batch"], cfg["fields"], cfg["factors"]
    fwd = b * p * (4 * k + 2) + 3 * b * k            # vx, sums, squares, linear
    flops = 3 * fwd + 2 * b * p * (k + 1)            # fwd + bwd, + the L2 term
    row = 4 * (k + 1)
    gather = distinct * row
    apply = 4 * distinct * row
    batch_in = b * p * 4 * 3 + b * 4                 # ids, vals, mask, labels
    return {"flops": float(flops), "gather_bytes": float(gather),
            "apply_bytes": float(apply),
            "hbm_bytes": float(gather + apply + batch_in)}


def feed_layout(cfg: Dict, batch: Dict) -> Dict:
    return {k: v for k, v in batch.items() if k != "row_mask"}


def build_trainer(cfg: Dict, params: Dict, mesh=None, shardings=None):
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.models import fm
    from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer

    return SparseTableCTRTrainer(
        params, fm.logits,
        TrainConfig(learning_rate=cfg["learning_rate"],
                    lambda_l2=cfg["lambda_l2"]),
        sparse_tables={"w": ["fids"], "v": ["fids"]},
        fused_fn=fm.logits_with_l2, mesh=mesh, param_shardings=shardings,
    )
