"""Wide&Deep (Cheng et al. 2016, arXiv:1606.07792, as the LightCTR
reference's PS-mode model lays it out): the benchmark's own initialiser,
plain float32 reference, work counts, and the adapter that builds the
program under test.

    wide  = sum_p w[fid_p] * val_p
    deep  = sigmoid(fc2(tanh(fc1(concat_f embed[fid_f]))))     # one row a field
    logit = wide + deep ;  p = sigmoid(logit)

Only ``build_trainer`` / ``feed_layout`` import the
program.  The reference takes nothing the program made: it reads the batch
the generator produced (one feature per field, slot j is field j, so the
field representative of field j IS slot j's id) and weights from
``init_params``.
"""

from __future__ import annotations

from typing import Dict

#: table leaf -> the batch field whose ids index it (reference's view)
TABLES = {"w": "fids", "embed": "fids"}


def init_params(cfg: Dict, key) -> Dict:
    """w zero, embed ~ N(0, 1/dim), fc weights ~ U(-0.5, 0.5), zero biases
    (the reference project's initialisers), float32."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness.initializers import hashed_normal

    v, d, h, f = cfg["vocab"], cfg["dim"], cfg["hidden"], cfg["fields"]
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w": jnp.zeros((v,), jnp.float32),
        "embed": hashed_normal(k1, v, d) / jnp.sqrt(float(d)),
        "fc1": {"w": jax.random.uniform(k2, (h, f * d), jnp.float32, -0.5, 0.5),
                "b": jnp.zeros((h,), jnp.float32)},
        "fc2": {"w": jax.random.uniform(k3, (1, h), jnp.float32, -0.5, 0.5),
                "b": jnp.zeros((1,), jnp.float32)},
    }


def param_specs(cfg: Dict) -> Dict:
    """PartitionSpec axes per leaf on a mesh with an ``embed`` axis: tables
    row-sharded, the tower replicated."""
    return {"w": ("embed",), "embed": ("embed", None),
            "fc1": {"w": (), "b": ()}, "fc2": {"w": (), "b": ()}}


def state_bytes(cfg: Dict, training: bool) -> int:
    v, d, h, f = cfg["vocab"], cfg["dim"], cfg["hidden"], cfg["fields"]
    params = 4 * (v + v * d + h * f * d + h + h + 1)
    return params * (2 if training else 1)


# -- plain reference ----------------------------------------------------------


def reference_logits(params: Dict, batch: Dict, cfg: Dict):
    """float32 (or whatever dtype ``params`` carry) forward pass, no
    kernels, no dedup: every slot gathers its own row."""
    import jax
    import jax.numpy as jnp

    vals = batch["vals"]
    wide = jnp.sum(params["w"][batch["fids"]] * vals, axis=-1)
    emb = params["embed"][batch["fids"]]                    # [B, F, D]
    x = emb.reshape(emb.shape[0], -1)
    h = jnp.tanh(x @ params["fc1"]["w"].T + params["fc1"]["b"])
    deep = jax.nn.sigmoid(h @ params["fc2"]["w"].T + params["fc2"]["b"])[:, 0]
    return wide + deep


def reference_penalty(params: Dict, batch: Dict, cfg: Dict):
    return 0.0


# -- work the algorithm needs, from shapes ------------------------------------


def train_step_cost(cfg: Dict, distinct: int) -> Dict[str, float]:
    """FLOPs and HBM bytes one training step has to spend, counting touched
    rows and not the table.  ``distinct``: distinct ids in a batch (both
    tables are indexed by the same ids)."""
    b, f, d, h = cfg["batch"], cfg["fields"], cfg["dim"], cfg["hidden"]
    tower = 2 * b * (f * d) * h + 2 * b * h          # fc1 + fc2, forward
    flops = 3 * tower + 2 * b * f                    # fwd + dW + dX, + wide
    row = 4 * (d + 1)                                # one embed row + one w
    gather = distinct * row
    apply = 4 * distinct * row                       # read/write row + accum
    batch_in = b * f * 4 * 5 + b * 4                 # 2 id streams, vals, 2 masks, labels
    return {"flops": float(flops), "gather_bytes": float(gather),
            "apply_bytes": float(apply),
            "hbm_bytes": float(gather + apply + batch_in)}


# -- the program under test ---------------------------------------------------


def feed_layout(cfg: Dict, batch: Dict) -> Dict:
    """The program's own host-side batch preparation for this model."""
    from lightctr_tpu.models import widedeep

    rep, rep_mask = widedeep.field_representatives(
        batch["fids"], batch["fields"], batch["mask"], cfg["fields"])
    out = {k: v for k, v in batch.items() if k != "row_mask"}
    out["rep_fids"], out["rep_mask"] = rep, rep_mask
    return out


def build_trainer(cfg: Dict, params: Dict, mesh=None, shardings=None):
    from lightctr_tpu import TrainConfig
    from lightctr_tpu.models import widedeep
    from lightctr_tpu.models.sparse_trainer import SparseTableCTRTrainer

    return SparseTableCTRTrainer(
        params, widedeep.logits,
        TrainConfig(learning_rate=cfg["learning_rate"],
                    lambda_l2=cfg["lambda_l2"]),
        sparse_tables={"w": ["fids"], "embed": ["rep_fids"]},
        mesh=mesh, param_shardings=shardings,
    )
