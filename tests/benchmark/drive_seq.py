"""``drive.py`` for a ``train_seq`` cell: one run of a throw-away tiny
benchmark root on the CPU with the sequence tower's timed path broken
underneath, printing the run's last line.

    python tests/benchmark/drive_seq.py --root <tmp> --workload kimilinear-train-packed8k \\
        --fault no_segment_reset|absent_experts_renormalised|half_targets|bf16
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

from lightctr_tpu.utils.devicecheck import pin_cpu_platform  # noqa: E402

pin_cpu_platform(8)

import helpers  # noqa: E402


def plant(fault: str) -> None:
    """Break the program's timed path where it produces its result."""
    import numpy as np

    from lightctr_tpu.data import ingest
    from lightctr_tpu.nn import moe

    laid_out = ingest.sequence_batch
    if fault == "no_segment_reset":
        # state, convolutions and attention run across document boundaries
        def one_document(batch):
            out = laid_out(batch)
            out["segment_ids"] = np.zeros_like(out["segment_ids"])
            return out

        ingest.sequence_batch = one_document
    elif fault == "half_targets":
        # the targets of the second half of the sequence left out
        def first_half(batch):
            out = laid_out(batch)
            out["target_mask"][:, out["target_mask"].shape[1] // 2:] = 0
            return out

        ingest.sequence_batch = first_half
    elif fault == "absent_experts_renormalised":
        # the weights renormalised over the held experts only
        import jax.numpy as jnp

        plan = moe.tile_plan

        def renormalised(idx, weights, first, n_held, tile):
            held = (idx >= first) & (idx < first + n_held)
            share = jnp.sum(jnp.where(held, weights, 0), axis=-1, keepdims=True)
            total = jnp.sum(weights, axis=-1, keepdims=True)
            return plan(idx, weights * total / jnp.where(share == 0, 1, share),
                        first, n_held, tile)

        moe.tile_plan = renormalised
    elif fault == "bf16":
        # the model in the precision below the one the configuration states
        import jax
        import jax.numpy as jnp

        from lightctr_tpu.models import kimi_linear

        make = kimi_linear.make_logits

        def make_bf16(spec):
            logits = make(spec)

            def low(params, batch):
                z, counts = logits(jax.tree_util.tree_map(
                    lambda x: x.astype(jnp.bfloat16), params), batch)
                return z.astype(jnp.float32), counts

            low.step_counts = logits.step_counts
            return low

        kimi_linear.make_logits = make_bf16
    elif fault:
        raise SystemExit(f"unknown fault {fault!r}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", default="3000000019")
    ap.add_argument("--seconds", default="1.0")
    ap.add_argument("--fault", default="")
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(args.root, "jax_cache")
    plant(args.fault)
    from benchmarks import run

    return run.main(["--workload", args.workload, "--seed", args.seed,
                     "--seconds", args.seconds, "--trace", "0"],
                    require=helpers.cpu_device, root=args.root)


if __name__ == "__main__":
    sys.exit(main())
