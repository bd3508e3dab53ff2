"""Run one cell of a throw-away tiny benchmark root on the CPU, optionally
with the timed path broken underneath, and print the run's last line.

A process of its own, so the harness's process-wide settings (compilation
cache, matmul precision, telemetry) never leak into the test session:

    python tests/benchmark/drive.py --root <tmp> --workload wd-train-zipf \\
        --trace 0 [--fault frozen_state|half_batch|no_exchange]

It skips the harness's look for a chip and drives the rest of a run.
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
    if fault == "frozen_state":
        # a step that returns its state unchanged
        from lightctr_tpu.models import ctr_trainer

        real = ctr_trainer.CTRTrainer.train_step

        def frozen(self, batch, **kw):
            params, state = self.params, self.opt_state
            import jax

            keep = jax.tree_util.tree_map(lambda x: x + 0, (params, state))
            loss = real(self, batch, **kw)
            self.params, self.opt_state = keep
            return loss

        ctr_trainer.CTRTrainer.train_step = frozen
    elif fault == "half_batch":
        # half of the batch left out, the mean taken over the rest
        from lightctr_tpu.models import ctr_trainer

        real = ctr_trainer.CTRTrainer.train_step

        def half(self, batch, **kw):
            n = len(batch["labels"]) // 2
            return real(self, {k: v[:n] for k, v in batch.items()}, **kw)

        ctr_trainer.CTRTrainer.train_step = half
    elif fault == "no_exchange":
        # the exchange between chips left out: what a data shard applies
        # holds its own rows' table gradients only (the other shard's
        # slots masked out, the mean still taken over the whole batch)
        import numpy as np

        from lightctr_tpu.models import ctr_trainer

        real = ctr_trainer.CTRTrainer.train_step

        def alone(self, batch, **kw):
            n = len(batch["labels"]) // 2
            cut = dict(batch)
            for k in ("mask", "rep_mask"):
                cut[k] = np.array(batch[k])
                cut[k][n:] = 0
            return real(self, cut, **kw)

        ctr_trainer.CTRTrainer.train_step = alone
    elif fault:
        raise SystemExit(f"unknown fault {fault!r}")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--seed", default="3000000019")
    ap.add_argument("--seconds", default="1.5")
    ap.add_argument("--fault", default="")
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(args.root, "jax_cache")
    plant(args.fault)
    from benchmarks import run

    return run.main(["--workload", args.workload, "--seed", args.seed,
                     "--seconds", args.seconds, "--trace", args.trace],
                    require=helpers.cpu_device, root=args.root)


if __name__ == "__main__":
    sys.exit(main())
