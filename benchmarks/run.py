"""The benchmark's command.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One new process per run: it refuses to run off a TPU (or with fewer chips
than the cell asks for), makes weights and inputs from the seed, warms the
cell's own shapes, measures for ``--seconds``, decides ``correct`` against
the plain reference once the window has closed, and prints the result as
the last line of standard output.  ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics and ``breakdown``.
Everything a cell is made of is found by name (``harness/manifest.py``).
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(chips: int) -> dict:
    """No accelerator, or fewer chips than the cell asks for: no run."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"benchmark: needs a TPU, but jax.devices()[0].platform is "
            f"{devs[0].platform!r}; nothing was run")
    if len(devs) < chips:
        raise SystemExit(
            f"benchmark: the cell asks for {chips} chips, JAX sees "
            f"{len(devs)}; nothing was run")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def run_cell(man, cell: dict, device: dict, seed: int, seconds: float,
             trace: bool, t_start: float, **overrides):
    """One run of a cell in this process -> the runner's result.
    ``overrides`` replace entries of the runner's context (the tools vary
    the traffic or ask for the control's readings)."""
    cfg = man.config(cell["config"])
    traffic = man.traffic(cell["traffic"])
    ctx = {
        "manifest": man, "cell": cell, "cfg": cfg, "traffic": traffic,
        "model": man.model(cfg["model"]), "seed": seed, "seconds": seconds,
        "trace": trace, "t_start": t_start, "device": device,
        "limits": man.cell_file(cell["name"])["limits"],
        "cache_root": os.path.join(man.bench_dir, ".cache"),
    }
    ctx.update(overrides)
    return man.runner(ctx["traffic"]["kind"]).run(ctx)


def main(argv=None, require=require_chips, root=ROOT) -> int:
    args = parse_args(argv)
    from benchmarks.harness import report
    from benchmarks.harness.manifest import Manifest

    man = Manifest(root)
    cell = man.workload(args.workload)
    device = require(cell["chips"])
    result = run_cell(man, cell, device, args.seed, args.seconds,
                      bool(args.trace), T_PROCESS_START)
    line = report.result_line(man, cell, result, bool(args.trace))
    report.print_checks(result["checks"], sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
