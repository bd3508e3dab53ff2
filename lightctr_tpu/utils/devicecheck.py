"""The explicit CPU preamble for ``tests/`` and the host-side tools, and
the TPU-or-nothing check of the measurement entry points.

The program runs on a TPU by default and fails at start-up where JAX finds
none; nothing here probes for a device or falls back to another platform.
A process that is MEANT to run on the host — the test suite, the
multi-process cluster tools and their workers — says so first, by calling
:func:`pin_cpu_platform` before anything touches the backend.  A chip
belongs to one process at a time, so a launcher that starts JAX children
pins itself AND its children (the environment set here is inherited).
"""

from __future__ import annotations

import os
import re
from typing import Optional


def pin_cpu_platform(n_devices: Optional[int] = None) -> None:
    """Pin this process, and every child it starts, to the CPU platform
    with ``n_devices`` virtual host devices.

    Call it before the first backend use: ``XLA_FLAGS`` (the virtual
    device count is read once, at backend initialisation), the
    ``JAX_PLATFORMS`` environment variable (inherited by children), and
    ``jax.config`` (this process may have imported jax already, after
    which the environment variable alone is too late).

    It also takes XLA:CPU's concurrency-optimized scheduler off, unless
    ``XLA_FLAGS`` already says either way.  The row-sharded sparse step
    holds one ``psum`` in every branch of two ``switch``es over the same
    axis, all on one channel id; under that scheduler the CPU runtime
    starts both conditionals at once, two all-reduces meet in one
    rendezvous and the process aborts or segfaults (PR 35: the parent's
    step over unpacked ``[V, 16]``-and-wider tables 0 of 4 runs, 4 of 4
    with the scheduler off).  A TPU runs its ops in one stream."""
    flags = os.environ.get("XLA_FLAGS", "")
    if n_devices is not None:
        pat = r"--xla_force_host_platform_device_count=\d+"
        want = f"--xla_force_host_platform_device_count={n_devices}"
        if re.search(pat, flags):
            flags = re.sub(pat, want, flags)
        else:
            flags = (flags + " " + want).strip()
    if "xla_cpu_enable_concurrency_optimized_scheduler" not in flags:
        flags = (flags + " --xla_cpu_enable_concurrency_optimized_scheduler"
                 "=false").strip()
    os.environ["XLA_FLAGS"] = flags
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def require_tpu(program: str):
    """``jax.devices()[0]`` when it is a TPU; otherwise the process exits
    non-zero, having measured nothing."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"{program}: needs a TPU, but jax.devices()[0].platform is "
            f"{dev.platform!r} ({dev.device_kind}); nothing was measured"
        )
    return dev
