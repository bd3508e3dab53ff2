"""What both cell runners share: keys from seeds, meshes, device memory,
the profiler, the compilation count."""

from __future__ import annotations

import contextlib
import glob
import os
import shutil
from typing import Dict, Optional


def prng_key(seed: int):
    """A key from a whole number of any size (the driver's pass 2**31)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def configure(cfg: Dict) -> str:
    """Persistent compilation cache at the program's fixed place inside the
    checkout, and the matmul precision the configuration states."""
    import jax

    from lightctr_tpu.utils.compile_cache import configure_compile_cache

    path = configure_compile_cache()
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    return path


def make_mesh(cfg: Dict, chips: int):
    """``(mesh, shardings_of(specs))`` for a configuration that states a
    mesh, else ``(None, None)``."""
    if "mesh" not in cfg:
        return None, None
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from lightctr_tpu.core.mesh import MeshSpec, make_mesh as program_mesh

    mesh = program_mesh(MeshSpec(**cfg["mesh"]), jax.devices()[:chips])

    def shardings(specs):
        if isinstance(specs, dict):
            return {k: shardings(v) for k, v in specs.items()}
        return NamedSharding(mesh, P(*specs))

    return mesh, shardings


def row_writers(cfg: Dict, chips: int) -> int:
    """Over how many chips the update of a batch's touched rows is divided.
    On a mesh the rows are sharded over ``embed`` and replicated over
    ``data``: every row lives on each data replica and each must write it,
    so a chip's least bytes are the total over the ``embed`` shards, not
    over the chips.  A configuration that states no mesh: the cell's chips."""
    if "mesh" not in cfg:
        return chips
    return int(cfg["mesh"].get("embed", 1))


def memory(chips: int) -> Dict[str, int]:
    """Of the fullest of the cell's chips: the process's peak of live
    buffers (on a TPU the constructor's copies, not the step's compiler
    temporaries, which ``memory_stats`` does not count), the buffers live
    now, and the chip's limit."""
    import jax

    peak = limit = in_use = 0
    for d in jax.devices()[:chips]:
        s = d.memory_stats()
        if s is None:       # a backend that reports none (the CPU, in tests)
            continue
        if s["peak_bytes_in_use"] >= peak:
            peak, limit = int(s["peak_bytes_in_use"]), int(s["bytes_limit"])
            in_use = int(s["bytes_in_use"])
    return {"peak": peak, "limit": limit, "in_use": in_use}


class CompileCounter:
    """Backend compilations since ``reset`` (JAX's own monitoring events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.count += 1

    def reset(self) -> None:
        self.count = 0


@contextlib.contextmanager
def profiled(trace_dir: Optional[str]):
    """The JAX profiler around the block when ``trace_dir`` is given (host
    Python frames off: the benchmark's own spans are what it reads)."""
    if trace_dir is None:
        yield
        return
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def read_trace(trace_dir: str) -> Dict:
    """Reduce the one trace under ``trace_dir`` and delete it."""
    from . import trace_reduce

    try:
        paths = glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        if len(paths) != 1:
            raise RuntimeError(f"expected one xplane file, found {paths}")
        return trace_reduce.reduce_trace(trace_reduce.load_xplane(paths[0]))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def span(name: str, **kw):
    import jax

    return jax.profiler.TraceAnnotation("bench/" + name, **kw)


def attach_trace(out: Dict, reduced: Optional[Dict]) -> Dict:
    """What a traced run adds to the result: the device's busy time and the
    traced window, and the breakdown."""
    if reduced is not None:
        out["device"].update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    return out
