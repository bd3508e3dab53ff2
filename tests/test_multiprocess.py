"""True multi-process distributed run on localhost — the reference's CI
strategy (SURVEY.md §4: build master/ps/worker against 127.0.0.1) re-expressed
as two OS processes joining via jax.distributed + a cross-process psum."""

import os
import socket
import subprocess
import sys
import textwrap
from pathlib import Path

REPO_ROOT = str(Path(__file__).resolve().parents[1])

WORKER = textwrap.dedent(
    """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax
    jax.config.update("jax_platforms", "cpu")
    pid = int(sys.argv[1]); port = sys.argv[2]
    from lightctr_tpu.dist import initialize_multihost
    initialize_multihost(coordinator_address=f"127.0.0.1:{port}",
                         num_processes=2, process_id=pid)
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.experimental import multihost_utils
    from jax.sharding import Mesh, PartitionSpec as P
    assert jax.device_count() == 4 and jax.local_device_count() == 2
    mesh = Mesh(np.asarray(jax.devices()).reshape(4), ("data",))
    x = jnp.ones((4,)) * (pid + 1)
    arr = multihost_utils.host_local_array_to_global_array(x, mesh, P("data"))
    f = shard_map(lambda v: jax.lax.psum(v, "data"), mesh=mesh,
                  in_specs=P("data"), out_specs=P("data"))
    out = multihost_utils.global_array_to_host_local_array(
        jax.jit(f)(arr), mesh, P("data"))
    print("RESULT", pid, float(np.asarray(out)[0]), flush=True)
    """
)


def test_two_process_psum(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = REPO_ROOT + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(i), str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
            env=env, cwd=REPO_ROOT,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=240)
        assert p.returncode == 0, err[-2000:]
        outs.append(out)
    # proc0 holds 1s on 2 global shards, proc1 2s on 2 -> psum = 1+1+2+2 = 6
    for i, out in enumerate(outs):
        assert f"RESULT {i} 6.0" in out, out
