"""tests_tpu are chip gates: they run on a TPU, through the chip tool, or
they do not run.  On any other platform collection fails with one message —
what these gates check at toy size on the CPU already lives in ``tests/``.

    python -m pytest tests_tpu -q        # from the repo root, on the chip

The check initialises the backend in the pytest process itself, the one
process that then runs the gates (a chip belongs to one process at a time).
"""

import jax
import pytest

_dev = jax.devices()[0]
if _dev.platform != "tpu":
    pytest.exit(
        f"tests_tpu needs a TPU: jax.devices()[0].platform is "
        f"{_dev.platform!r} ({_dev.device_kind}); nothing was collected",
        returncode=2,
    )
