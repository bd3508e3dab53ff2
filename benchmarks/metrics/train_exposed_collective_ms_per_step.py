"""Exposed collective time a step: on each chip the part of its collective
operations' intervals (all-reduce, all-gather, all-to-all, reduce-scatter,
collective-permute, by instruction name or name stack) during which no other
operation runs on that chip, mean over the cell's chips, per step
(``harness/trace_reduce.py``: ``exposed_collective_s``).  A step with no
collective in its trace — one chip — has nothing to read.
"""

META = {
    "name": "train_exposed_collective_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "exchange",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    r = ctx['reduced']
    if r is None or not ctx['steps'] or r['collective_s'] <= 0:
        return None
    return 1e3 * r['exposed_collective_s'] / ctx['steps']
