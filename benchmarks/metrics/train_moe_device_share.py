"""Device time of operations under the ``seq/moe`` scope (the routed expert
layers of a step, forward and backward: router, plan, shared expert and the
grouped expert product), over device busy time.  A program without the scope
reads nothing.
"""

META = {
    "name": "train_moe_device_share",
    "unit": "%",
    "better": "lower",
    "source": "device_trace",
    "layer": "sequence tower",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    from benchmarks.harness.trace_reduce import scope_seconds
    r = ctx['reduced']
    if r is None or r['busy_s'] <= 0:
        return None
    s = scope_seconds(r, r'seq/moe')
    return 100.0 * s / r['busy_s'] if s > 0 else None
