"""Device time of operations under the ``seq/gqa`` scope (the grouped-query
attention layers of a step, forward and backward: projections, rotary
positions and both attention cores), over device busy time.  A program
without the scope reads nothing.
"""

META = {
    "name": "train_gqa_device_share",
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
    s = scope_seconds(r, r'seq/gqa')
    return 100.0 * s / r['busy_s'] if s > 0 else None
