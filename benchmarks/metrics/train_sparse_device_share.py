"""Device time of operations under the ``sparse_tables/*`` scopes, or that are
a unique/sort/gather/scatter, over device busy time.
"""

META = {
    "name": "train_sparse_device_share",
    "unit": "%",
    "better": "lower",
    "source": "device_trace",
    "layer": "sparse phases",
    "moves": "train_examples_per_s_per_chip"
}

SPARSE = r"sparse_tables/|unique|sort|gather|scatter"


def read(ctx):
    from benchmarks.harness.trace_reduce import scope_seconds
    r = ctx['reduced']
    if r is None or r['busy_s'] <= 0:
        return None
    return 100.0 * scope_seconds(r, SPARSE) / r['busy_s']
