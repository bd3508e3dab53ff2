"""Window time per step less device busy time per step (traced window): what
the host side of ``train_step`` (layout, ``_put``, dispatch, telemetry)
leaves the chip waiting for.
"""

META = {
    "name": "train_host_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "host step",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    r = ctx['reduced']
    if r is None or not ctx['steps']:
        return None
    return 1e3 * (r['window_s'] - r['busy_s']) / ctx['steps']
