"""Union of the device's operation intervals in the traced window, per step,
mean over the cell's chips.
"""

META = {
    "name": "train_device_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "jitted step",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    r = ctx['reduced']
    if r is None or not ctx['steps'] or r['busy_s'] <= 0:
        return None
    return 1e3 * r['busy_s'] / ctx['steps']
