"""The device's idle time a step: the traced window less the device's busy time
(union of its operation intervals, mean over the cell's chips), per step.  It is
NOT the host's work a step (that is ``train_host_busy_ms_per_step``): a host
that runs ahead of the device and then blocks on purpose leaves this near 0
however long its own step is.  Was ``train_host_ms_per_step`` until PR 28.
"""

META = {
    "name": "train_device_idle_ms_per_step",
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
