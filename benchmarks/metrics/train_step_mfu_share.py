"""The whole step's share of the chip's peak: the least time one chip needs for
its share of a step — the larger of required FLOPs over peak FLOP/s and
required HBM bytes over peak bytes/s, both from the model file's shape
functions (touched rows, not the table), over the cell's chips — over the
window's time per step.  Bytes bound it in every cell today.
"""

META = {
    "name": "train_step_mfu_share",
    "unit": "%",
    "better": "higher",
    "source": "host_clock",
    "layer": "jitted step",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    p, c = ctx['peaks'], ctx['cost']
    least_s = max(c['flops'] / p['bf16_flops_per_s'], c['hbm_bytes'] / p['hbm_bytes_per_s']) / ctx['chips']
    return 100.0 * least_s / (ctx['window_s'] / ctx['steps'])
