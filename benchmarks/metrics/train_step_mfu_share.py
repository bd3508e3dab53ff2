"""The whole step's share of the chip's peak: the least time one chip needs for
its share of a step — the larger of required FLOPs over peak FLOP/s and
required HBM bytes over peak bytes/s, both from the model file's shape
functions (touched rows, not the table) — over the window's time per step.
A chip's share: FLOPs, gathered rows and the batch over the cell's chips; the
update's bytes over the chips that share the rows (``common.row_writers``: on a
mesh the ``embed`` shards alone, since every ``data`` replica holds each row and
must write it).  Bytes bound it in every cell today.
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
    from benchmarks.harness.common import row_writers
    p, c, chips = ctx['peaks'], ctx['cost'], ctx['chips']
    bytes_a_chip = ((c['hbm_bytes'] - c['apply_bytes']) / chips
                    + c['apply_bytes'] / row_writers(ctx['cfg'], chips))
    least_s = max(c['flops'] / chips / p['bf16_flops_per_s'],
                  bytes_a_chip / p['hbm_bytes_per_s'])
    return 100.0 * least_s / (ctx['window_s'] / ctx['steps'])
