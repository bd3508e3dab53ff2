"""The least time a chip needs for the full layers' attention cores of a
step, forward and backward — the larger of FLOPs over peak FLOP/s and bytes
over peak HBM bytes/s from ``train_step_cost`` (``gqa_full_flops``,
``gqa_full_bytes``: the (query, key) pairs of the traffic's documents, each
attending to its whole past) — over the device time under the
``seq/gqa/full`` scope, whatever implements it.  FLOPs bound it at the
published sizes.
"""

META = {
    "name": "gqa_full_roofline",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    from benchmarks.harness.trace_reduce import scope_seconds
    r, c, p = ctx['reduced'], ctx['cost'], ctx['peaks']
    if r is None or not c.get('gqa_full_flops'):
        return None
    core_s = scope_seconds(r, r'seq/gqa/full') / max(1, ctx['steps'])
    if core_s <= 0:
        return None
    least_s = max(c['gqa_full_flops'] / p['bf16_flops_per_s'],
                  c['gqa_full_bytes'] / p['hbm_bytes_per_s'])
    return 100.0 * least_s / core_s
