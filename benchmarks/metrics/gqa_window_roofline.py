"""The least time a chip needs for the window layers' attention cores of a
step, forward and backward — the larger of FLOPs over peak FLOP/s and bytes
over peak HBM bytes/s: ``train_step_cost``'s ``gqa_window_flops_per_pair``
times the (query, key) pairs inside the window that the traffic file counts
(``counted.windowed_pairs_per_step``), and its ``gqa_window_bytes`` — over
the device time under the ``seq/gqa/window`` scope, whatever implements it.
FLOPs bound it at the published sizes.
"""

META = {
    "name": "gqa_window_roofline",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    from benchmarks.harness.trace_reduce import scope_seconds
    r, c, p = ctx['reduced'], ctx['cost'], ctx['peaks']
    pairs = ctx['traffic'].get('counted', {}).get('windowed_pairs_per_step')
    if r is None or not pairs or not c.get('gqa_window_flops_per_pair'):
        return None
    core_s = scope_seconds(r, r'seq/gqa/window') / max(1, ctx['steps'])
    if core_s <= 0:
        return None
    least_s = max(pairs * c['gqa_window_flops_per_pair'] / p['bf16_flops_per_s'],
                  c['gqa_window_bytes'] / p['hbm_bytes_per_s'])
    return 100.0 * least_s / core_s
