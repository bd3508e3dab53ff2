"""The least time a chip needs for the chunked delta-rule scans of a step,
forward and backward — the larger of their FLOPs over peak FLOP/s and their
bytes over peak HBM bytes/s, both from ``benchmarks/models/kimi_linear.py``'s
``train_step_cost`` (``kda_scan_flops``, ``kda_scan_bytes``) — over the device
time under the ``seq/kda/scan`` scope, whatever implements it.  Bytes bound it
at the published sizes.
"""

META = {
    "name": "kda_scan_roofline",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    from benchmarks.harness.trace_reduce import scope_seconds
    r, c, p = ctx['reduced'], ctx['cost'], ctx['peaks']
    if r is None or 'kda_scan_flops' not in c:
        return None
    scan_s = scope_seconds(r, r'seq/kda/scan') / max(1, ctx['steps'])
    if scan_s <= 0:
        return None
    least_s = max(c['kda_scan_flops'] / p['bf16_flops_per_s'],
                  c['kda_scan_bytes'] / p['hbm_bytes_per_s'])
    return 100.0 * least_s / scan_s
