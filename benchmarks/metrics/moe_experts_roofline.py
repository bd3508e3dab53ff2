"""The least time a chip needs for the held experts' three products over the
assignments the router made to them, forward and backward — the larger of FLOPs
over peak FLOP/s and bytes over peak HBM bytes/s from ``train_step_cost``
(``moe_experts_flops``, ``moe_experts_bytes``: from the assignments the program
counted, every held expert's weights read in both passes) — over the device
time under the ``seq/moe/experts`` scope, whatever implements the grouped
product.
"""

META = {
    "name": "moe_experts_roofline",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    from benchmarks.harness.trace_reduce import scope_seconds
    r, c, p = ctx['reduced'], ctx['cost'], ctx['peaks']
    if r is None or not c.get('moe_experts_flops'):
        return None
    experts_s = scope_seconds(r, r'seq/moe/experts') / max(1, ctx['steps'])
    if experts_s <= 0:
        return None
    least_s = max(c['moe_experts_flops'] / p['bf16_flops_per_s'],
                  c['moe_experts_bytes'] / p['hbm_bytes_per_s'])
    return 100.0 * least_s / experts_s
