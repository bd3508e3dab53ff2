"""Bytes the Adagrad update of the touched rows must move (read and write row
and accumulator; from shapes) over peak HBM bytes/s, over the device time of
whatever implements the apply — found by the ``sparse_tables/apply`` scope,
not by kernel name.  A chip's bytes are the total over the chips that share the
rows (``common.row_writers``): the cell's chips, or on a mesh the ``embed``
shards alone, since every ``data`` replica holds each row and must write it.
ISSUE 25 calls it ``sparse_apply_hbm_roofline_share.train``; the contract's
``<kernel>_roofline`` holds.
"""

META = {
    "name": "sparse_apply_roofline",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    from benchmarks.harness.common import row_writers
    from benchmarks.harness.trace_reduce import scope_seconds
    r = ctx['reduced']
    if r is None:
        return None
    apply_s = scope_seconds(r, r'sparse_tables/apply') / max(1, ctx['steps'])
    if apply_s <= 0:
        return None
    writers = row_writers(ctx['cfg'], ctx['chips'])
    least_s = ctx['cost']['apply_bytes'] / writers / ctx['peaks']['hbm_bytes_per_s']
    return 100.0 * least_s / apply_s
