"""Device time a step under the ``sparse_tables/dedup_gather/dedup_ids`` scope:
the dedup of each id stream (``sparse_kernels.dedup_ids``: three sorts
and a scan) and the rewrite of the batch's ids to positions.
Union of the phase's operation intervals after the wrapper rule, mean over the
cell's chips, per step (``harness/phases.py``).  A program without the scope
reads nothing.
"""

META = {
    "name": "train_phase_dedup_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "sparse phases",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    from benchmarks.harness.phases import phase_ms_per_step
    return phase_ms_per_step(ctx, "dedup")
