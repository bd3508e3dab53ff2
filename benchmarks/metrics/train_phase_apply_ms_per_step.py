"""Device time a step under the ``sparse_tables/apply`` scope:
the touched rows' merge-apply (``sparse_kernels.merge_apply``: the
accumulator's gather, the Adagrad arithmetic, the scatters and their switch).
Union of the phase's operation intervals after the wrapper rule, mean over the
cell's chips, per step (``harness/phases.py``).  A program without the scope
reads nothing.
"""

META = {
    "name": "train_phase_apply_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "sparse phases",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    from benchmarks.harness.phases import phase_ms_per_step
    return phase_ms_per_step(ctx, "apply")
