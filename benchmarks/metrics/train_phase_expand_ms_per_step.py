"""Device time a step under the ``model/expand`` scope:
the models' per-position read of their table leaves
(``sparse_kernels.expand_rows``: ``rows[inv]``), forward and backward: the
take, its transpose's scatter-add and, on a mesh, the all-reduce over ``data``
that transpose feeds.
Union of the phase's operation intervals after the wrapper rule, mean over the
cell's chips, per step (``harness/phases.py``).  A program without the scope
reads nothing.
"""

META = {
    "name": "train_phase_expand_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "jitted step",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    from benchmarks.harness.phases import phase_ms_per_step
    return phase_ms_per_step(ctx, "expand")
