"""Device time a step under the ``sparse_tables/dedup_gather/gather_rows`` scope:
the forward gather of each table's touched rows (``gather_live`` /
``gather_shards``: the live plan, the switch over the ladder, the lane-row
gather and pick, and on a mesh the ``psum`` that joins the shards' rows).
Union of the phase's operation intervals after the wrapper rule, mean over the
cell's chips, per step (``harness/phases.py``).  A program without the scope
reads nothing.
"""

META = {
    "name": "train_phase_gather_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "sparse phases",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    from benchmarks.harness.phases import phase_ms_per_step
    return phase_ms_per_step(ctx, "gather")
