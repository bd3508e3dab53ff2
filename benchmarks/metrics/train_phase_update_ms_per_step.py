"""Device time a step under the ``step/update`` scope:
the gradient's global norm, the dense leaves' optimizer update and
the step's health vector (loss, norm, the step's own counts, the quality sketch).
Union of the phase's operation intervals after the wrapper rule, mean over the
cell's chips, per step (``harness/phases.py``).  A program without the scope
reads nothing.
"""

META = {
    "name": "train_phase_update_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "jitted step",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    from benchmarks.harness.phases import phase_ms_per_step
    return phase_ms_per_step(ctx, "update")
