"""Device time a step of the ``model`` phase: what the jitted step runs
under none of its five phase scopes — the forward and backward pass of the
model and its loss (the ``seq/*`` scopes are inside it), which open no scope of
their own — so less the row expansion, which is
``train_phase_expand_ms_per_step``'s.
Union of the phase's operation intervals after the wrapper rule, mean over the
cell's chips, per step (``harness/phases.py``).  A program that opens none of
the scopes that split the step reads nothing.
"""

META = {
    "name": "train_phase_model_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "device_trace",
    "layer": "jitted step",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    from benchmarks.harness.phases import phase_ms_per_step
    return phase_ms_per_step(ctx, "model")
