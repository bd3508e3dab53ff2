"""Device busy time of none of the step's six phases (dedup, gather, expand,
model, update, apply), after the wrapper rule, over device busy time: the
guard that the map of ``harness/phases.py`` is whole.  What is left is what the
compiler made without a name stack (copies, a wrapper around several phases)
or what another program than the step ran.  A program that opens none of the
scopes that split the step reads nothing.
"""

META = {
    "name": "train_unphased_device_share",
    "unit": "%",
    "better": "lower",
    "source": "device_trace",
    "layer": "jitted step",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    from benchmarks.harness.phases import unphased_share
    return unphased_share(ctx)
