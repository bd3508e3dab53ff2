"""What ``obs/`` costs on the step path at its defaults, measured where it
runs: mean over the window's steps of the self time of the program's
``trainer/record`` span (registry, event log, health feed, resource and
device hooks, stepwatch), i.e. its duration less the ``trainer/health_fetch``
below it, which is a wait for the device and no work of the host's.
"""

META = {
    "name": "train_telemetry_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "program_span",
    "layer": "host step",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    from benchmarks.harness import spans
    steps = spans.last_steps(ctx)
    if steps is None:
        return None
    # within a step tree the health fetch happens below trainer/record alone
    return sum(
        sum(map(spans.dur_ms, spans.named(below, "trainer/record")))
        - sum(map(spans.dur_ms, spans.named(below, "trainer/health_fetch")))
        for _, below in steps) / len(steps)
