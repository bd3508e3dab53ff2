"""What the host needs for a step if the chip were infinitely fast: median
over the window's steps of the program's ``trainer/step`` span less its
``trainer/health_fetch`` descendants (the one place the host waits for the
device on purpose).  ``batch / this`` is the host's ceiling in examples/s.
The median, because the host may also block inside ``trainer/exec`` when the
runtime's queue of dispatched steps is full, and that wait cannot be split
from the dispatch.
"""

META = {
    "name": "train_host_busy_ms_per_step",
    "unit": "ms",
    "better": "lower",
    "source": "program_span",
    "layer": "host step",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    import statistics

    from benchmarks.harness import spans
    steps = spans.last_steps(ctx)
    if steps is None:
        return None
    return statistics.median(
        spans.dur_ms(root) - sum(map(spans.dur_ms, spans.named(below, "trainer/health_fetch")))
        for root, below in steps)
