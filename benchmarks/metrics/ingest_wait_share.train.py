"""Share of the window the step loop spent blocked in ``next(stream)``: the
benchmark's own clock around the call into the program's input pipeline.
"""

META = {
    "name": "ingest_wait_share.train",
    "unit": "%",
    "better": "lower",
    "source": "host_clock",
    "layer": "ingest",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    return 100.0 * ctx['ingest_wait_s'] / ctx['window_s']
