"""The ingest producer's time per batch: mean duration of the program's
``ingest/produce`` span over the window's batches — the prefetch worker's
``next(source)`` (shard slice, shuffle, the model's host layout) plus
``prepare``.  ``batch / this`` is the producer's ceiling in examples/s,
whatever the consumer waits today.
"""

META = {
    "name": "ingest_produce_ms_per_batch",
    "unit": "ms",
    "better": "lower",
    "source": "program_span",
    "layer": "ingest",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    from benchmarks.harness import spans
    made = spans.last_spans(ctx, "ingest/produce")
    if made is None:
        return None
    return sum(map(spans.dur_ms, made)) / len(made)
