"""What handing a batch to the device costs the driving thread: mean over the
window's steps of the program's ``trainer/input`` span (``CTRTrainer._put``:
one ``device_put`` an array, to every device of a mesh).  The span's
attributes say what crossed: ``arrays``, ``bytes``, ``devices``.
"""

META = {
    "name": "train_input_ms_per_step",
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
    return sum(sum(map(spans.dur_ms, spans.named(below, "trainer/input")))
               for _, below in steps) / len(steps)
