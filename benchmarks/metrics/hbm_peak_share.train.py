"""``memory_stats()['peak_bytes_in_use']`` of the fullest chip over its
``bytes_limit``: the process's peak of live buffers.  Today that is the
trainer constructor's transient (the caller's tables, the program's copy
of them and its zero accumulators: three times the parameters), not the
step; the step's compiler temporaries are not counted by ``memory_stats``.
"""

META = {
    "name": "hbm_peak_share.train",
    "unit": "%",
    "better": "lower",
    "source": "program_counter",
    "layer": "device memory",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    m = ctx['memory']
    if not m['limit']:
        return None
    return 100.0 * m['peak'] / m['limit']
