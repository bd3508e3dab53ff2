"""``memory_stats()['bytes_in_use']`` of the fullest chip over its
``bytes_limit``, read as the window closes: the buffers the training state
holds between steps (parameters and optimizer state; a few batches).
"""

META = {
    "name": "hbm_resident_share.train",
    "unit": "%",
    "better": "lower",
    "source": "program_counter",
    "layer": "device memory",
    "moves": "train_examples_per_s_per_chip"
}


def read(ctx):
    m = ctx['memory']
    if not m['limit'] or not m['in_use']:
        return None
    return 100.0 * m['in_use'] / m['limit']
