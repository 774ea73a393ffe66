"""Language-model batches: token ids ``[sequences, seq_len]`` int32, one
example a token position (``batch = sequences x seq_len``).

The ids are the mix's draws (Zipf: token frequencies are Zipfian) hashed
through ``keys_from_raw`` into ``[0, key_space)``, the slice of the
vocabulary the configuration holds: key == row of an identity-localised
table.  A sequence is one whole document: no packing, no padding.  The pool
of ``cycle`` batches comes from the mix's ``pool_seed``, so every seed sees
the same batches (the same unique rows per shard, so the same compiled
programs); ``--seed`` sets the order of the cycle."""

import numpy as np

from benchmarks.harness.traffic import deal, draw_raw_ids, keys_from_raw


def make(params, mix, *, seed, n_workers, cycle, batch):
    """``[worker][i] -> tokens``: every worker's own order of one pool."""
    sequences = params["sequences"]
    if batch % sequences:
        raise ValueError(f"batch {batch} is not {sequences} whole sequences")
    raw = draw_raw_ids(
        mix, params["zipf_a"], (cycle, sequences, batch // sequences)
    )
    pool = [
        keys_from_raw(raw[i], params["key_space"]).astype(np.int32)
        for i in range(cycle)
    ]
    return [[pool[i] for i in idx] for idx in deal(cycle, n_workers, seed)]


def keys_of(batch):
    return batch
