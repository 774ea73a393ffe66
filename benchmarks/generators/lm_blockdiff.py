"""Block-diffusion batches: ``[xt ; x0]`` token ids ``[sequences, 2 x
seq_len]`` int32, one example a clean token position (``batch = sequences x
seq_len``; the body sees two positions for each).

The clean ids ``x0`` are drawn as ``lm.py`` draws them (Zipf hashed through
``keys_from_raw``), into ``[0, key_space - 1)``: the slice's last row stands
for the mask token and is never drawn clean.  In every block of
``block_length`` tokens, ``m`` uniform on ``1..block_length`` positions,
chosen uniformly, are replaced by the mask token: ``xt``.  The masked counts
and positions come from the mix's ``pool_seed`` as the ids do, so the pool
of ``cycle`` batches is the same for every ``--seed`` (the same unique rows,
so the same compiled programs); ``--seed`` sets the order of the cycle."""

import numpy as np

from benchmarks.harness.traffic import deal, draw_raw_ids, keys_from_raw


def make(params, mix, *, seed, n_workers, cycle, batch):
    """``[worker][i] -> tokens``: every worker's own order of one pool."""
    sequences, b = params["sequences"], params["block_length"]
    if batch % sequences or (batch // sequences) % b:
        raise ValueError(
            f"batch {batch} is not {sequences} sequences of whole blocks of {b}"
        )
    L, mask_id = batch // sequences, params["key_space"] - 1
    raw = draw_raw_ids(mix, params["zipf_a"], (cycle, sequences, L))
    rng = np.random.default_rng([int(mix["pool_seed"]), 0xB10C])
    pool = []
    for i in range(cycle):
        x0 = keys_from_raw(raw[i], mask_id).astype(np.int32)
        m = rng.integers(1, b + 1, size=(sequences, L // b, 1))
        # a uniform permutation of each block's positions; its first m masked
        ranks = rng.random((sequences, L // b, b)).argsort(-1).argsort(-1)
        xt = np.where((ranks < m).reshape(sequences, L), mask_id, x0)
        pool.append(np.concatenate([xt, x0], axis=1).astype(np.int32))
    return [[pool[i] for i in idx] for idx in deal(cycle, n_workers, seed)]


def keys_of(batch):
    return batch
