"""Criteo-shaped click batches ``(keys [B, nnz] uint64, labels [B] f32)``.

Copy of ``parameter_server_tpu/data/synthetic.py::SyntheticCTR`` with the
skew exponent and the key space as parameters: keys are skewed raw ids
hashed over the key space, and the label is Bernoulli of the logistic of a
hidden sparse weight vector (a twentieth of the keys carry +-1), so the
loss has something to learn."""

import numpy as np

from benchmarks.harness.keys import mix64
from benchmarks.harness.traffic import deal, draw_raw_ids, keys_from_raw


def _true_weight(keys, key_space, informative):
    h = mix64(keys, seed=0xABCDEF)
    n_inf = max(1, int(key_space * informative))
    inf = (h % np.uint64(key_space)) < np.uint64(n_inf)
    sign = np.where((h >> np.uint64(1)) & np.uint64(1), 1.0, -1.0)
    return np.where(inf, sign, 0.0)


def make(params, mix, *, seed, n_workers, cycle, batch):
    """``[worker][i] -> (keys, labels)``: a pool of ``cycle`` distinct
    batches, every worker's cycle an order of its own of the whole pool."""
    nnz, key_space = params["nnz"], params["key_space"]
    raw = draw_raw_ids(mix, params["zipf_a"], (cycle, batch, nnz))
    rng = np.random.default_rng([int(seed), 0xC7])
    pool = []
    for i in range(cycle):
        keys = keys_from_raw(raw[i], key_space)
        logits = _true_weight(
            keys, key_space, params["informative"]
        ).sum(axis=1) + params["bias"]
        p = 1.0 / (1.0 + np.exp(-logits))
        pool.append((keys, (rng.random(batch) < p).astype(np.float32)))
    return [[pool[i] for i in idx] for idx in deal(cycle, n_workers, seed)]


def keys_of(batch):
    return batch[0]
