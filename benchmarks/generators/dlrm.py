"""DLRM-shaped batches ``(keys [B, n_sparse] uint64, dense [B, n_dense] f32,
labels [B] f32)``.

Copy of ``parameter_server_tpu/data/synthetic.py::SyntheticDLRM`` with the
skew exponent and the key space as parameters, and each feature hashed with
its own salt (one table per feature, laid end to end in one row space).  The
label depends on the dense features and on hidden per-key effects (a quarter
of the keys carry +-0.5), so both the MLPs and the rows have to train."""

import numpy as np

from benchmarks.harness.keys import mix64
from benchmarks.harness.traffic import deal, draw_raw_ids, keys_from_raw


def _key_effect(keys):
    h = mix64(keys, seed=0x5EED)
    sign = np.where((h >> np.uint64(2)) & np.uint64(1), 1.0, -1.0)
    return np.where((h % np.uint64(4)) == 0, sign * 0.5, 0.0)


def make(params, mix, *, seed, n_workers, cycle, batch):
    """``[worker][i] -> (keys, dense, labels)``: a pool of ``cycle`` distinct
    batches, every worker's cycle an order of its own of the whole pool."""
    n_sparse, n_dense = params["n_sparse"], params["n_dense"]
    key_space = params["key_space"]
    raw = draw_raw_ids(mix, params["zipf_a"], (cycle, batch, n_sparse))
    rng = np.random.default_rng([int(seed), 0xD1])
    w_dense = rng.normal(size=n_dense) / np.sqrt(n_dense)
    pool = []
    for i in range(cycle):
        keys = keys_from_raw(raw[i], key_space, per_feature=True)
        dense = rng.normal(size=(batch, n_dense)).astype(np.float32)
        logits = dense @ w_dense + _key_effect(keys).sum(axis=1) - 0.5
        labels = (rng.random(batch) < 1 / (1 + np.exp(-logits))).astype(
            np.float32
        )
        pool.append((keys, dense, labels))
    return [[pool[i] for i in idx] for idx in deal(cycle, n_workers, seed)]


def keys_of(batch):
    return batch[0]
