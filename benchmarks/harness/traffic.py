"""The general part of traffic generation: what a traffic file's parameters
mean.  A mix is data (``benchmarks/traffic/<mix>.json``); the generator a
configuration names (``benchmarks/generators/<name>.py``) gives the drawn
keys the shape of its examples.

A mix fixes the *sizes* of the work and ``--seed`` everything else.  The keys
of a pool of ``cycle_batches`` batches are drawn from the mix's own
``pool_seed``, and every worker cycles through the whole pool, so every
``--seed`` and every worker sees the same set of batches: the same unique
rows per shard, and so the same padded and sliced shapes, which is every
program the run compiles (the program slices a pull's reply to its true row
count, so each distinct count is a program on each chip; a seed that moved
the counts would compile anew, a minute of set-up, and a pool per worker
would compile workers x as many).  ``--seed`` sets the order of each
worker's cycle, the labels, the dense features, the model's weights and the
gradients of the reference check."""

from __future__ import annotations

import numpy as np

from benchmarks.harness.keys import mix64


def draw_raw_ids(mix: dict, zipf_a: float, shape) -> np.ndarray:
    """Raw ids before hashing: ``zipf`` (exponent from the configuration's
    generator parameters, scaled by the mix's ``zipf_scale`` if it has one)
    or ``uniform`` over 2**62."""
    rng = np.random.default_rng(int(mix["pool_seed"]))
    dist = mix["key_dist"]
    if dist == "zipf":
        a = zipf_a * float(mix.get("zipf_scale", 1.0))
        return rng.zipf(a, size=shape).astype(np.uint64)
    if dist == "uniform":
        return rng.integers(0, 1 << 62, size=shape, dtype=np.uint64)
    raise ValueError(f"unknown key_dist {dist!r}")


def keys_from_raw(raw: np.ndarray, key_space: int,
                  per_feature: bool = False) -> np.ndarray:
    """Hash raw ids into ``[0, key_space)``; ``per_feature`` salts each
    column, so one raw id in two features is two rows (one embedding table
    per feature, laid end to end in one space)."""
    h = mix64(raw, seed=7)
    if per_feature:
        cols = np.arange(raw.shape[-1], dtype=np.uint64)
        with np.errstate(over="ignore"):
            h = mix64(h + cols, seed=11)
    return h % np.uint64(key_space)


def deal(n_batches: int, n_workers: int, seed: int) -> list:
    """Every worker's own order of the whole pool, from the seed."""
    rng = np.random.default_rng([int(seed), 0xDEA1])
    return [list(rng.permutation(n_batches)) for _ in range(n_workers)]
