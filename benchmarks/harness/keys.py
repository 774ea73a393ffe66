"""The benchmark's own copy of the 64-bit avalanche mix the program hashes
keys with (``parameter_server_tpu/utils/keys.py::mix64``).  The generators
and the plain references use this copy, so a change to the program's hash
shows as a failed reference check and not as silently different traffic."""

import numpy as np

_MUL1 = np.uint64(0xFF51AFD7ED558CCD)
_MUL2 = np.uint64(0xC4CEB9FE1A85EC53)
_S33 = np.uint64(33)


def mix64(x, seed=0):
    """splitmix64-style avalanche mix over a uint64 array."""
    x = np.asarray(x, dtype=np.uint64)
    with np.errstate(over="ignore"):
        x = (x ^ np.uint64(seed)) * _MUL1
        x ^= x >> _S33
        x *= _MUL2
        x ^= x >> _S33
    return x


def hash_slots(keys, capacity, seed=0):
    """Row slot of each key under the hashing trick the configurations name
    (``HashLocalizer``: ``mix64(key, seed) % capacity``)."""
    return (mix64(keys, seed) % np.uint64(capacity)).astype(np.int64)
