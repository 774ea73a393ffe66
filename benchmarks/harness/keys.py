"""The benchmark's own copy of the 64-bit avalanche mix the program hashes
keys with (``parameter_server_tpu/utils/keys.py::mix64``).  The generators
and the plain references use this copy, so a change to the program's hash
shows as a failed reference check and not as silently different traffic.

And the one place that knows what a table's key-to-row map means.  A
configuration states it once (``table.localizer``: ``"hash"``, the default,
or ``"identity"``); the cluster is built with it and records it, and
whatever in the harness turns keys into rows asks here, with that record.
Independent of the program's ``HashLocalizer`` / ``IdentityLocalizer`` for
the same reason as the mix."""

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


def _identity_rows(keys, rows: int) -> np.ndarray:
    keys = np.asarray(keys, dtype=np.uint64)
    if keys.size and int(keys.max()) >= rows:
        raise ValueError(
            f"key {int(keys.max())} has no row in an identity-localised "
            f"table of {rows} rows: its keys are the rows 0 to {rows - 1}"
        )
    return keys.astype(np.int64)


def _identity_draw(rng, n_keys: int, rows: int) -> np.ndarray:
    return rng.choice(rows, size=min(n_keys, rows), replace=False).astype(
        np.uint64
    )


def _hash_draw(rng, n_keys: int, rows: int) -> np.ndarray:
    return rng.integers(1, 1 << 62, size=n_keys, dtype=np.uint64)


#: localizer -> (rows of keys, draw of check keys); ``localizer_of`` is the
#: one validator, so a name that is not here is a caller's fault (KeyError)
_MAPS = {
    "hash": (hash_slots, _hash_draw),
    "identity": (_identity_rows, _identity_draw),
}
LOCALIZERS = tuple(_MAPS)


def localizer_of(table_spec: dict, where: str) -> str:
    """The key-to-row map a configuration's ``table`` group states; absent
    means ``"hash"``.  ``where`` names the file in the error."""
    kind = table_spec.get("localizer", "hash")
    if kind not in _MAPS:
        raise ValueError(
            f"{where}: table.localizer is {kind!r}, and the harness knows "
            f"{' and '.join(map(repr, LOCALIZERS))}"
        )
    return kind


def rows_of(keys, rows: int, localizer: str) -> np.ndarray:
    """Row of each key in a table of ``rows`` rows: ``hash_slots`` under
    ``"hash"``; under ``"identity"`` the key itself (a dense vocabulary:
    token id == row), where a key at or above ``rows`` is an error."""
    return _MAPS[localizer][0](keys, rows)


def draw_check_keys(rng, n_keys: int, rows: int, localizer: str) -> np.ndarray:
    """Distinct keys for a reference check, from the domain the localizer
    takes: ``n_keys`` of ``[1, 2^62)`` under ``"hash"`` (distinct but for a
    chance of 1e-12); under ``"identity"`` ``min(n_keys, rows)`` of
    ``[0, rows)`` without replacement, spread over the whole range and so
    over every shard of a range-partitioned table."""
    return _MAPS[localizer][1](rng, n_keys, rows)
