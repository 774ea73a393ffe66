"""Key localization: global 64-bit feature keys -> dense local row ids.

This is the host-side half of the reference's core sparse trick
(``src/util/localizer.h`` :: ``Localizer`` [U]): global keys from parsed
examples are deduplicated and remapped to a compact dense id space so the
device only ever sees fixed-shape integer-indexed batches.  The device-side
half (gather / scatter-add over the row table) lives in
``parameter_server_tpu.ops.scatter`` (built in the same round as this module;
if that import fails you are looking at an intermediate tree).

Two flavors:

- :func:`localize_batch` — stateless per-batch dedup (np.unique), what the
  reference does per feature block.
- :class:`Localizer` — a persistent growing vocabulary mapping global keys to
  stable row slots, used by streaming learners (FTRL) where a key must keep
  its optimizer state across batches.

Shapes fed to jit-compiled code must be static; :func:`bucket_size` pads
unique-key counts to a small set of bucket sizes so recompilation happens at
most ``O(log(max_keys))`` times (SURVEY.md §7 hard part #1).
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

#: Sentinel padding key: never a valid feature key. Padded rows scatter into a
#: dedicated trash row on device (see ops.scatter), so no masking is needed on
#: the hot path.
PAD_KEY = np.uint64(0xFFFFFFFFFFFFFFFF)

_MIX_MUL = np.uint64(0xFF51AFD7ED558CCD)
_MIX_MUL2 = np.uint64(0xC4CEB9FE1A85EC53)


def mix64(x: np.ndarray, seed: int | np.uint64 = 0) -> np.ndarray:
    """splitmix64-style avalanche mix, vectorized over uint64 arrays."""
    x = np.asarray(x, dtype=np.uint64)
    with np.errstate(over="ignore"):
        x = (x ^ np.uint64(seed)) * _MIX_MUL
        x ^= x >> np.uint64(33)
        x *= _MIX_MUL2
        x ^= x >> np.uint64(33)
    return x


#: murmur3 fmix32 constants — the 32-bit avalanche used when hashing happens
#: ON DEVICE (TPU has no native uint64).  ``models/linear.py`` ``mix32_jax``
#: imports these so the host/device twins stay bit-identical by construction.
MIX32_A = 0x85EB_CA6B
MIX32_B = 0xC2B2_AE35

#: uint32 image of PAD_KEY under truncation; reserved on the device-hash
#: path (keys must be < 2**32 - 1 there).
PAD_KEY32 = np.uint32(0xFFFF_FFFF)


def mix32(x: np.ndarray, seed: int | np.uint32 = 0) -> np.ndarray:
    """murmur3 fmix32 avalanche, vectorized over uint32 arrays.

    Host twin of the device-side ``mix32_jax``: both produce identical slot
    assignments, so host preprocessing and device hashing interoperate.
    """
    x = np.asarray(x, dtype=np.uint32)
    with np.errstate(over="ignore"):
        x = x ^ np.uint32(seed)
        x ^= x >> np.uint32(16)
        x *= np.uint32(MIX32_A)
        x ^= x >> np.uint32(13)
        x *= np.uint32(MIX32_B)
        x ^= x >> np.uint32(16)
    return x


def ensure_uint32_keys(keys: np.ndarray) -> np.ndarray:
    """Validate raw-width keys for the device-hash path; return them uint32.

    The device-hash trainer truncates keys to uint32, so keys ``>= 2**32-1``
    would silently wrap (or alias :data:`PAD_KEY32` and route to the trash
    row), corrupting training with no error.  This enforces the documented
    "< 2**32 - 1 unless PAD" contract: callers pass keys at their RAW width
    (a caller-side ``astype(np.uint32)`` would wrap bad keys before the
    check can see them — ADVICE r2), and this returns the validated uint32
    array.  Already-uint32 input passes through untouched (the width itself
    is the proof).  Shared by ``LocalLRTrainer.step_block`` and the prefetch
    producer so pipelined ingest keeps the same guard.
    """
    keys = np.asarray(keys)
    if keys.dtype == np.uint32:
        return keys
    kb = keys.astype(np.uint64)  # signed -1 coerces to PAD_KEY
    # cheap scalar early-out: only blocks containing a suspicious key
    # (>= uint32 max; PAD_KEY itself is uint64 max) pay for the mask
    if int(kb.max(initial=0)) >= 0xFFFF_FFFF:
        bad = (kb != PAD_KEY) & (kb >= np.uint64(0xFFFF_FFFF))
        if bad.any():
            raise ValueError(
                "device-hash keys must be < 2**32 - 1 "
                f"(or PAD_KEY); got {int(kb[bad][0])}"
            )
    return kb.astype(np.uint32)


def bucket_size(n: int, *, min_bucket: int = 256) -> int:
    """Round ``n`` up to the next power-of-two bucket (>= min_bucket).

    Bucketing the number of unique keys per batch keeps jit cache size
    logarithmic in batch size instead of recompiling per distinct count.
    """
    if n <= min_bucket:
        return min_bucket
    return 1 << int(np.ceil(np.log2(n)))


def leg_bucket(n: int) -> int:
    """Bucket of one server's leg of a request (``n`` ids): next power of
    two, >= 8 (the Pallas block floor).  The server pads a leg's ids and
    values to it; a worker that hands device arrays over pads to the same."""
    return bucket_size(max(n, 1), min_bucket=8)


def flat_keys(keys) -> np.ndarray:
    """Keys as every localization takes them: flattened, as ``uint64``.
    Keys are uint64 by contract; signed parser output is coerced so PAD_KEY
    padding cannot wrap to -1 and break the sortedness invariant.  A view
    of ``keys`` where they are that already."""
    return np.ascontiguousarray(keys).ravel().astype(np.uint64, copy=False)


def localize_batch(
    keys: np.ndarray, *, pad_to_bucket: bool = True, min_bucket: int = 256
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Deduplicate a batch of global keys.

    Args:
      keys: int/uint array of global feature keys, any shape; flattened.
      pad_to_bucket: pad the unique-key array with :data:`PAD_KEY` up to a
        power-of-two bucket so downstream jit sees few distinct shapes.

    Returns:
      ``(unique_keys, inverse, n_unique)`` where ``unique_keys`` is sorted
      (padded with PAD_KEY at the tail if requested), ``inverse`` maps each
      input position to its row in ``unique_keys``, and ``n_unique`` is the
      true (unpadded) unique count.

    The sortedness of ``unique_keys`` is what lets the server side slice by
    key range with binary search (reference ``Parameter::Slice`` [U]).
    """
    uniq, inverse = np.unique(flat_keys(keys), return_inverse=True)
    n_unique = int(uniq.shape[0])
    if pad_to_bucket:
        cap = bucket_size(n_unique, min_bucket=min_bucket)
        if cap > n_unique:
            pad = np.full(cap - n_unique, PAD_KEY, dtype=uniq.dtype)
            uniq = np.concatenate([uniq, pad])
    return uniq, inverse.astype(np.int32), n_unique


def slice_by_ranges(
    sorted_keys: np.ndarray, range_bounds: np.ndarray
) -> np.ndarray:
    """Partition sorted keys into server key ranges.

    ``range_bounds`` is the ``num_servers + 1`` ascending boundary array from
    the NodeAssigner-style even split of the key space (reference
    ``src/system/assigner.h`` [U]).  Returns the ``num_servers + 1`` split
    indices into ``sorted_keys`` (use ``searchsorted`` semantics: server ``s``
    owns ``sorted_keys[idx[s]:idx[s+1]]``).
    """
    return np.searchsorted(sorted_keys, range_bounds, side="left")


def even_key_ranges(num_servers: int, key_space: int = 2**64) -> np.ndarray:
    """Evenly split ``[0, key_space)`` into ``num_servers`` contiguous ranges.

    Defaults to the full uint64 space (which :func:`localize_batch` produces —
    signed parser keys wrap into the top half).  The returned array has
    ``num_servers + 1`` bounds; since ``2**64`` itself is not representable,
    the final bound saturates to ``2**64 - 1`` (== :data:`PAD_KEY`) — PAD keys
    are excluded from server slicing anyway (callers slice ``uniq[:n]``).
    """
    if not (0 < key_space <= 2**64):
        raise ValueError("key_space must be in (0, 2**64]")
    step = key_space // num_servers
    bounds_py = [min(i * step, 2**64 - 1) for i in range(num_servers)]
    bounds_py.append(min(key_space, 2**64 - 1))
    return np.array(bounds_py, dtype=np.uint64)


def localize_to_slots(
    keys: np.ndarray, localizer: "Localizer", *, min_bucket: int = 256
) -> Tuple[np.ndarray, np.ndarray, int]:
    """Full host-side key pipeline: raw keys -> unique row slots + inverse.

    Returns ``(slots, inverse, n)``: sorted unique slot ids padded to a
    power-of-two bucket (pads point at the trash row ``capacity``),
    position->slot-row inverse, and the true unique-slot count.

    Two engines, one result (:func:`localize_engine` says which runs).  The
    definition composes :func:`localize_batch` with ``localizer.assign`` and
    then re-uniquifies the *slots* (two distinct keys may hash-share a slot;
    the device requires unique ids for the scatter fast path): two sorts of
    the positions.  A stateless localizer's slot is a pure function of the
    key, so for those "unique keys, assign, unique slots" and "assign every
    position, unique slots" are the same set and the same inverse, and the
    native pass (``native/src/keymap.cc`` :: ``ps_localize_slots``) hashes
    every position, dedups the slots through a table and sorts only the
    distinct ones: bit for bit the same arrays, collisions and ``PAD_KEY``
    included.  The stateful :class:`Localizer` hands out rows in the arrival
    order of the *sorted unique* keys, so there the composition is the
    definition and stays.
    """
    native = _native_pass(localizer)
    if native is not None:
        return _localize_native(*native, flat_keys(keys), localizer, min_bucket)
    uniq, key_inv, _ = localize_batch(
        keys, pad_to_bucket=False, min_bucket=min_bucket
    )
    raw_slots = localizer.assign(uniq)
    uniq_slots, slot_inv = np.unique(raw_slots, return_inverse=True)
    n = int(uniq_slots.shape[0])
    cap = bucket_size(n, min_bucket=min_bucket)
    if cap > n:
        uniq_slots = np.concatenate(
            [uniq_slots, np.full(cap - n, localizer.capacity, dtype=uniq_slots.dtype)]
        )
    inverse = slot_inv[key_inv].astype(np.int32)
    return uniq_slots.astype(np.int32, copy=False), inverse, n


def _native_pass(localizer):
    """``(lib, kind)`` of ``ps_localize_slots`` where the native pass runs
    for ``localizer``: it is one of the stateless classes themselves (a
    subclass may map otherwise) and the keymap library loaded.  None
    otherwise."""
    if type(localizer) is IdentityLocalizer:
        kind = 2
    elif type(localizer) is HashLocalizer:
        kind = 1 if localizer.hash_bits == 32 else 0
    else:
        return None
    lib = _keymap_lib()
    return None if lib is None else (lib, kind)


def localize_engine(localizer) -> str:
    """Which engine :func:`localize_to_slots` runs for ``localizer``:
    ``"native"`` (one pass over the positions) or ``"numpy"``."""
    return "numpy" if _native_pass(localizer) is None else "native"


def _localize_native(
    lib, kind: int, flat: np.ndarray, localizer, min_bucket: int
) -> Tuple[np.ndarray, np.ndarray, int]:
    """:func:`localize_to_slots` of flat ``uint64`` keys through
    ``ps_localize_slots``.  The seed goes through the NumPy scalar the
    Python class builds, so a seed it refuses is refused here the same."""
    import ctypes

    capacity = int(localizer.capacity)
    if kind == 2:
        seed = 0
    else:
        seed = int((np.uint32 if kind == 1 else np.uint64)(localizer.seed))
    inverse = np.empty(flat.shape[0], dtype=np.int32)
    bad = ctypes.c_uint64(0)
    n = lib.ps_localize_slots(
        kind,
        flat.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
        flat.shape[0],
        capacity,
        seed,
        inverse.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.byref(bad),
    )
    if n == -1:
        raise ValueError(
            f"IdentityLocalizer: key {bad.value} outside [0, "
            f"{capacity}) (dense-vocab tables take raw ids)"
        )
    if n < 0:
        raise MemoryError("ps_localize_slots: out of memory")
    slots = np.empty(bucket_size(n, min_bucket=min_bucket), dtype=np.int32)
    lib.ps_localize_take(
        slots.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        slots.shape[0],
        capacity,
    )
    return slots, inverse, int(n)


def localize_shard_native(
    lib,
    keys: np.ndarray,
    grows: int,
    shard_map: Tuple[np.ndarray, np.ndarray, np.ndarray],
    trash: int,
) -> Optional[Tuple[np.ndarray, np.ndarray, int, int]]:
    """A server's localization of one leg through ``ps_localize_shard``
    (``native/src/keymap.cc``): one pass over ``keys`` in the dtype they
    arrived in (``int32`` / ``int64``; anything else is first made the
    ``int64`` the definition compares in) against ``shard_map``, the
    contiguous ``int64`` ``(starts, ends, locals)`` of the owned segments.
    The call keeps the interpreter's lock (:func:`_keymap_lib` says why).

    Returns ``(local_ids int32, touched_segments int64, real, upto)``:
    ``real`` counts the keys below ``grows`` and ``upto`` is one past the
    last of them (what follows are pads, at ``trash``); or None where a
    real key is in no owned segment.  ``kv/server.py::_localize_numpy`` is
    the definition.
    """
    k = np.asarray(keys)
    if k.dtype not in (np.int32, np.int64) or not k.flags.c_contiguous:
        k = np.ascontiguousarray(k, dtype=np.int64)
    starts, ends, locs = shard_map
    nseg = starts.shape[0]
    out = np.empty(k.shape, dtype=np.int32)
    segs = np.empty(nseg, dtype=np.int64)
    counts = np.empty(2, dtype=np.int64)
    m = lib.ps_localize_shard_locked(
        k.ctypes.data, k.itemsize, k.size, grows,
        starts.ctypes.data, ends.ctypes.data, locs.ctypes.data, nseg,
        trash, out.ctypes.data, segs.ctypes.data, counts.ctypes.data,
    )
    if m < 0:
        return None
    return out, segs[:m], int(counts[0]), int(counts[1])


class HashLocalizer:
    """Stateless deterministic key -> slot mapping (the hashing trick).

    Multi-worker training requires every worker to map a global key to the
    *same* table row without coordination; a deterministic hash provides that
    (at the cost of collisions, which :func:`localize_to_slots` tolerates by
    re-uniquifying slots).  This is the standard large-vocabulary CTR/DLRM
    scheme and the multi-worker counterpart of :class:`Localizer`.
    """

    def __init__(self, capacity: int, seed: int = 0, hash_bits: int = 64):
        if not (0 < capacity < 2**31 - 1):
            raise ValueError(
                "capacity must fit int32 row ids (shard billion-row tables "
                "across servers / mesh axes instead)"
            )
        if hash_bits not in (32, 64):
            raise ValueError("hash_bits must be 32 or 64")
        self.capacity = capacity
        self.seed = seed
        #: 32 = murmur fmix32 on truncated keys, matching the device-side
        #: ``models.linear.mix32_jax`` (TPU has no uint64); keys must fit
        #: uint32 for collision behavior to stay key-space-uniform.
        self.hash_bits = hash_bits
        self.overflowed = True  # collisions always possible

    def assign(self, unique_keys: np.ndarray) -> np.ndarray:
        keys = np.asarray(unique_keys, dtype=np.uint64)
        if self.hash_bits == 32:
            slots = (
                mix32(keys.astype(np.uint32), np.uint32(self.seed))
                % np.uint32(self.capacity)
            ).astype(np.int32)
        else:
            slots = (
                mix64(keys, self.seed) % np.uint64(self.capacity)
            ).astype(np.int32)
        return np.where(keys == PAD_KEY, np.int32(self.capacity), slots)


class IdentityLocalizer:
    """Exact key == row-slot mapping for dense-vocabulary tables.

    Embedding tables (token id -> row) need every id to hit ITS OWN row —
    hashing would collide distinct tokens.  Keys must already be dense ids
    in ``[0, capacity)``; PAD_KEY maps to the trash row ``capacity``.
    """

    def __init__(self, capacity: int):
        if not (0 < capacity < 2**31 - 1):
            raise ValueError("capacity must fit int32 row ids")
        self.capacity = capacity
        self.overflowed = False

    def assign(self, unique_keys: np.ndarray) -> np.ndarray:
        keys = np.asarray(unique_keys, dtype=np.uint64)
        is_pad = keys == PAD_KEY
        # only PAD may reach the trash row (== capacity); a real key equal to
        # capacity must error, not silently alias pad updates
        bad = ~is_pad & (keys >= np.uint64(self.capacity))
        if bad.any():
            raise ValueError(
                f"IdentityLocalizer: key {int(keys[bad][0])} outside [0, "
                f"{self.capacity}) (dense-vocab tables take raw ids)"
            )
        return np.where(
            is_pad, np.int64(self.capacity), keys.astype(np.int64)
        ).astype(np.int32)


class _NativeKeyMap:
    """ctypes wrapper around the C++ keymap (``native/src/keymap.cc``)."""

    def __init__(self, lib, capacity: int) -> None:
        self._lib = lib
        self._h = lib.ps_keymap_new(capacity)
        if not self._h:
            raise MemoryError("ps_keymap_new failed")

    def assign(self, flat_keys: np.ndarray) -> np.ndarray:
        import ctypes

        flat_keys = np.ascontiguousarray(flat_keys, dtype=np.uint64)
        out = np.empty(flat_keys.shape[0], dtype=np.int32)
        self._lib.ps_keymap_assign(
            self._h,
            flat_keys.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            flat_keys.shape[0],
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        )
        return out

    def len(self) -> int:
        return int(self._lib.ps_keymap_len(self._h))

    def overflowed(self) -> bool:
        return bool(self._lib.ps_keymap_overflowed(self._h))

    def __del__(self) -> None:  # pragma: no cover — interpreter teardown
        h, self._h = getattr(self, "_h", None), None
        if h:
            try:
                self._lib.ps_keymap_free(h)
            except Exception:
                pass


def _keymap_lib():
    """The native keymap library (``native/src/keymap.cc``) with its
    signatures declared, or None (no toolchain, ``PS_NO_NATIVE``: the
    NumPy engines run)."""
    import ctypes

    from parameter_server_tpu import native

    lib = native.load("keymap")
    if lib is None:
        return None
    if not getattr(lib, "_ps_keymap_sigs", False):
        lib.ps_keymap_new.argtypes = [ctypes.c_int64]
        lib.ps_keymap_new.restype = ctypes.c_void_p
        lib.ps_keymap_free.argtypes = [ctypes.c_void_p]
        lib.ps_keymap_len.argtypes = [ctypes.c_void_p]
        lib.ps_keymap_len.restype = ctypes.c_int64
        lib.ps_keymap_overflowed.argtypes = [ctypes.c_void_p]
        lib.ps_keymap_assign.argtypes = [
            ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int32),
        ]
        lib.ps_localize_slots.argtypes = [
            ctypes.c_int,
            ctypes.POINTER(ctypes.c_uint64),
            ctypes.c_int64,
            ctypes.c_int64,
            ctypes.c_uint64,
            ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_uint64),
        ]
        lib.ps_localize_slots.restype = ctypes.c_int64
        lib.ps_localize_take.argtypes = [
            ctypes.POINTER(ctypes.c_int32),
            ctypes.c_int64,
            ctypes.c_int32,
        ]
        lib.ps_localize_take.restype = None
        # The server's pass is bound through PyDLL: the call KEEPS the
        # interpreter's lock.  It lasts tens of microseconds (7.7 k to 46 k
        # keys), less than handing the lock back and queueing for it behind
        # a process's other threads costs a recv thread: through CDLL the
        # stage read 0.54 ms a leg in ``dlrm_emb.skew.x4`` and 0.51 in
        # ``criteo_lr.skew``, through PyDLL 0.12 and 0.19 (PERF.md §6, PR 38).
        shard = ctypes.PyDLL(lib._name).ps_localize_shard
        shard.argtypes = (
            [ctypes.c_void_p, ctypes.c_int]
            + [ctypes.c_int64] * 2
            + [ctypes.c_void_p] * 3
            + [ctypes.c_int64, ctypes.c_int32]
            + [ctypes.c_void_p] * 3
        )
        shard.restype = ctypes.c_int64
        lib.ps_localize_shard_locked = shard
        lib._ps_keymap_sigs = True
    return lib


def _native_keymap(capacity: int):
    """The native keymap engine, or None (numpy fallback)."""
    lib = _keymap_lib()
    return None if lib is None else _NativeKeyMap(lib, capacity)


class Localizer:
    """Persistent global-key -> stable dense row-slot mapping.

    Streaming learners (async SGD / FTRL over an unbounded key stream) need a
    key to map to the *same* table row every time so its optimizer state
    accumulates.  The reference keeps this in the server's hash map
    (``src/parameter/kv_map.h`` :: ``KVMap`` [U]); on TPU the table is a fixed
    ``[capacity, dim]`` HBM array, so the hash lives on the host and hands the
    device dense row ids.

    When the vocabulary overflows ``capacity``, new keys hash-share rows
    (feature hashing) rather than erroring — matching large-scale CTR practice
    and the reference's countmin-based tail filtering spirit.

    The mapping is a flat open-addressing hash table (linear probing, load
    factor <= 1/2) with two interchangeable engines: the native C++ one
    (``native/src/keymap.cc``, the reference's KVMap/Localizer analogue —
    ~10-20x the old per-key dict loop) and a vectorized numpy fallback
    (windowed batch probing) for toolchain-less hosts.  A per-key Python
    dict loop was the measured host bottleneck at Criteo batch rates
    (VERDICT r1 weak #3).
    """

    #: empty bucket sentinel in the probe table (PAD_KEY never enters it —
    #: assign() short-circuits pads to the trash row first).
    _EMPTY = PAD_KEY
    #: probe window: each vectorized round inspects W consecutive buckets
    #: per key, so a linear-probe cluster walk of length L costs ceil(L/W)
    #: rounds instead of L (rounds are the Python-level cost driver).
    _W = 8

    def __init__(self, capacity: int):
        if not (0 < capacity < 2**31 - 1):
            raise ValueError("capacity must be positive and fit int32 row ids")
        self.capacity = capacity
        self._native = _native_keymap(capacity)
        if self._native is None:
            self._size = 1 << 16
            self._tkeys = np.full(self._size, self._EMPTY, dtype=np.uint64)
            self._tvals = np.zeros(self._size, dtype=np.int32)
        self._n = 0
        self._overflowed = False

    def __len__(self) -> int:
        if self._native is not None:
            return self._native.len()
        return self._n

    @property
    def overflowed(self) -> bool:
        if self._native is not None:
            return self._native.overflowed()
        return self._overflowed

    def _lookup(self, keys: np.ndarray) -> np.ndarray:
        """Vectorized windowed probe: slot for each key, -1 where absent."""
        mask = np.int64(self._size - 1)
        offs = np.arange(self._W, dtype=np.int64)
        pos = (mix64(keys) & np.uint64(mask)).astype(np.int64)
        vals = np.full(keys.shape[0], -1, dtype=np.int32)
        active = np.arange(keys.shape[0])
        while active.size:
            win = (pos[active][:, None] + offs) & mask  # [n, W]
            cur = self._tkeys[win]
            hit = cur == keys[active][:, None]
            stop = hit | (cur == self._EMPTY)  # absent iff EMPTY before hit
            stopped = stop.any(axis=1)
            first = stop.argmax(axis=1)
            rows = np.nonzero(stopped)[0]
            is_hit = hit[rows, first[rows]]
            hrows = rows[is_hit]
            vals[active[hrows]] = self._tvals[win[hrows, first[hrows]]]
            cont = active[~stopped]
            pos[cont] = (pos[cont] + self._W) & mask
            active = cont
        return vals

    def _insert(self, keys: np.ndarray, vals: np.ndarray) -> None:
        """Vectorized insert of NEW unique keys (callers grow first)."""
        mask = np.int64(self._size - 1)
        offs = np.arange(self._W, dtype=np.int64)
        pos = (mix64(keys) & np.uint64(mask)).astype(np.int64)
        remaining = np.arange(keys.shape[0])
        while remaining.size:
            win = (pos[remaining][:, None] + offs) & mask
            empty = self._tkeys[win] == self._EMPTY
            has_empty = empty.any(axis=1)
            # fully occupied window: jump that key ahead by W
            full = remaining[~has_empty]
            pos[full] = (pos[full] + self._W) & mask
            rows = np.nonzero(has_empty)[0]
            if rows.size:
                # claim each key's first empty bucket; duplicate targets
                # resolve by numpy scatter last-writer-wins, verified by
                # re-gather (keys are unique, so the winner re-reads itself).
                # Losers re-probe the SAME window next round: the bucket they
                # lost is occupied now, so they fall to a later empty slot.
                target = win[rows, empty[rows].argmax(axis=1)]
                cand = remaining[rows]
                self._tkeys[target] = keys[cand]
                self._tvals[target] = vals[cand]
                won = self._tkeys[target] == keys[cand]
                keep = np.zeros(keys.shape[0], dtype=bool)
                keep[remaining] = True
                keep[cand[won]] = False
                remaining = remaining[keep[remaining]]
            else:
                remaining = full

    def _grow_for(self, n_new: int) -> None:
        grew = False
        while (self._n + n_new) * 2 > self._size:
            self._size *= 2
            grew = True
        if grew:
            live = self._tkeys != self._EMPTY
            old_keys = self._tkeys[live]
            old_vals = self._tvals[live]
            self._tkeys = np.full(self._size, self._EMPTY, dtype=np.uint64)
            self._tvals = np.zeros(self._size, dtype=np.int32)
            if old_keys.size:
                self._insert(old_keys, old_vals)

    def assign(self, unique_keys: np.ndarray) -> np.ndarray:
        """Map unique global keys to row slots, growing the vocab as needed.

        PAD_KEY maps to slot ``capacity`` (the trash row — tables allocate
        ``capacity + 1`` rows; see ops.scatter).  Slot order matches the
        sequential first-appearance order of the old dict implementation:
        new keys get ids ``len(self)..`` in batch order.
        """
        keys = np.asarray(unique_keys, dtype=np.uint64)
        flat = keys.ravel()
        if self._native is not None:
            return self._native.assign(flat).reshape(keys.shape)
        out = np.empty(flat.shape[0], dtype=np.int32)
        is_pad = flat == PAD_KEY
        out[is_pad] = self.capacity
        real = np.nonzero(~is_pad)[0]
        rk = flat[real]
        vals = self._lookup(rk)
        missing = vals < 0
        if missing.any():
            new_keys = rk[missing]
            # dedup first (the contract says unique keys, but duplicates must
            # still share ONE slot, like the native engine / old dict — else
            # a dupe would burn an unreachable vocab row); slots are handed
            # out in first-appearance order
            uniq_new, first_idx, inv = np.unique(
                new_keys, return_index=True, return_inverse=True
            )
            arrival = np.argsort(first_idx, kind="stable")
            rank = np.empty(arrival.size, dtype=np.int64)
            rank[arrival] = np.arange(arrival.size)
            n_take = min(max(self.capacity - self._n, 0), arrival.size)
            taken = rank < n_take
            slots_u = np.empty(arrival.size, dtype=np.int32)
            slots_u[taken] = (self._n + rank[taken]).astype(np.int32)
            if n_take < arrival.size:
                # Feature-hashing fallback on overflow. Deterministic pure
                # function of the key — deliberately NOT cached, so host
                # memory stays bounded by ``capacity`` on unbounded
                # streaming key sets.
                self._overflowed = True
                slots_u[~taken] = (
                    uniq_new[~taken] % np.uint64(self.capacity)
                ).astype(np.int32)
            if n_take:
                self._grow_for(n_take)
                self._insert(uniq_new[taken], slots_u[taken])
                self._n += n_take
            vals[missing] = slots_u[inv]
        out[real] = vals
        return out.reshape(keys.shape)


def localizer_meta(loc) -> dict:
    """Reconstruction metadata for a localizer (checkpoint manifest extras).

    A checkpointed table is only servable with the SAME key->row mapping it
    was trained with (the reference writes raw key ranges so the mapping is
    the identity; here the mapping is a host-side function and must be
    recorded alongside the shards — VERDICT r2 weak #5).
    """
    meta = {"kind": type(loc).__name__, "capacity": int(loc.capacity)}
    if isinstance(loc, HashLocalizer):
        meta["seed"] = int(loc.seed)
        meta["hash_bits"] = int(loc.hash_bits)
    return meta


def localizer_from_meta(meta: dict):
    """Rebuild the key->row mapping recorded by :func:`localizer_meta`.

    Only deterministic localizers reconstruct (``HashLocalizer``,
    ``IdentityLocalizer``); the stateful :class:`Localizer` depends on key
    arrival order, which the checkpoint does not capture — pass the live
    instance (or re-stream the training keys) instead.
    """
    kind = meta.get("kind")
    if kind == "HashLocalizer":
        return HashLocalizer(
            int(meta["capacity"]),
            seed=int(meta.get("seed", 0)),
            hash_bits=int(meta.get("hash_bits", 64)),
        )
    if kind == "IdentityLocalizer":
        return IdentityLocalizer(int(meta["capacity"]))
    raise ValueError(
        f"cannot reconstruct localizer from meta {meta!r} (stateful "
        "Localizer mappings are arrival-order-dependent; pass the instance)"
    )
