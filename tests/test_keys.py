import sys
import threading

import numpy as np
import pytest

from parameter_server_tpu.utils.keys import (
    PAD_KEY,
    HashLocalizer,
    IdentityLocalizer,
    Localizer,
    bucket_size,
    even_key_ranges,
    localize_batch,
    localize_engine,
    localize_to_slots,
    slice_by_ranges,
)
from parameter_server_tpu.utils.countmin import CountMin


def test_bucket_size_powers_of_two():
    assert bucket_size(1) == 256
    assert bucket_size(256) == 256
    assert bucket_size(257) == 512
    assert bucket_size(1000) == 1024
    assert bucket_size(1024) == 1024


def test_localize_batch_roundtrip():
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 10_000, size=(32, 17), dtype=np.uint64)
    uniq, inv, n = localize_batch(keys)
    # inverse reconstructs the input
    np.testing.assert_array_equal(uniq[inv].reshape(keys.shape), keys)
    # sortedness (excluding pad tail)
    assert np.all(np.diff(uniq[:n].astype(np.int64)) > 0)
    # padding
    assert uniq.shape[0] == bucket_size(n)
    assert np.all(uniq[n:] == PAD_KEY)


def test_localize_batch_no_pad():
    uniq, inv, n = localize_batch(np.array([5, 3, 5, 1]), pad_to_bucket=False)
    np.testing.assert_array_equal(uniq, [1, 3, 5])
    assert n == 3


def test_slice_by_ranges():
    bounds = even_key_ranges(4, key_space=100)
    keys = np.array([0, 10, 24, 25, 30, 70, 99], dtype=np.uint64)
    idx = slice_by_ranges(keys, bounds)
    # server 0 owns [0,25): keys 0,10,24
    assert idx[0] == 0 and idx[1] == 3
    # server 1 owns [25,50): keys 25,30
    assert idx[2] == 5
    # server 3 owns [75,100): key 99
    assert idx[3] == 6 and idx[4] == 7


def test_localizer_stable_slots():
    loc = Localizer(capacity=100)
    a = loc.assign(np.array([7, 3, 9], dtype=np.uint64))
    b = loc.assign(np.array([9, 7, 11], dtype=np.uint64))
    assert b[0] == a[2] and b[1] == a[0]  # same key -> same slot
    assert len(loc) == 4
    assert not loc.overflowed


def test_localizer_pad_key_to_trash_row():
    loc = Localizer(capacity=10)
    slots = loc.assign(np.array([1, PAD_KEY], dtype=np.uint64))
    assert slots[1] == 10  # trash row == capacity


def test_localizer_overflow_hashes():
    loc = Localizer(capacity=4)
    slots = loc.assign(np.arange(10, dtype=np.uint64))
    assert loc.overflowed
    assert np.all(slots < 4)
    # stable even after overflow
    again = loc.assign(np.arange(10, dtype=np.uint64))
    np.testing.assert_array_equal(slots, again)


def test_even_key_ranges_full_uint64():
    bounds = even_key_ranges(4)  # default: full uint64 space
    assert bounds[0] == 0 and bounds[-1] == np.uint64(2**64 - 1)
    # a top-bit-set key (e.g. wrapped signed key) is owned by the last server
    keys = np.array([2**63 + 5], dtype=np.uint64)
    idx = slice_by_ranges(keys, bounds)
    assert idx[2] == 0 and idx[3] == 1  # falls in server 2's range [2^63, 3*2^62)


def test_localizer_bounded_after_overflow():
    loc = Localizer(capacity=4)
    loc.assign(np.arange(1000, dtype=np.uint64))
    # dict stays bounded by capacity; overflow keys hash, not cached
    assert len(loc) == 4 and loc.overflowed


def test_localizer_bad_capacity():
    with pytest.raises(ValueError):
        Localizer(capacity=0)


def test_countmin_never_undercounts():
    cm = CountMin(width=1 << 12, depth=4)
    rng = np.random.default_rng(1)
    keys = rng.integers(0, 500, size=5000, dtype=np.uint64)
    cm.add(keys)
    true_counts = np.bincount(keys.astype(np.int64), minlength=500)
    est = cm.query(np.arange(500, dtype=np.uint64))
    assert np.all(est >= true_counts)
    # with a wide sketch estimates should be close
    assert np.mean(est - true_counts) < 1.0


def test_countmin_filter():
    cm = CountMin(width=1 << 12, depth=4)
    cm.add(np.array([42] * 10 + [7], dtype=np.uint64))
    mask = cm.filter(np.array([42, 7, 99], dtype=np.uint64), threshold=5)
    assert mask.tolist() == [True, False, False]


def test_localizer_engines_agree(monkeypatch):
    """Native C++ keymap and the numpy fallback produce identical slot
    streams — sequential ids, overflow hashing, PAD, duplicates sharing a
    slot, and table growth/rehash (vocab crosses both engines' initial
    1<<16 table at load factor 1/2)."""
    from parameter_server_tpu.utils import keys as keys_mod

    native = Localizer(capacity=50_000)
    if native._native is None:  # pragma: no cover — toolchain-less host
        pytest.skip("no native toolchain")
    # real constructor, numpy engine (native.load caches per process, so
    # PS_NO_NATIVE can't flip it here — stub the factory instead)
    monkeypatch.setattr(keys_mod, "_native_keymap", lambda cap: None)
    fallback = Localizer(capacity=50_000)
    assert fallback._native is None

    rng = np.random.default_rng(3)
    for i in range(20):
        n = int(rng.integers(1, 4000))
        batch = np.unique(rng.integers(0, 2**62, size=n).astype(np.uint64))
        if i % 3 == 0:
            batch = np.concatenate([batch, [PAD_KEY]])
        if i % 4 == 0 and batch.size > 2:  # duplicates share one slot
            batch = np.concatenate([batch, batch[:2]])
        np.testing.assert_array_equal(
            native.assign(batch), fallback.assign(batch)
        )
    assert len(native) == len(fallback) > (1 << 16) // 2  # growth exercised
    assert native.overflowed == fallback.overflowed


def test_localizer_duplicate_new_keys_share_slot():
    loc = Localizer(capacity=100)
    out = loc.assign(np.array([5, 5, 7], dtype=np.uint64))
    assert out.tolist() == [0, 0, 1]
    assert len(loc) == 2


# -- localize_to_slots: the native one-pass engine against the definition --

#: kind -> a localizer of ``capacity``.  The capacities below reach the
#: radix sort's highest digit (2^31 - 2) and a modulus that is no power of two.
STATELESS = {
    "hash64": lambda capacity: HashLocalizer(capacity, seed=5),
    "hash32": lambda capacity: HashLocalizer(capacity, seed=5, hash_bits=32),
    "identity": IdentityLocalizer,
}
CAPACITY = {"hash64": (1 << 31) - 2, "hash32": 100_003, "identity": 100_003}


def _with_pads(rng, hi):
    keys = (rng.zipf(1.3, 4000) % hi).astype(np.uint64)
    keys[rng.choice(keys.size, 300, replace=False)] = PAD_KEY
    return keys


def _as_int64(rng, hi):
    keys = (rng.zipf(1.3, 4000) % hi).astype(np.int64)
    keys[::17] = -1  # a signed parser's pad: coerced to PAD_KEY
    return keys


#: batch -> (rng, exclusive upper end of the keys) -> keys
BATCHES = {
    "zipf_with_duplicates": lambda rng, hi: rng.zipf(1.3, 6000) % hi,
    "all_distinct": lambda rng, hi: rng.choice(hi, 3000, replace=False),
    "one_key_repeated": lambda rng, hi: np.full(777, hi - 1),
    "a_single_key": lambda rng, hi: np.array([hi // 2]),
    "empty": lambda rng, hi: np.zeros(0, np.uint64),
    "with_pad_positions": _with_pads,
    "colliding_in_a_small_capacity": lambda rng, hi: rng.integers(0, hi, 500),
    "two_dimensional": lambda rng, hi: (rng.zipf(1.2, 64 * 39) % hi).reshape(64, 39),
    "int64_input": _as_int64,
    "strided_ids": lambda rng, hi: np.arange(0, min(hi, 1 << 20), 64),
}


def _stateless_case(kind, batch, seed=0):
    capacity = 13 if batch.startswith("colliding") else CAPACITY[kind]
    hi = capacity if kind == "identity" else 1 << 40
    keys = np.asarray(BATCHES[batch](np.random.default_rng(seed), hi))
    if keys.dtype != np.int64:
        keys = keys.astype(np.uint64)
    return keys, STATELESS[kind](capacity)


def _numpy_engine(monkeypatch, keys, localizer, min_bucket):
    """``localize_to_slots`` as it runs where the library did not load."""
    from parameter_server_tpu.utils import keys as keys_mod

    with monkeypatch.context() as m:
        m.setattr(keys_mod, "_keymap_lib", lambda: None)
        assert localize_engine(localizer) == "numpy"
        return localize_to_slots(keys, localizer, min_bucket=min_bucket)


def _assert_same_localization(got, want):
    for a, b in zip(got[:2], want[:2]):
        assert a.dtype == b.dtype == np.int32 and a.shape == b.shape
        assert a.tobytes() == b.tobytes()
    assert type(got[2]) is type(want[2]) is int and got[2] == want[2]


@pytest.fixture
def native_keymap():
    from parameter_server_tpu.utils import keys as keys_mod

    if keys_mod._keymap_lib() is None:  # pragma: no cover
        pytest.skip("no native toolchain")


@pytest.mark.parametrize("min_bucket", [256, 8])
@pytest.mark.parametrize("batch", list(BATCHES))
@pytest.mark.parametrize("kind", list(STATELESS))
def test_native_localization_is_the_numpy_one_bit_for_bit(
    kind, batch, min_bucket, native_keymap, monkeypatch
):
    keys, loc = _stateless_case(kind, batch)
    assert localize_engine(loc) == "native"
    got = localize_to_slots(keys, loc, min_bucket=min_bucket)
    want = _numpy_engine(monkeypatch, keys, loc, min_bucket)
    _assert_same_localization(got, want)
    slots, inverse, n = got
    assert slots.shape == (bucket_size(n, min_bucket=min_bucket),)
    assert inverse.shape == (keys.size,)
    assert np.all(slots[n:] == loc.capacity)
    assert np.all(np.diff(slots[:n]) > 0)
    # every position reads its own key's slot back
    flat = keys.ravel().astype(np.uint64)
    assert np.array_equal(slots[inverse], loc.assign(flat))
    if batch.startswith("colliding") and kind != "identity":
        assert n < np.unique(flat).size  # distinct keys do share slots


#: keys an IdentityLocalizer(100) refuses -> the key its error names
OUT_OF_RANGE = {
    "one_key": ([3, 100, 7], 100),
    "the_smallest_of_several": ([5000, 3, 250, 7, 250, 101], 101),
    "beside_pads": ([int(PAD_KEY), 1 << 63, 2, int(PAD_KEY)], 1 << 63),
}


@pytest.mark.parametrize("case", list(OUT_OF_RANGE))
def test_identity_out_of_range_raises_the_same_from_both_engines(
    case, native_keymap, monkeypatch
):
    keys, named = OUT_OF_RANGE[case]
    keys, loc = np.array(keys, np.uint64), IdentityLocalizer(100)
    with pytest.raises(ValueError) as native:
        localize_to_slots(keys, loc)
    with pytest.raises(ValueError) as fallback:
        _numpy_engine(monkeypatch, keys, loc, 256)
    assert str(native.value) == str(fallback.value)
    assert f"key {named} outside [0, 100)" in str(native.value)
    # the refused call leaves nothing behind in the thread's scratch
    ok = np.array([1, 1, 0], np.uint64)
    _assert_same_localization(
        localize_to_slots(ok, loc), _numpy_engine(monkeypatch, ok, loc, 256)
    )


class _ShiftedHash(HashLocalizer):
    def assign(self, unique_keys):
        return (super().assign(unique_keys) + 1) % np.int32(self.capacity)


@pytest.mark.parametrize(
    "make", [lambda: Localizer(64), lambda: _ShiftedHash(64)],
    ids=["stateful", "a_subclass_with_its_own_assign"],
)
def test_other_localizers_keep_the_numpy_path_and_their_results(make):
    """Only the stateless classes themselves take the native pass: the
    stateful ``Localizer`` hands out rows in the order of the sorted
    unique keys (overflow hashing past its capacity), which the two
    ``np.unique`` define, and a subclass may map as it likes."""
    rng = np.random.default_rng(4)
    loc, twin = make(), make()
    assert localize_engine(loc) == "numpy"
    for _ in range(3):  # the third batch overflows the stateful capacity
        keys = rng.integers(0, 1 << 40, 60).astype(np.uint64)
        keys[::9] = PAD_KEY
        slots, inverse, n = localize_to_slots(keys, loc, min_bucket=8)
        uniq, key_inv = np.unique(keys, return_inverse=True)
        want_slots, slot_inv = np.unique(twin.assign(uniq), return_inverse=True)
        assert n == want_slots.size and np.array_equal(slots[:n], want_slots)
        assert np.all(slots[n:] == 64) and slots.dtype == inverse.dtype == np.int32
        assert np.array_equal(inverse, slot_inv[key_inv])


def test_with_ps_no_native_the_numpy_engine_runs(monkeypatch):
    from parameter_server_tpu import native

    keys, loc = _stateless_case("hash64", "zipf_with_duplicates")
    want = localize_to_slots(keys, loc)
    monkeypatch.setattr(native, "_cache", {})  # load() caches per process
    monkeypatch.setenv("PS_NO_NATIVE", "1")
    assert localize_engine(loc) == "numpy"
    assert native.loaded() == {"keymap": False}
    _assert_same_localization(localize_to_slots(keys, loc), want)


@pytest.mark.parametrize("threads, rounds", [(2, 200), (16, 25)])
def test_threads_localize_through_their_own_native_scratch(
    threads, rounds, native_keymap, monkeypatch
):
    """The dedup table and the sorted slots are kept per calling thread and
    ctypes releases the GIL for the call: threads that localize different
    batches at once (sizes apart, so the tables differ) each get their own
    batch's result, between ``ps_localize_slots`` and ``ps_localize_take``
    too."""
    cases = [
        _stateless_case(kind, batch, seed=s)
        for s, (kind, batch) in enumerate(
            [("hash64", "zipf_with_duplicates"), ("identity", "all_distinct"),
             ("hash32", "with_pad_positions"), ("hash64", "a_single_key")]
        )
    ]
    want = [_numpy_engine(monkeypatch, k, loc, 8) for k, loc in cases]
    wrong = []

    def loop(offset):
        for r in range(rounds):
            i = (offset + r * (1 + offset % 3)) % len(cases)
            got = localize_to_slots(*cases[i], min_bucket=8)
            if not (got[2] == want[i][2]
                    and got[0].tobytes() == want[i][0].tobytes()
                    and got[1].tobytes() == want[i][1].tobytes()):
                wrong.append((offset, r, i))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pool = [threading.Thread(target=loop, args=(t,)) for t in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(interval)
    assert not wrong
