"""A batch's keys are localized once a step (``kv/worker.py``).

``KVWorker._localize`` keeps the newest localization it computed for a
table and hands it back when it is asked for the same keys again: same
table, same localizer object, same ``min_bucket``, keys equal by content
once flattened as ``uint64``.  So a step's pull computes and its push
reuses, and everything downstream (pulled rows, the combined plane, the
legs on the wire) is bit for bit what two independent
``localize_to_slots`` calls give.
"""

import sys
import threading
import time

import numpy as np
import pytest

import jax

from parameter_server_tpu.config import OptimizerConfig, TableConfig
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.kv import worker as worker_mod
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.kv.worker import KVWorker
from parameter_server_tpu.utils.keys import (
    HashLocalizer,
    IdentityLocalizer,
    Localizer,
    localize_to_slots,
)
from parameter_server_tpu.utils.trace import Tracer

ROWS, SERVERS, MIN_BUCKET = 1 << 12, 2, 16

#: kind -> (a fresh localizer, the largest key it takes)
LOCALIZERS = {
    "hash64": (lambda: HashLocalizer(ROWS), 1 << 40),
    "hash32": (lambda: HashLocalizer(ROWS, hash_bits=32), 1 << 31),
    "identity": (lambda: IdentityLocalizer(ROWS), ROWS),
    "stateful": (lambda: Localizer(ROWS), 1 << 40),
}


class _LossyVan(LoopbackVan):
    """Says it sent, and loses, the next ``lose`` requests to ``S0``."""

    lose = 0

    def send(self, msg):
        if self.lose and msg.recver == "S0" and msg.is_request:
            self.lose -= 1
            return True
        return super().send(msg)


def _cluster(kind="hash64", dim=8, tables=("t",)):
    van = _LossyVan()
    cfgs = {
        t: TableConfig(
            name=t, rows=ROWS, dim=dim, init_scale=0.1,
            optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
        )
        for t in tables
    }
    for i in range(SERVERS):
        KVServer(Postoffice(f"S{i}", van), cfgs, i, SERVERS)
    worker = KVWorker(
        Postoffice("W1", van), cfgs, SERVERS, min_bucket=MIN_BUCKET,
        localizers={t: LOCALIZERS[kind][0]() for t in tables},
        tracer=Tracer(),
    )
    return van, worker


def _batch(kind="hash64", dim=8, seed=11):
    """``[2, 96]`` keys with duplicates, and a gradient row a position."""
    rng = np.random.default_rng(seed)
    pool = rng.choice(LOCALIZERS[kind][1], size=60, replace=False)
    keys = rng.choice(pool, size=(2, 96)).astype(np.uint64)
    grads = rng.standard_normal((keys.size, dim)).astype(np.float32)
    return keys, grads


@pytest.fixture
def calls(monkeypatch):
    """The key arrays ``localize_to_slots`` was called with, in order."""
    seen = []
    real = worker_mod.localize_to_slots

    def counted(keys, localizer, **kw):
        seen.append(np.array(keys))
        return real(keys, localizer, **kw)

    monkeypatch.setattr(worker_mod, "localize_to_slots", counted)
    return seen


def _record_legs(worker):
    """Every request leg the worker submits: kind, receiver, the leg's ids
    and its value planes, as bytes at the time of the submit."""
    legs = []
    submit = worker.submit

    def recording(msgs, **kw):
        for m in msgs:
            legs.append((
                m.task.kind, m.recver, m.keys.tobytes(),
                [np.asarray(v).tobytes() for v in m.values],
            ))
        return submit(msgs, **kw)

    worker.submit = recording
    return legs


@pytest.mark.parametrize("dim", [1, 128])
@pytest.mark.parametrize("kind", list(LOCALIZERS))
def test_pull_then_push_localizes_once_and_is_bit_identical(kind, dim, calls):
    keys, grads = _batch(kind, dim)
    seen = {}
    for how in ("reused", "independent"):
        van, worker = _cluster(kind, dim)
        try:
            legs = _record_legs(worker)
            del calls[:]
            rows = np.asarray(worker.pull_sync("t", keys, timeout=30))
            if how == "independent":
                worker._localized.clear()  # the push computes from scratch
            slots, combined = worker._prepare_push("t", keys, grads)
            worker.push_sync("t", keys, grads, timeout=30)
            after = np.asarray(worker.pull_sync("t", keys, timeout=30))
            assert len(calls) == (1 if how == "reused" else 2)
            seen[how] = (rows, np.array(slots), combined, after, legs)
        finally:
            van.close()
    got, want = seen["reused"], seen["independent"]
    assert got[0].shape == keys.shape + ((dim,) if dim > 1 else ())
    for a, b in zip(got[:4], want[:4]):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()
    assert got[4] == want[4] and len(got[4]) == 3 * SERVERS
    assert np.any(got[3] != got[0])  # the push landed


#: the same keys as the push hands them over -> still a hit
SAME_KEYS = {
    "an_equal_copy": lambda k: k.copy(),
    "reshaped_flat": lambda k: k.reshape(-1),
    "as_int64": lambda k: k.astype(np.int64),
    "as_int32": lambda k: k.astype(np.int32),
    "in_fortran_order": lambda k: np.asfortranarray(k),
}


@pytest.mark.parametrize("how", list(SAME_KEYS))
def test_the_same_keys_in_another_array_hit(how, calls):
    keys, grads = _batch("hash32")  # keys that every integer dtype holds
    van, worker = _cluster("hash32")
    try:
        worker.pull_sync("t", keys, timeout=30)
        again = SAME_KEYS[how](keys)
        assert again is not keys
        worker.push_sync("t", again, grads, timeout=30)
        assert len(calls) == 1
        c = worker.counters()
        assert (c["localize_computed"], c["localize_reused"]) == (1, 1)
    finally:
        van.close()


def _refill_in_place(worker, keys):
    keys[0, :7] = keys[0, 7:14]
    return "t", keys


def _replace_localizer(worker, keys):
    worker.localizers["t"] = HashLocalizer(ROWS)
    return "t", keys


def _another_min_bucket(worker, keys):
    worker.min_bucket = 2 * MIN_BUCKET
    return "t", keys


#: what changes between the pull and the push -> a miss
OTHER_INPUT = {
    "the_buffer_refilled_in_place": _refill_in_place,
    "another_count_of_keys": lambda w, k: ("t", k[:, :-1]),
    "another_table": lambda w, k: ("u", k),
    "a_replaced_localizer": _replace_localizer,
    "another_min_bucket": _another_min_bucket,
}


@pytest.mark.parametrize("how", list(OTHER_INPUT))
def test_another_input_misses(how, calls):
    keys, _ = _batch()
    van, worker = _cluster(tables=("t", "u"))
    try:
        worker.pull_sync("t", keys, timeout=30)
        first = worker._localized["t"]
        table, pushed = OTHER_INPUT[how](worker, keys)
        grads = np.ones((pushed.size, 8), np.float32)
        worker.push_sync(table, pushed, grads, timeout=30)
        assert len(calls) == 2
        assert calls[1].tobytes() == pushed.ravel().tobytes()
        c = worker.counters()
        assert (c["localize_computed"], c["localize_reused"]) == (2, 0)
        # the newest computed replaces the table's entry, and only that one
        assert (worker._localized["t"] is first) == (table == "u")
        assert worker._localized[table].keys.tobytes() == pushed.tobytes()
    finally:
        van.close()


def test_the_kept_arrays_refuse_writes_and_the_keys_are_a_private_copy():
    keys, _ = _batch()
    van, worker = _cluster()
    try:
        slots, inverse = worker._localize("t", keys)
        held = worker._localized["t"].keys
        assert not np.shares_memory(held, keys)
        for arr in (slots, inverse):
            with pytest.raises(ValueError, match="read-only"):
                arr[0] = 0
        again = worker._localize("t", keys.copy())
        assert again[0] is slots and again[1] is inverse
    finally:
        van.close()


def test_threads_on_one_worker_get_their_own_keys_localization():
    """A serving thread (``pull_stale``) beside the training loop: the
    entry is swapped whole, so a call returns the pair of ITS keys, never
    one batch's keys beside another's slots."""
    van, worker = _cluster()
    batches = [_batch(seed=s)[0] for s in range(8)]
    want = [
        localize_to_slots(k, HashLocalizer(ROWS), min_bucket=MIN_BUCKET)[:2]
        for k in batches
    ]
    wrong, stop = [], time.monotonic() + 1.0

    def loop(offset):
        i = offset
        while time.monotonic() < stop and not wrong:
            i = (i + 1) % len(batches)
            for _pull_then_push in range(2):
                slots, inverse = worker._localize("t", batches[i])
                if not (np.array_equal(slots, want[i][0])
                        and np.array_equal(inverse, want[i][1])):
                    wrong.append(i)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=loop, args=(n,)) for n in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
        van.close()
    assert not wrong
    c = worker.counters()
    assert c["localize_computed"] > 0 and c["localize_reused"] > 0


def test_a_deadline_retry_of_a_pull_reissues_from_its_plan(calls):
    keys, _ = _batch()
    van, worker = _cluster()
    try:
        want = np.asarray(worker.pull_sync("t", keys, timeout=30))
        van.lose = 1
        ts = worker.pull("t", keys)  # reused; S0's leg is lost
        assert not worker.wait(ts, timeout=0.2)
        # another batch in between: the retry reads its plan, not the memo
        worker._localize("t", keys[:, :5])
        got = np.asarray(worker.pull_result(ts, timeout=1))
        assert worker.pull_retries == 1
        assert got.tobytes() == want.tobytes()
        assert len(calls) == 2 and calls[1].size == 10
        assert worker.pending_count() == 0
    finally:
        van.close()


def test_counters_and_spans_read_as_the_loops_imply():
    van, worker = _cluster()
    try:
        batches = [_batch(seed=s) for s in (1, 2, 3)]
        # the PS cells' loop: pull, gradient, push of one batch's keys
        for keys, grads in batches:
            worker.pull_sync("t", keys, timeout=30)
            worker.push_sync("t", keys, grads, timeout=30)
        c = worker.counters()
        assert (c["localize_computed"], c["localize_reused"]) == (3, 3)
        spans = worker.tracer.spans
        assert len(spans("ps.worker.localize")) == 3
        assert [s[4]["localize"] for s in spans("ps.worker.pull")] == (
            ["computed"] * 3
        )
        assert [s[4]["localize"] for s in spans("ps.worker.push")] == (
            ["reused"] * 3
        )
        assert all(
            s[4]["keys"] == 192 and s[4]["unique"] <= 60
            for s in spans("ps.worker.localize")
        )
        # the replayed pool: a pull of a batch seen two steps ago computes
        worker.pull_sync("t", batches[0][0], timeout=30)
        assert worker.counters()["localize_computed"] == 4

        # the hybrid trainer's order: push of batch t, then the prefetching
        # pull of batch t + 1, pushed flat and from the device
        worker.tracer.clear()
        pending = worker.pull("t", batches[0][0])  # the keys just pulled
        assert worker.counters()["localize_reused"] == 4
        for (keys, grads), (nxt, _g) in zip(batches, batches[1:] + batches[:1]):
            worker.pull_result(pending, timeout=30)
            worker.wait(
                worker.push_device(
                    "t", keys.reshape(-1), jax.device_put(grads, worker.device)
                ),
                timeout=30,
            )
            pending = worker.pull("t", nxt)
        worker.pull_result(pending, timeout=30)
        c = worker.counters()
        assert (c["localize_computed"], c["localize_reused"]) == (7, 7)
        assert [s[4]["localize"] for s in spans("ps.worker.push")] == (
            ["reused"] * 3
        )
        assert len(spans("ps.worker.localize")) == 3
    finally:
        van.close()


#: localizer kind, whether the keymap library loads -> the engine that runs
ENGINES = [
    ("hash64", True, "native"),
    ("hash32", True, "native"),
    ("identity", True, "native"),
    ("stateful", True, "numpy"),  # two np.unique are its definition
    ("hash64", False, "numpy"),  # no toolchain, PS_NO_NATIVE
]


@pytest.mark.parametrize("kind, library, engine", ENGINES)
def test_the_localize_span_names_its_engine_and_a_counter_counts_it(
    kind, library, engine, monkeypatch
):
    from parameter_server_tpu.utils import keys as keys_mod

    if not library:
        monkeypatch.setattr(keys_mod, "_keymap_lib", lambda: None)
    elif keys_mod._keymap_lib() is None:  # pragma: no cover
        pytest.skip("no native toolchain")
    van, worker = _cluster(kind)
    try:
        for seed in (1, 2, 3):
            keys, grads = _batch(kind, seed=seed)
            worker.pull_sync("t", keys, timeout=30)
            worker.push_sync("t", keys, grads, timeout=30)
        c = worker.counters()
        assert (c["localize_computed"], c["localize_reused"]) == (3, 3)
        assert c["localize_native"] == (3 if engine == "native" else 0)
        spans = worker.tracer.spans("ps.worker.localize")
        assert [s[4]["engine"] for s in spans] == [engine] * 3
    finally:
        van.close()
