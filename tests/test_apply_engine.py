"""Bundle-batched fused apply engine (ISSUE 11): parity, sync-free acks,
compile-cache discipline, and e2e chaos bitwise equivalence.

``KVServer.handle_request_batch`` collapses a coalesced bundle's
consecutive same-table PUSHes into ONE donated-buffer device apply and
defers every PULL's readback to a single ``device_get`` per bundle.  The
contract under test:

- ``dup_policy="rounds"`` (default) is **bitwise-identical to sequential
  per-member applies for every optimizer**, including bundles whose
  members push overlapping row ids (occurrence-round partitioning applies
  each row's t-th contribution in member order).
- ``dup_policy="combine"`` pre-merges duplicate rows on device
  (``segment_combine``) — one apply always, classic PS sum semantics,
  sequential-identical when member rows are disjoint.
- The PUSH ack path never observes device results (``is_ready`` stays
  False through the ack — the behavioral twin of the
  ``tools/check_wrappers.py`` AST ban).
- Compile-cache keys stay bucketed: randomized request sizes compile at
  most one step per (members, bucket) signature, never per raw size.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from parameter_server_tpu.config import (
    ApplyEngineConfig,
    OptimizerConfig,
    TableConfig,
)
from parameter_server_tpu.core.chaos import ChaosVan
from parameter_server_tpu.core.coalesce import CoalescingVan
from parameter_server_tpu.core.messages import Message, Task, TaskKind
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.resender import ReliableVan
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.kv.server import KVServer, _bucket
from parameter_server_tpu.kv.worker import KVWorker

DIM = 4
ROWS = 64


def _server(van, *, opt="adagrad", apply=None, rows=ROWS, node="S0"):
    cfg = TableConfig(
        name="w",
        rows=rows,
        dim=DIM,
        optimizer=OptimizerConfig(kind=opt, learning_rate=0.1),
    )
    return KVServer(Postoffice(node, van), {"w": cfg}, 0, 1, apply=apply)


def _push(ids, vals):
    return Message(
        task=Task(TaskKind.PUSH, "kv", payload={"table": "w"}),
        sender="W0",
        recver="S0",
        keys=np.asarray(ids, dtype=np.int32),
        values=[np.asarray(vals, dtype=np.float32).reshape(-1, DIM)],
    )


def _pull(ids):
    return Message(
        task=Task(TaskKind.PULL, "kv", payload={"table": "w"}),
        sender="W0",
        recver="S0",
        keys=np.asarray(ids, dtype=np.int32),
    )


def _rows(rng, n, lo=0, hi=ROWS):
    """n sorted unique row ids (the worker pre-combines within a push, so
    per-member ids are unique; duplicates live ACROSS members)."""
    return np.sort(rng.choice(np.arange(lo, hi), size=n, replace=False))


def _grads(rng, n):
    return rng.normal(size=(n, DIM)).astype(np.float32)


def _member_msgs(seed, k=4):
    """k push members with deliberately overlapping ids and mixed sizes
    (exercises cross-member duplicates AND device bucket padding)."""
    rng = np.random.default_rng(seed)
    sizes = [5, 3, 9, 1, 6, 2][:k]
    msgs = []
    for i, n in enumerate(sizes):
        # low id range forces heavy overlap between members
        ids = _rows(rng, n, 0, max(12, 2 * n))
        msgs.append(_push(ids, _grads(rng, n)))
    return msgs


def _table_bits(server):
    tbl = server.tables["w"]
    return np.asarray(tbl.value), {
        k: np.asarray(v) for k, v in sorted(tbl.state.items())
    }


def _assert_tables_equal(a, b):
    va, sa = a
    vb, sb = b
    np.testing.assert_array_equal(va, vb)  # bitwise, not allclose
    assert sorted(sa) == sorted(sb)
    for k in sa:
        np.testing.assert_array_equal(sa[k], sb[k])


def _no_errors(replies):
    for r in replies:
        assert r is not None
        assert "__error__" not in r.task.payload, r.task.payload


# ------------------------------------------------ batched vs sequential


@pytest.mark.parametrize("opt", ["sgd", "adagrad", "adam", "ftrl"])
def test_rounds_batched_is_bitwise_sequential(opt):
    """Default policy, overlapping member ids, EVERY optimizer: one
    batched apply == member-by-member applies, bit for bit (value AND
    optimizer state)."""
    van = LoopbackVan()
    try:
        seq = _server(van, opt=opt, node="Sseq")
        bat = _server(van, opt=opt, node="Sbat")
        for msg in _member_msgs(seed=1):
            seq.handle_request(msg)
        replies = bat.handle_request_batch(_member_msgs(seed=1))
        _no_errors(replies)
        _assert_tables_equal(_table_bits(seq), _table_bits(bat))
        assert bat.pushes == seq.pushes  # bookkeeping ran per member
    finally:
        van.close()


def test_combine_matches_sequential_on_disjoint_rows():
    van = LoopbackVan()
    try:
        rng = np.random.default_rng(3)
        msgs = []
        for i in range(4):  # disjoint id ranges: combine == sequential
            ids = _rows(rng, 6, 16 * i, 16 * (i + 1))
            msgs.append(_push(ids, _grads(rng, 6)))
        seq = _server(van, node="Sseq")
        bat = _server(
            van, node="Sbat", apply=ApplyEngineConfig(dup_policy="combine")
        )
        for m in msgs:
            seq.handle_request(m)
        _no_errors(bat.handle_request_batch(msgs))
        _assert_tables_equal(_table_bits(seq), _table_bits(bat))
    finally:
        van.close()


def test_combine_sums_cross_member_duplicates():
    """Classic PS semantics: duplicate rows across members pre-sum into
    one gradient before the step — identical to ONE push of the summed
    grads, not to sequential replay."""
    van = LoopbackVan()
    try:
        ids = np.array([2, 5, 9], dtype=np.int64)
        g1 = _grads(np.random.default_rng(4), 3)
        g2 = _grads(np.random.default_rng(5), 3)
        ref = _server(van, node="Sref")
        ref.handle_request(_push(ids, g1 + g2))
        bat = _server(
            van, node="Sbat", apply=ApplyEngineConfig(dup_policy="combine")
        )
        _no_errors(bat.handle_request_batch([_push(ids, g1), _push(ids, g2)]))
        _assert_tables_equal(_table_bits(ref), _table_bits(bat))
    finally:
        van.close()


def test_pull_inside_bundle_observes_exactly_prior_members():
    """[push A, pull, push B] in one bundle: the pull flushes A's group
    and must NOT see B — same observable order as sequential handling."""
    van = LoopbackVan()
    try:
        rng = np.random.default_rng(6)
        ids = np.arange(8, dtype=np.int64)
        a, b = _grads(rng, 8), _grads(rng, 8)
        seq = _server(van, node="Sseq")
        seq.handle_request(_push(ids, a))
        want = seq.handle_request(_pull(ids)).values[0]
        bat = _server(van, node="Sbat")
        replies = bat.handle_request_batch(
            [_push(ids, a), _pull(ids), _push(ids, b)]
        )
        _no_errors(replies)
        np.testing.assert_array_equal(np.asarray(replies[1].values[0]), want)
        # ...and the trailing push still applied
        seq.handle_request(_push(ids, b))
        _assert_tables_equal(_table_bits(seq), _table_bits(bat))
    finally:
        van.close()


def test_batch_isolates_member_failures():
    """A failing member answers __error__; the rest of the bundle lands."""
    van = LoopbackVan()
    try:
        rng = np.random.default_rng(7)
        ids = np.arange(4, dtype=np.int64)
        g = _grads(rng, 4)
        bad = _push(ids, g)
        bad.task = Task(TaskKind.PUSH, "kv", payload={"table": "nope"})
        srv = _server(van)
        replies = srv.handle_request_batch([_push(ids, g), bad])
        assert "__error__" not in replies[0].task.payload
        assert "__error__" in replies[1].task.payload
        assert srv.pushes == 1
    finally:
        van.close()


def test_dup_policy_is_validated():
    van = LoopbackVan()
    try:
        with pytest.raises(ValueError, match="dup_policy"):
            _server(van, apply=ApplyEngineConfig(dup_policy="merge"))
    finally:
        van.close()


# ------------------------------------------------------- sync-free acks


def _entangle_fn():
    """Jitted identity whose output depends on ~300 ms of device work the
    compiler cannot elide (0.0 * finite is exact-zero but data-dependent),
    making 'did the ack wait for the device?' directly observable."""

    @jax.jit
    def entangle(v):
        z = jnp.full((1300, 1300), jnp.float32(1e-3)) + v[0, 0]
        for _ in range(6):
            z = jnp.tanh(z @ z)
        return v + 0.0 * z[: v.shape[0], : v.shape[1]]

    return entangle


@pytest.mark.parametrize("batched", [False, True], ids=["single", "bundle"])
def test_push_ack_does_not_wait_for_device_apply(batched):
    """Behavioral twin of the check_wrappers AST ban: with the device
    apply artificially entangled into ~300 ms of compute, the ack still
    returns while the table value is NOT ready — the reply path performed
    no sync."""
    van = LoopbackVan()
    try:
        srv = _server(van)
        tbl = srv.tables["w"]
        entangle = _entangle_fn()
        orig_push, orig_batch = tbl.push, tbl.push_batch

        def slow_push(ids, vals, n=None):
            orig_push(ids, vals, n)
            tbl.value = entangle(tbl.value)
            return tbl.value  # the ledger's readiness ref, as KVTable.push

        def slow_push_batch(ids, positions, vals, n=None):
            orig_batch(ids, positions, vals, n)
            tbl.value = entangle(tbl.value)
            return tbl.value

        tbl.push, tbl.push_batch = slow_push, slow_push_batch
        rng = np.random.default_rng(8)

        def fire(seed):
            rng2 = np.random.default_rng(seed)
            if batched:
                msgs = [
                    _push(_rows(rng2, 5), _grads(rng2, 5)),
                    _push(_rows(rng2, 7), _grads(rng2, 7)),
                ]
                return srv.handle_request_batch(msgs)
            return [srv.handle_request(_push(_rows(rng2, 5), _grads(rng2, 5)))]

        fire(0)  # warm-up: compile the apply + entangle steps
        jax.block_until_ready(tbl.value)
        t0 = time.perf_counter()
        replies = fire(1)
        ack_s = time.perf_counter() - t0
        _no_errors(replies)
        assert not tbl.value.is_ready(), (
            "push ack blocked until the device apply completed"
        )
        jax.block_until_ready(tbl.value)
        device_s = time.perf_counter() - t0
        assert ack_s < device_s, (ack_s, device_s)
    finally:
        van.close()


# ------------------------------------------------ compile-cache hygiene


def test_batched_apply_compile_cache_stays_bucketed():
    """Randomized member counts and sizes must compile at most one device
    step per (members, bucket...) signature — NEVER one per raw size (the
    wire produces arbitrary lengths; compile storms are the failure mode
    the bucketing exists to prevent)."""
    van = LoopbackVan()
    try:
        srv = _server(van, apply=ApplyEngineConfig(apply_batch=8))
        tbl = srv.tables["w"]
        rng = np.random.default_rng(9)
        raw_sizes = set()
        k_seen, bm_seen, bu_seen = set(), set(), set()
        pushes = 0
        for _ in range(25):
            k = int(rng.integers(2, 5))
            sizes = [int(rng.integers(1, 33)) for _ in range(k)]
            msgs = [
                _push(_rows(rng, n), _grads(rng, n)) for n in sizes
            ]
            _no_errors(srv.handle_request_batch(msgs))
            pushes += k
            raw_sizes.update(sizes)
            k_seen.add(k)
            bm_seen.add(_bucket(max(sizes)))
            bu_seen.update(_bucket(n) for n in range(1, max(sizes) + 1))
        # the workload really was shape-diverse: far more raw sizes than
        # bucket keys, so per-size compilation would blow the bound below
        assert len(raw_sizes) > len(bm_seen) * len(k_seen)
        bound = len(k_seen) * len(bm_seen) * len(bu_seen)
        assert pushes > bound
        assert tbl._push_batch_fn._cache_size() <= bound, (
            f"{tbl._push_batch_fn._cache_size()} compiled batch steps for "
            f"{pushes} pushes (bucket bound {bound})"
        )
    finally:
        van.close()


# ------------------------------------------------------- e2e chaos stack


def _e2e_cfgs():
    return {
        "w": TableConfig(
            name="w",
            rows=1 << 10,
            dim=DIM,
            optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
        )
    }


def _e2e_run(van, num_servers=2, steps=3):
    """Deterministic push schedule: each step issues TWO pushes of the
    same table in ONE coalescing window with overlapping key sets, so the
    per-server bundles carry cross-member duplicate rows."""
    cfgs = _e2e_cfgs()
    for s in range(num_servers):
        KVServer(Postoffice(f"S{s}", van), cfgs, s, num_servers)
    worker = KVWorker(Postoffice("W0", van), cfgs, num_servers)
    rng = np.random.default_rng(11)
    for _ in range(steps):
        pool = rng.choice(1 << 10, size=96, replace=False).astype(np.uint32)
        k1 = np.sort(pool[:64])
        k2 = np.sort(pool[32:])  # 32 keys overlap k1
        g1 = rng.normal(size=(64, DIM)).astype(np.float32)
        g2 = rng.normal(size=(64, DIM)).astype(np.float32)
        with worker.coalesce_window():
            t1 = worker.push("w", k1, g1)
            t2 = worker.push("w", k2, g2)
        assert worker.wait(t1, timeout=60) and worker.wait(t2, timeout=60)
    probe = np.arange(1 << 10, dtype=np.uint32)
    return worker.pull_sync("w", probe, timeout=60)


def test_e2e_bundled_batched_pushes_bitwise_match_sequential_under_chaos():
    """The acceptance gate: the full production stack — coalesced bundles,
    batch delivery, grouped device applies, retransmission under seeded
    drop/duplication chaos — lands the SAME bits as clean per-request
    handling over a plain LoopbackVan, with cross-bundle duplicate ids in
    every window."""
    clean = LoopbackVan()
    try:
        want = _e2e_run(clean)
    finally:
        clean.close()

    chaos = ChaosVan(LoopbackVan(), seed=2, drop=0.05, duplicate=0.05)
    rel = ReliableVan(chaos, timeout=0.05, backoff=1.0, max_retries=60, seed=2)
    van = CoalescingVan(rel)
    try:
        got = _e2e_run(van)
        assert van.flush(30)
        assert rel.gave_up == 0
        assert chaos.injected_drops + chaos.injected_dups > 0
        assert van.counters()["coalesce_msgs"] > van.counters()["coalesce_frames"]
    finally:
        van.close()
    np.testing.assert_array_equal(got, want)  # bitwise, not allclose
