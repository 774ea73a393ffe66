"""Read-heavy serving plane (ISSUE 13): hot-row cache, read-only PULL
fast path, and SLO-driven admission control.

Four layers under test:

1. :class:`HotRowCache` unit semantics — version-clock freshness, owner
   binding, collision eviction, the batched probe, the audit trail;
2. ``KVWorker.pull_serve`` end-to-end against ``pull_sync`` ground truth,
   including the server's ``__ro__`` fast path bitwise contract;
3. the bounded-staleness CHAOS acceptance: under drop/duplicate/delay and
   a live shard migration, no cached read is ever staler than the
   worker's observed ``__sver__`` watermark;
4. admission control: a deterministic overload flips
   ``SloEngine.healthy()`` false and reads shed within one telemetry
   beat, visible as ``serve.shed`` + ``slo.breach`` flight-recorder
   events — plus the three shed policies and the serving telemetry
   columns (pstop RO/S, HIT%, SHED/S).
"""

import pathlib
import sys
import time

import numpy as np
import pytest

from parameter_server_tpu.config import (
    OptimizerConfig,
    ServeConfig,
    TableConfig,
)
from parameter_server_tpu.core import flightrec
from parameter_server_tpu.core.chaos import ChaosVan
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.resender import ReliableVan
from parameter_server_tpu.core.telemetry import TelemetryAggregator
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.kv.cache import HotRowCache
from parameter_server_tpu.kv.migrate import ShardMigrator
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.kv.worker import KVWorker
from parameter_server_tpu.serve.admission import AdmissionController, ShedError
from parameter_server_tpu.serve.loadgen import LoadGenerator
from parameter_server_tpu.utils.slo import SloEngine, serving_plane_specs

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "tools"))
import pstop  # noqa: E402

ROWS = 1 << 10
DIM = 4
NUM_SERVERS = 2


def _table_cfgs():
    return {
        "w": TableConfig(
            name="w", rows=ROWS, dim=DIM,
            optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
        )
    }


def _cluster(van, *, cache=None):
    servers = [
        KVServer(Postoffice(f"S{s}", van), _table_cfgs(), s, NUM_SERVERS)
        for s in range(NUM_SERVERS)
    ]
    worker = KVWorker(
        Postoffice("W0", van), _table_cfgs(), NUM_SERVERS, cache=cache
    )
    return servers, worker


# ------------------------------------------------------------ 1. cache unit


def test_cache_hit_then_watermark_invalidation():
    c = HotRowCache(64, audit=True)
    row = np.arange(DIM, dtype=np.float32)
    c.insert("w", np.array([7]), row[None, :], sver=3, server="S0")
    c.observe("w", "S0", 3)
    got = c.lookup("w", 7, "S0")
    np.testing.assert_array_equal(got, row)
    assert c.hits == 1 and c.misses == 0
    # a fresher write anywhere on the shard advances the watermark past
    # the entry's stamp: the entry dies lazily at the next probe
    c.observe("w", "S0", 5)
    assert c.lookup("w", 7, "S0") is None
    assert c.invalidations == 1 and c.misses == 1
    # audit invariant holds for the one hit that was served
    assert c.audit == [("w", 7, 3, 3)]


def test_cache_watermark_is_monotone_and_insert_never_regresses():
    c = HotRowCache(64)
    c.observe("w", "S0", 9)
    c.observe("w", "S0", 4)  # reordered reply: no-op
    assert c.watermark("w", "S0") == 9
    fresh = np.full((1, DIM), 2.0, np.float32)
    stale = np.full((1, DIM), 1.0, np.float32)
    c.insert("w", np.array([3]), fresh, sver=10, server="S0")
    c.insert("w", np.array([3]), stale, sver=9, server="S0")  # late reply
    got = c.lookup("w", 3, "S0")
    np.testing.assert_array_equal(got, fresh[0])


def test_cache_owner_mismatch_misses_before_any_epoch_adoption():
    """Migration safety: entries remember their source server, so a row
    whose range moved misses immediately — even before the worker clears
    the cache on routing adoption."""
    c = HotRowCache(64)
    c.insert("w", np.array([5]), np.ones((1, DIM), np.float32), 1, "S1")
    assert c.lookup("w", 5, "S0") is None  # S0 owns it now -> dead entry
    assert c.invalidations == 1


def test_cache_collision_eviction_bounds_memory():
    c = HotRowCache(4)  # 4 lines: keys 1 and 5 share line 1
    c.insert("w", np.array([1]), np.full((1, DIM), 1.0, np.float32), 1, "S0")
    c.insert("w", np.array([5]), np.full((1, DIM), 5.0, np.float32), 1, "S0")
    assert c.lookup("w", 1, "S0") is None  # evicted by the collision
    np.testing.assert_array_equal(
        c.lookup("w", 5, "S0"), np.full(DIM, 5.0, np.float32)
    )
    assert len(c) == 1


def test_lookup_many_matches_scalar_semantics():
    c = HotRowCache(64, audit=True)
    keys = np.array([1, 2, 3])
    rows = np.arange(3 * DIM, dtype=np.float32).reshape(3, DIM)
    c.insert("w", keys, rows, sver=2, server="S0")
    c.insert("w", np.array([3]), rows[2:], sver=2, server="S1")  # moved row
    code0 = c.server_code("S0")
    slots = np.array([1, 2, 3, 9], dtype=np.int64)
    hit, hit_rows = c.lookup_many(
        "w", slots, np.full(4, code0, dtype=np.int32)
    )
    assert hit.tolist() == [True, True, False, False]
    np.testing.assert_array_equal(hit_rows, rows[:2])
    # key 3 was owned by S1 in-cache but probed for S0: lazily evicted
    assert c.invalidations == 1
    assert c.hits == 2 and c.misses == 2
    assert [a[:2] for a in c.audit] == [("w", 1), ("w", 2)]
    assert all(sv >= wm for _, _, sv, wm in c.audit)


def test_lookup_stale_ignores_freshness_and_invalidate_all_keeps_wm():
    c = HotRowCache(64)
    c.insert("w", np.array([2]), np.ones((1, DIM), np.float32), 1, "S0")
    c.observe("w", "S0", 99)
    got = c.lookup_stale("w", 2)
    assert got is not None
    row, sver = got
    np.testing.assert_array_equal(row, np.ones(DIM, np.float32))
    assert sver == 1
    dropped = c.invalidate_all(reason="test")
    assert dropped == 1 and len(c) == 0
    assert c.watermark("w", "S0") == 99  # watermarks shadow server clocks


# --------------------------------------------- 2. pull_serve / __ro__ e2e


def test_pull_serve_matches_pull_sync_cold_warm_and_after_write():
    van = LoopbackVan()
    try:
        cache = HotRowCache(1 << 11, node="W0")
        _servers, worker = _cluster(van, cache=cache)
        rng = np.random.default_rng(0)
        keys = rng.choice(ROWS, size=256, replace=False).astype(np.int64)
        worker.push_sync(
            "w", np.sort(keys),
            rng.normal(size=(keys.size, DIM)).astype(np.float32), timeout=60,
        )
        # duplicates + unsorted order + a second dimensionality
        probe = np.concatenate([keys[:64][::-1], keys[:9]])
        ref = worker.pull_sync("w", probe, timeout=60)
        cold = worker.pull_serve("w", probe, timeout=60)  # all misses
        np.testing.assert_array_equal(cold, ref)
        warm = worker.pull_serve("w", probe, timeout=60)  # all hits
        np.testing.assert_array_equal(warm, ref)
        assert cache.hits > 0
        # a write invalidates through the PIGGYBACKED watermark: the very
        # next serve re-fetches instead of serving the dead entries
        worker.push_sync(
            "w", np.sort(keys[:64]),
            np.ones((64, DIM), np.float32), timeout=60,
        )
        after = worker.pull_serve("w", probe, timeout=60)
        np.testing.assert_array_equal(
            after, worker.pull_sync("w", probe, timeout=60)
        )
        batch2d = keys[:32].reshape(4, 8)
        np.testing.assert_array_equal(
            worker.pull_serve("w", batch2d, timeout=60),
            worker.pull_sync("w", batch2d, timeout=60),
        )
    finally:
        van.close()


def test_read_only_fast_path_is_bitwise_equal_and_instrumented():
    van = LoopbackVan()
    try:
        servers, worker = _cluster(van)
        rng = np.random.default_rng(1)
        keys = np.sort(rng.choice(ROWS, size=512, replace=False)).astype(
            np.int64
        )
        worker.push_sync(
            "w", keys, rng.normal(size=(keys.size, DIM)).astype(np.float32),
            timeout=60,
        )
        normal = worker.pull_sync("w", keys, timeout=60)
        ro = worker.pull_result(
            worker.pull("w", keys, read_only=True), timeout=60
        )
        np.testing.assert_array_equal(normal, ro)
        assert sum(s.ro_pulls for s in servers) > 0
        assert any("ro_pull.w" in s.latency_digests() for s in servers)
    finally:
        van.close()


# ---------------------------- 3. bounded staleness under chaos + migration


@pytest.mark.chaos
def test_bounded_staleness_under_chaos_with_live_migration():
    """The serving-plane acceptance invariant: across drop/duplicate/delay
    chaos, interleaved writes, and a LIVE shard migration, every cache hit
    served a row stamped at or above the worker's observed ``__sver__``
    watermark for the owning server — and the final serve agrees with the
    ground-truth RPC pull."""
    chaos = ChaosVan(LoopbackVan(), seed=3, drop=0.2, duplicate=0.2,
                     delay=0.01)
    van = ReliableVan(
        chaos, timeout=0.05, backoff=1.0, max_retries=120, seed=3
    )
    try:
        cache = HotRowCache(1 << 11, node="W0", audit=True)
        _servers, worker = _cluster(van, cache=cache)
        mig = ShardMigrator(Postoffice("M0", van), chunk_rows=128)
        rng = np.random.default_rng(7)
        hot = np.sort(rng.choice(ROWS, size=96, replace=False)).astype(
            np.int64
        )
        worker.push_sync(
            "w", hot, rng.normal(size=(hot.size, DIM)).astype(np.float32),
            timeout=60,
        )
        for step in range(10):
            # serve twice back-to-back: the second is the hit-path serve
            # (the write below advances the shard clock and — by design —
            # conservatively invalidates everything cached from it)
            worker.pull_serve("w", hot, timeout=60)
            worker.pull_serve("w", hot, timeout=60)
            # dirty a rotating subset: versions advance, watermarks follow
            sub = hot[step % 3 :: 3]
            worker.push_sync(
                "w", sub,
                rng.normal(size=(sub.size, DIM)).astype(np.float32),
                timeout=60,
            )
            if step == 5:
                # live migration: move the tail half of S1's range to S0
                new_routing = mig.migrate(
                    worker.routing, "w", ROWS - ROWS // 4, ROWS, 0
                )
                assert worker.adopt_routing(new_routing)
        final = worker.pull_serve("w", hot, timeout=60)
        np.testing.assert_array_equal(
            final, worker.pull_sync("w", hot, timeout=60)
        )
        assert chaos.injected_drops > 0  # the chaos actually did something
        assert cache.hits > 0 and cache.audit
        staler = [
            (t, k, sv, wm) for t, k, sv, wm in cache.audit if sv < wm
        ]
        assert not staler, f"cached reads staler than watermark: {staler[:5]}"
    finally:
        van.close()


# ---------------------------------------------------- 4. admission control


def test_slo_breach_sheds_within_one_beat_and_recovers():
    flightrec.configure(enabled=True, clear=True)
    van = LoopbackVan()
    try:
        cache = HotRowCache(1 << 11, node="W0")
        _servers, worker = _cluster(van, cache=cache)
        keys = np.arange(32, dtype=np.int64)
        worker.push_sync(
            "w", keys, np.ones((keys.size, DIM), np.float32), timeout=60
        )
        eng = SloEngine(serving_plane_specs("w", backlog_bundles=2))
        adm = AdmissionController(
            worker, healthy=lambda: eng.healthy("S0"), node="W0"
        )
        t0 = 100.0
        eng.observe("S0", "inflight_bundles", 0.0, now=t0)
        eng.evaluate(now=t0)
        assert adm.pull("w", keys, timeout=60).shape == (keys.size, DIM)
        # deterministic overload: backlog gauge above the armed limit —
        # ONE evaluate beat later the gate is shut
        eng.observe("S0", "inflight_bundles", 16.0, now=t0 + 1.0)
        eng.evaluate(now=t0 + 1.0)
        with pytest.raises(ShedError) as ei:
            adm.pull("w", keys, timeout=60)
        assert ei.value.retry_after_s == adm.cfg.retry_after_s
        assert adm.serve_shed == 1
        kinds = [e["kind"] for e in flightrec.get().events()]
        assert "slo.breach" in kinds and "serve.shed" in kinds
        # recovery: the breaching sample ages out of the window, the next
        # beat clears the breach, reads flow again
        eng.observe("S0", "inflight_bundles", 0.0, now=t0 + 30.0)
        eng.evaluate(now=t0 + 30.0)
        assert adm.pull("w", keys, timeout=60).shape == (keys.size, DIM)
        assert "slo.clear" in [e["kind"] for e in flightrec.get().events()]
    finally:
        van.close()
        flightrec.configure(enabled=True, clear=True)


def test_busy_hint_alone_trips_admission():
    van = LoopbackVan()
    try:
        _servers, worker = _cluster(van, cache=HotRowCache(64))
        adm = AdmissionController(worker, node="W0")
        assert not adm.overloaded("w")
        # a live __busy__ hint from an owner of "w" is a local overload
        # signal needing no SLO feed (stamp what the reply tap would)
        with worker._staleness_lock:
            worker._busy_last["S1"] = time.monotonic()
        assert adm.overloaded("w")
        with pytest.raises(ShedError):
            adm.pull("w", np.arange(4, dtype=np.int64))
    finally:
        van.close()


def test_stale_policy_serves_cached_rows_and_sheds_uncached():
    van = LoopbackVan()
    try:
        cache = HotRowCache(1 << 11, node="W0")
        _servers, worker = _cluster(van, cache=cache)
        keys = np.arange(16, dtype=np.int64)
        worker.push_sync(
            "w", keys, np.ones((keys.size, DIM), np.float32), timeout=60
        )
        ref = worker.pull_sync("w", keys, timeout=60)
        worker.pull_serve("w", keys, timeout=60)  # warm the cache
        adm = AdmissionController(
            worker, healthy=lambda: False, node="W0",
            cfg=ServeConfig(policy="stale"),
        )
        got = adm.pull("w", keys)  # degraded but answered
        np.testing.assert_array_equal(got, ref)
        assert adm.serve_stale == 1
        with pytest.raises(ShedError):
            adm.pull("w", np.arange(900, 910, dtype=np.int64))  # not cached
        assert adm.serve_shed == 1
    finally:
        van.close()


def test_queue_policy_waits_for_health_then_serves_or_sheds():
    van = LoopbackVan()
    try:
        cache = HotRowCache(1 << 11, node="W0")
        _servers, worker = _cluster(van, cache=cache)
        keys = np.arange(8, dtype=np.int64)
        worker.push_sync(
            "w", keys, np.ones((keys.size, DIM), np.float32), timeout=60
        )
        calls = {"n": 0}

        def healthy_after_three():
            calls["n"] += 1
            return calls["n"] > 3

        adm = AdmissionController(
            worker, healthy=healthy_after_three, node="W0",
            cfg=ServeConfig(policy="queue", queue_deadline_s=2.0,
                            queue_poll_s=0.001),
        )
        got = adm.pull("w", keys, timeout=60)
        assert got.shape == (keys.size, DIM)
        assert adm.serve_queue_waits == 1 and adm.serve_shed == 0
        adm_down = AdmissionController(
            worker, healthy=lambda: False, node="W0",
            cfg=ServeConfig(policy="queue", queue_deadline_s=0.02,
                            queue_poll_s=0.001),
        )
        with pytest.raises(ShedError):
            adm_down.pull("w", keys)
    finally:
        van.close()


# ------------------------------------------------------------- 5. loadgen


def test_loadgen_is_open_loop_seeded_and_counts_sheds():
    seen: list = []

    def record_pull(table, keys):
        seen.append(np.asarray(keys).copy())
        if len(seen) % 2 == 0:
            raise ShedError("drill", 0.01)

    gen = LoadGenerator(
        record_pull, table="w", num_keys=ROWS, keys_per_pull=4,
        clients=1000, per_client_qps=0.05, zipf_s=1.1, seed=11,
    )
    assert gen.qps == pytest.approx(50.0)
    rep = gen.run(0.3)
    assert rep.pulls == rep.served + rep.shed and rep.pulls == len(seen)
    assert rep.shed == rep.pulls // 2
    assert rep.shed_rate == round(rep.shed / rep.pulls, 4)
    # same seed -> the identical offered request sequence (open loop is
    # scheduled up front, independent of service-time feedback)
    seen2: list = []
    LoadGenerator(
        lambda t, k: seen2.append(np.asarray(k).copy()), table="w",
        num_keys=ROWS, keys_per_pull=4, clients=1000, per_client_qps=0.05,
        zipf_s=1.1, seed=11,
    ).run(0.3)
    assert len(seen2) == len(seen)
    for a, b in zip(seen, seen2):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("healthy", [True, False], ids=["healthy", "overloaded"])
def test_loadgen_through_admission_counts_hits_and_sheds(healthy):
    """The whole read stack on a real cluster: Zipfian open-loop arrivals ->
    admission -> ``pull_serve`` -> cache or the read-only wire path.  A
    healthy fleet serves every read and the hot keys hit the cache; an
    overloaded one sheds every read and none reaches a server."""
    van = LoopbackVan()
    try:
        cache = HotRowCache(1 << 11, node="W0")
        servers, worker = _cluster(van, cache=cache)
        keys = np.arange(ROWS, dtype=np.int64)
        worker.push_sync(
            "w", keys, np.ones((keys.size, DIM), np.float32), timeout=60
        )
        adm = AdmissionController(worker, healthy=lambda: healthy, node="W0")
        ro_before = sum(s.counters()["ro_pulls"] for s in servers)
        rep = LoadGenerator(
            adm.pull, table="w", num_keys=ROWS, keys_per_pull=8,
            clients=1000, per_client_qps=0.2, zipf_s=1.1, seed=3, cache=cache,
        ).run(0.3)
        ro_after = sum(s.counters()["ro_pulls"] for s in servers)
        assert rep.pulls == rep.served + rep.shed > 0
        if healthy:
            assert rep.shed == 0 and adm.serve_shed == 0
            assert rep.cache_hits > 0 and 0 < rep.hit_rate <= 1
            assert ro_after > ro_before  # misses rode the read-only path
        else:
            assert rep.served == 0 and rep.shed_rate == 1.0
            assert adm.serve_shed == rep.pulls
            assert ro_after == ro_before and rep.cache_hits == 0
    finally:
        van.close()


# --------------------------------------- 6. telemetry columns + pstop/gate


def test_aggregator_derives_serving_rates_and_pstop_renders_them():
    agg = TelemetryAggregator()
    agg.ingest("W0", {
        "seq": 1, "t_mono_s": 100.0,
        "counters": {"ro_pulls": 0, "serve_shed": 0,
                     "cache_hits": 0, "cache_misses": 0},
    }, now=100.0)
    agg.ingest("W0", {
        "seq": 2, "t_mono_s": 102.0,
        "counters": {"ro_pulls": 120, "serve_shed": 6,
                     "cache_hits": 90, "cache_misses": 30},
    }, now=102.0)
    row = agg.latest()["W0"]
    assert row["ro_per_s"] == pytest.approx(60.0)
    assert row["shed_per_s"] == pytest.approx(3.0)
    assert row["cache_hit_pct"] == pytest.approx(75.0)
    lines = pstop.render(agg.latest())
    assert "RO/S" in lines[0] and "HIT%" in lines[0] and "SHED/S" in lines[0]
    assert "60.0" in lines[1] and "75.0" in lines[1] and "3.0" in lines[1]
    snap = pstop.snapshot(agg.latest())
    assert snap["nodes"]["W0"]["ro_per_s"] == pytest.approx(60.0)
    # a node with no serving traffic renders placeholders, not zeros
    agg2 = TelemetryAggregator()
    agg2.ingest("S0", {"seq": 1, "t_mono_s": 1.0}, now=1.0)
    assert "ro_per_s" not in agg2.latest()["S0"]
    assert pstop.render(agg2.latest())[1].count(" -") >= 3
