"""Wire-enforced SSP/BSP/ASP consistency plane (ISSUE 20).

Layers under test:

1. :class:`FleetClock` unit semantics — gate math, liveness (the slowest
   worker always passes), incarnation-advance and idle pruning (a corpse
   must never wedge the fleet minimum);
2. :class:`BoundTuner` policy — widen on a wire-bottleneck verdict,
   tighten (and win) on a loss-variance spike, cooldown between moves;
3. the wire end-to-end: a too-fast worker parked by typed ``__wait__``
   replies and released when the fleet catches up (``consist.gate`` /
   ``consist.release`` events + counters), BSP bitwise-equal to the
   ungated synchronous path, graceful degradation past the gate deadline
   (stale-cache shed and forced-ungated, both flight-recorded);
4. the CHAOS acceptance: under seeded drop/duplicate/delay, across a
   live shard migration AND a same-id worker restart (incarnation bump),
   the SSP invariant holds — sampled server clocks never spread past
   ``bound + 1`` — and the fleet never deadlocks;
5. observability: pstop MODE/BOUND/GATEms columns,
   ``consistency_plane_specs`` evaluated by the live aggregator, the
   postmortem gate-never-released anchor, and the scenario DSL's
   ``consistency_mode`` phase knob.
"""

import pathlib
import sys
import threading
import time

import numpy as np
import pytest

from parameter_server_tpu.config import (
    ConsistencyConfig,
    ConsistencyMode,
    OptimizerConfig,
    TableConfig,
)
from parameter_server_tpu.core import flightrec
from parameter_server_tpu.core.chaos import ChaosVan
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.resender import ReliableVan
from parameter_server_tpu.core.telemetry import (
    TelemetryAggregator,
    TelemetryPublisher,
)
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.kv.cache import HotRowCache
from parameter_server_tpu.kv.consistency import BoundTuner, FleetClock
from parameter_server_tpu.kv.migrate import ShardMigrator
from parameter_server_tpu.kv.routing import FENCED_KEY, WAIT_KEY
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.kv.worker import KVWorker
from parameter_server_tpu.utils.slo import SloEngine, consistency_plane_specs

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent / "tools"))

import postmortem  # noqa: E402
import pstop  # noqa: E402

ROWS = 1 << 8
DIM = 4
NUM_SERVERS = 2

pytestmark = pytest.mark.consistency


def _table_cfgs(mode=None, bound=0, *, deadline=30.0, cache=None):
    consistency = None
    if mode is not None:
        consistency = ConsistencyConfig(
            mode=mode, max_delay=bound, gate_deadline_s=deadline
        )
    return {
        "w": TableConfig(
            name="w", rows=ROWS, dim=DIM,
            optimizer=OptimizerConfig(kind="sgd", learning_rate=0.1),
            consistency=consistency,
        )
    }


def _cluster(van, cfgs, n_workers=2, *, caches=None):
    servers = [
        KVServer(Postoffice(f"S{s}", van), cfgs, s, NUM_SERVERS)
        for s in range(NUM_SERVERS)
    ]
    workers = [
        KVWorker(
            Postoffice(f"W{i}", van), cfgs, NUM_SERVERS,
            cache=(caches or {}).get(i),
        )
        for i in range(n_workers)
    ]
    return servers, workers


def _step(w, keys, grads, timeout=30.0):
    vals = w.pull_sync("w", keys, timeout=timeout)
    w.push_sync("w", keys, grads, timeout=timeout)
    return vals


KEYS = np.arange(8, dtype=np.int64)
GRADS = np.ones((8, DIM), dtype=np.float32)


# --------------------------------------------------- 1. FleetClock units


def test_fleet_clock_gate_math_and_liveness():
    c = FleetClock()
    c.hello("W0", 0)
    c.hello("W1", 0)
    # the slowest worker always passes: it IS the minimum
    assert c.gate("W0", 0, 0) == (True, 0)
    c.commit("W0", 0)  # W0 -> 1
    # W0 is now 1 ahead of W1 (still 0): bound 0 defers, bound 1 admits
    allowed, fm = c.gate("W0", 1, 0)
    assert not allowed and fm == 0
    assert c.gate("W0", 1, 1) == (True, 0)
    # ASP (bound None) always admits but still tracks
    assert c.gate("W0", 7, None)[0]
    assert c.snapshot()["W0"] == 7
    c.commit("W1", 0)
    assert c.fleet_min() == 1


def test_fleet_clock_single_worker_never_gates():
    c = FleetClock()
    c.hello("W0", 0)
    for s in range(20):
        assert c.gate("W0", s, 0)[0]
        c.commit("W0", s)


def test_fleet_clock_incarnation_advance_prunes_the_corpse():
    c = FleetClock()
    c.hello("W0", 0, step=9)
    c.hello("W1", 0, step=0)
    # W1 dies at step 0; van detects the same-id restart (incarnation 1):
    # the DEAD incarnation's entry must not wedge the minimum
    c.on_incarnation_advance("W1", 1)
    assert c.pruned == 1
    assert c.fleet_min() == 9  # only W0 participates now
    assert c.gate("W0", 9, 0)[0]
    # the restarted W1 re-registers at its restored step; an older hello
    # must not resurrect the corpse
    c.hello("W1", 1, step=7)
    assert c.fleet_min() == 7
    c.hello("W1", 0, step=0)  # stale duplicate hello: step only max()es
    assert c.fleet_min() == 7


def test_fleet_clock_idle_prune_unwedges_the_gate():
    c = FleetClock(idle_timeout_s=0.05)
    c.hello("W0", 0)
    c.hello("W1", 0)
    c.commit("W0", 0)
    assert not c.gate("W0", 1, 0)[0]  # W1 holds the minimum
    time.sleep(0.08)  # W1 goes silent past the idle timeout
    allowed, fm = c.gate("W0", 1, 0)  # the defer path prunes the corpse
    assert allowed and fm == 1
    assert c.pruned == 1
    assert c.size() == 1


# --------------------------------------------------- 2. BoundTuner policy


def test_bound_tuner_widens_tightens_and_cools_down():
    cfg = ConsistencyConfig(mode=ConsistencyMode.SSP, max_delay=4)
    t = BoundTuner(cfg, min_bound=1, max_bound=16, window=4, cooldown_s=10.0)
    # widen on the wire-bottleneck verdict (gate-wait SLO breach)
    assert t.maybe_retune(0.0, wire_bottleneck=True) == (
        8, "gate-wait SLO breach: widen"
    )
    # cooldown: no second move inside the window
    assert t.maybe_retune(5.0, wire_bottleneck=True) is None
    assert t.maybe_retune(11.0, wire_bottleneck=True) == (
        16, "gate-wait SLO breach: widen"
    )
    # capped at max_bound
    assert t.maybe_retune(22.0, wire_bottleneck=True) is None
    # a loss-variance spike TIGHTENS, and wins over a widen verdict
    for x in [1.0, 1.01, 0.99, 1.0]:  # calm prior window
        t.observe_loss(x)
    for x in [1.0, 3.0, -1.0, 2.5]:  # spiking recent window
        t.observe_loss(x)
    nb, why = t.maybe_retune(40.0, wire_bottleneck=True)
    assert nb == 8 and "tighten" in why
    assert t.retunes == 3


def test_bound_tuner_rejects_non_ssp():
    with pytest.raises(ValueError):
        BoundTuner(ConsistencyConfig(mode=ConsistencyMode.BSP))


# ------------------------------------------- 3. wire enforcement e2e


def test_ssp_gate_parks_fast_worker_until_release():
    """The tentpole behavior: a worker 2 steps ahead of the fleet minimum
    under bound 1 is parked by ``__wait__`` replies — never dropped — and
    admitted the moment the straggler commits, with the defer/admit pair
    journaled as ``consist.gate`` / ``consist.release``."""
    flightrec.configure(enabled=True, clear=True)
    van = LoopbackVan()
    try:
        cfgs = _table_cfgs(ConsistencyMode.SSP, 1)
        servers, (wa, wb) = _cluster(van, cfgs)
        wa.consist_hello(table="w")
        wb.consist_hello(table="w")
        done = threading.Event()

        def fast():
            for _ in range(3):
                _step(wa, KEYS, GRADS)
            done.set()

        th = threading.Thread(target=fast, daemon=True)
        th.start()
        # A's first steps compile: wait for the gate's first defer, not for
        # a stretch of wall clock that a loaded host outlasts
        deadline = time.monotonic() + 30
        while wa.consist_waits == 0 and time.monotonic() < deadline:
            assert not done.is_set(), "worker A outran the bound ungated"
            time.sleep(0.01)
        assert wa.consist_waits > 0
        time.sleep(0.2)  # parked, not merely deferred once
        assert not done.is_set(), "worker A outran the bound ungated"
        _step(wb, KEYS, GRADS)  # the straggler commits: fleet_min -> 1
        assert done.wait(10), "gate never released after the fleet advanced"
        th.join(timeout=5)
        sc = {}
        for s in servers:
            for k, v in s.counters().items():
                sc[k] = sc.get(k, 0) + v
        assert sc["consist_defers"] > 0
        assert sc["consist_releases"] >= 1
        kinds = [e["kind"] for e in flightrec.get().events()]
        assert "consist.gate" in kinds and "consist.release" in kinds
        gates = [
            e for e in flightrec.get().events()
            if e["kind"] == "consist.gate"
        ]
        rels = [
            e for e in flightrec.get().events()
            if e["kind"] == "consist.release"
        ]
        # first-defer/admit pairing: every gate eventually released
        assert len(gates) == len(rels)
        assert all(g["sender"] == "W0" for g in gates)
        # worker-side wall time parked on the gate is digested
        digs = wa.latency_digests()
        assert digs["consist.gate_wait"]["count"] >= 1
    finally:
        van.close()


def test_wait_reply_is_fence_shaped_for_rolling_upgrades():
    """MIGRATION contract: ``__wait__`` replies carry the fence keys, so a
    pre-ISSUE-20 worker treats them as a routing fence and blindly
    retries; new workers read the typed fields (clock, fleet_min, bound,
    retry_after) and pace themselves on the gate budget instead."""
    van = LoopbackVan()
    captured = []
    orig = KVWorker._scan_waits  # staticmethod: class access is the function

    def spy(responses, order):
        for r in responses:
            p = getattr(r.task, "payload", None) or {}
            if p.get(WAIT_KEY):
                captured.append(p)
        return orig(responses, order)

    try:
        cfgs = _table_cfgs(ConsistencyMode.BSP)
        _servers, (wa, wb) = _cluster(van, cfgs)
        wa.consist_hello(table="w")
        wb.consist_hello(table="w")
        KVWorker._scan_waits = staticmethod(spy)
        _step(wa, KEYS, GRADS)  # step 0: admitted
        done = threading.Event()
        th = threading.Thread(
            target=lambda: (_step(wa, KEYS, GRADS), done.set()), daemon=True
        )
        th.start()
        time.sleep(0.3)  # step 1 parks behind wb (still at 0)
        _step(wb, KEYS, GRADS)
        assert done.wait(10)
        th.join(timeout=5)
        assert captured, "no __wait__ reply crossed the wire"
        p = captured[0]
        assert p[FENCED_KEY] is True  # old workers: fence-retry loop
        assert p[WAIT_KEY] is True  # new workers: typed gate wait
        assert "__error__" in p and "consistency gate" in p["__error__"]
        assert isinstance(p["clock"], dict) and "fleet_min" in p
        assert p["bound"] == 0 and p["retry_after"] > 0
    finally:
        KVWorker._scan_waits = staticmethod(orig)
        van.close()


def test_bsp_wire_is_bitwise_equal_to_the_ungated_path():
    """BSP acceptance: gating only DEFERS requests before apply, so a
    lockstep schedule admits everything untouched — the gated run's final
    table is bit-identical to the ungated synchronous path's."""
    rng = np.random.default_rng(5)
    keys = rng.choice(ROWS, size=(6, 8), replace=False).astype(np.int64)
    grads = rng.normal(size=(6, 8, DIM)).astype(np.float32)

    def run(cfgs, hello):
        van = LoopbackVan()
        try:
            _servers, (wa, wb) = _cluster(van, cfgs)
            if hello:
                wa.consist_hello(table="w")
                wb.consist_hello(table="w")
            for i in range(6):  # strict alternation: a rendezvous schedule
                w = (wa, wb)[i % 2]
                _step(w, keys[i], grads[i])
            return wa.pull_sync("w", np.arange(ROWS, dtype=np.int64))
        finally:
            van.close()

    ungated = run(_table_cfgs(), hello=False)
    gated = run(_table_cfgs(ConsistencyMode.BSP), hello=True)
    np.testing.assert_array_equal(gated, ungated)


def test_gate_deadline_sheds_read_to_stale_cache():
    """Graceful degradation, read side: a pull parked past the gate
    deadline answers from the hot-row cache's stale path (bounded by the
    advertised ``__sver__`` the entries were cached at) and journals a
    ``consist.shed`` with ``how=stale-cache``."""
    flightrec.configure(enabled=True, clear=True)
    van = LoopbackVan()
    try:
        cache = HotRowCache(1 << 8, node="W0")
        cfgs = _table_cfgs(ConsistencyMode.SSP, 0, deadline=0.4)
        _servers, (wa, wb) = _cluster(van, cfgs, caches={0: cache})
        wa.consist_hello(table="w")
        wb.consist_hello(table="w")
        _step(wa, KEYS, GRADS)  # step 0 for wa; wb never advances
        # warm the cache through the serving path (read-only, unstamped)
        warm = wa.pull_serve("w", KEYS, timeout=30)
        t0 = time.monotonic()
        got = wa.pull_sync("w", KEYS, timeout=30)  # step 1: parks, sheds
        assert time.monotonic() - t0 < 10
        assert wa.consist_sheds == 1
        np.testing.assert_array_equal(got, warm)  # served from the cache
        sheds = [
            e for e in flightrec.get().events() if e["kind"] == "consist.shed"
        ]
        assert sheds and sheds[0]["how"] == "stale-cache"
    finally:
        van.close()


def test_gate_deadline_forces_push_through_never_dropped():
    """Graceful degradation, write side: a push parked past the deadline
    is forced through ungated (``consist.shed`` ``how=forced``) — the
    gradient is never dropped, so no work is silently lost.  Proven by
    parity: the degraded run's final table equals an ungated control run
    of the same two steps exactly (same keys, same hash collisions)."""
    flightrec.configure(enabled=True, clear=True)

    def run(gated):
        van = LoopbackVan()
        try:
            cfgs = (
                _table_cfgs(ConsistencyMode.SSP, 0, deadline=0.3)
                if gated else _table_cfgs()
            )
            _servers, (wa, wb) = _cluster(van, cfgs)
            if gated:
                wa.consist_hello(table="w")
                wb.consist_hello(table="w")
            _step(wa, KEYS, GRADS)  # step 0
            _step(wa, KEYS, GRADS)  # step 1: pull + push force through
            got = wa.pull_result(wa.pull("w", KEYS, read_only=True), 30.0)
            return wa, got
        finally:
            van.close()

    wa, degraded = run(gated=True)
    assert wa.consist_forced >= 1
    _wa, control = run(gated=False)
    np.testing.assert_array_equal(degraded, control)
    hows = {
        e["how"] for e in flightrec.get().events()
        if e["kind"] == "consist.shed"
    }
    assert "forced" in hows
    # the combined degradation counter feeds the shed-rate SLO
    assert wa.counters()["consist_degraded"] == (
        wa.consist_sheds + wa.consist_forced
    )


def test_consist_set_flips_mode_live_and_records_retune():
    flightrec.configure(enabled=True, clear=True)
    van = LoopbackVan()
    try:
        cfgs = _table_cfgs(ConsistencyMode.SSP, 2)
        servers, (wa,) = _cluster(van, cfgs, n_workers=1)
        wa.consist_hello(table="w")
        assert servers[0].counters()["consist_mode"] == 2
        assert servers[0].counters()["consist_bound"] == 2
        wa.set_consistency(table="w", bound=8, why="test widen")
        assert servers[0].counters()["consist_bound"] == 8
        wa.set_consistency(table="w", mode="asp", why="test free-run")
        assert servers[0].counters()["consist_mode"] == 3
        assert servers[0].counters()["consist_bound"] == -1
        retunes = [
            e for e in flightrec.get().events()
            if e["kind"] == "consist.retune"
        ]
        assert [r["why"] for r in retunes] == ["test widen", "test free-run"]
    finally:
        van.close()


# ------------------------------------------------- 4. chaos acceptance


@pytest.mark.chaos
@pytest.mark.parametrize("seed", [0, 3])
def test_ssp_bound_holds_under_chaos_migration_and_restart(seed):
    """ISSUE 20 acceptance: 3 workers under wire-enforced SSP(bound=2)
    with seeded drop/duplicate/delay, a live shard migration mid-run, and
    a same-id WORKER restart (incarnation bump) mid-run.  Sampled server
    clocks never spread beyond ``bound + 1`` (the wire invariant: an
    admitted step satisfies ``s - fleet_min <= bound``, and a commit
    advances at most to ``s + 1``), the restart's stale entry is pruned
    rather than wedging the fleet minimum, and every surviving worker
    completes — zero deadlocks."""
    BOUND = 2
    STEPS = 20
    chaos = ChaosVan(
        LoopbackVan(), seed=seed, drop=0.05, duplicate=0.1, delay=0.002
    )
    van = ReliableVan(
        chaos, timeout=0.05, backoff=1.0, max_retries=120, seed=seed
    )
    try:
        cfgs = _table_cfgs(ConsistencyMode.SSP, BOUND, deadline=0.0)
        servers, workers = _cluster(van, cfgs, n_workers=3)
        for w in workers:
            w.consist_hello(table="w")
        # phase 0: all three workers live (the spread invariant is strict);
        # phase 1: restart window — a worker legitimately rejoins BELOW the
        # fleet minimum at its restored step, so only liveness is asserted
        phase = [0]
        spreads = []  # (phase, max-min) samples
        stop = threading.Event()
        fails = []

        def audit():
            while not stop.wait(0.005):
                for s in servers:
                    snap = s._consist["w"]["clock"].snapshot()
                    if len(snap) >= 2:
                        sp = max(snap.values()) - min(snap.values())
                        # read the phase AFTER sampling: a flip mid-sample
                        # can only EXCLUDE a sample from the strict set,
                        # never smuggle a restart-window spread into it
                        spreads.append((phase[0], sp))

        def loop(i, kv):
            rng = np.random.default_rng(1000 * seed + i)
            try:
                for t in range(STEPS):
                    if i == 0:
                        time.sleep(0.003)  # the straggler
                    keys = rng.choice(ROWS, size=8, replace=False).astype(
                        np.int64
                    )
                    _step(kv, keys, GRADS, timeout=60.0)
            except Exception as e:  # noqa: BLE001 — surfaced below
                fails.append((i, e))

        auditor = threading.Thread(target=audit, daemon=True)
        auditor.start()
        threads = [
            threading.Thread(target=loop, args=(i, kv), daemon=True)
            for i, kv in enumerate(workers[:2])
        ]
        for th in threads:
            th.start()
        # W2 trains a few steps, "crashes", and restarts in place: the van
        # bumps its incarnation, the servers prune the dead entry, and the
        # restarted process re-hellos at its restored step
        w2 = workers[2]
        for t in range(5):
            _step(w2, KEYS, GRADS, timeout=60.0)
        restored_step = w2.consist_step("w")
        phase[0] = 1
        van.unbind("W2")
        van.restart_node("W2")
        assert any(
            s.counters().get("consist_pruned", 0) > 0 for s in servers
        ), "incarnation advance did not prune the dead entry"
        w2b = KVWorker(Postoffice("W2", van), cfgs, NUM_SERVERS)
        w2b.consist_hello(table="w", step=restored_step)
        th2 = threading.Thread(
            target=loop, args=(2, w2b), daemon=True
        )
        th2.start()
        # live migration mid-run: move a range from S1 to S0
        mig = ShardMigrator(Postoffice("M0", van), chunk_rows=64)
        new_routing = mig.migrate(
            workers[0].routing, "w", ROWS - ROWS // 4, ROWS, 0
        )
        # worker 0 is mid-run: it may already have learnt the new table off
        # a fence reply, so what adopt_routing returns is a race; where the
        # worker ends up is not
        workers[0].adopt_routing(new_routing)
        assert workers[0].routing.epoch == new_routing.epoch
        for th in threads + [th2]:
            th.join(timeout=180)
        stop.set()
        auditor.join(timeout=5)
        assert not fails, f"worker failures: {fails}"
        assert all(not th.is_alive() for th in threads + [th2]), (
            "deadlock: a worker never finished"
        )
        assert chaos.injected_drops > 0  # the chaos actually did something
        strict = [sp for ph, sp in spreads if ph == 0]
        assert strict, "the auditor never sampled the all-live phase"
        assert max(strict) <= BOUND + 1, (
            f"SSP invariant violated: clock spread {max(strict)} > "
            f"bound {BOUND} + 1 (samples={len(strict)})"
        )
        # after the rejoin the fleet re-converges: every worker ran STEPS
        # steps, so the final committed clocks agree exactly
        for s in servers:
            snap = s._consist["w"]["clock"].snapshot()
            assert len(snap) == 3
            assert max(snap.values()) - min(snap.values()) == 0, snap
        # nobody degraded: deadline 0 disables shedding, so completion
        # proves pure gating stayed live through restart + migration
        total_shed = sum(
            w.consist_sheds + w.consist_forced
            for w in list(workers[:2]) + [w2b]
        )
        assert total_shed == 0
    finally:
        van.close()


# ------------------------------------------------- 5. observability


def test_consistency_plane_specs_evaluated_by_aggregator():
    """The gate-wait p99 and shed-rate SLOs ride the same telemetry
    channel as every other plane: worker digests + counters in, windowed
    verdicts out."""
    van = LoopbackVan()
    try:
        cfgs = _table_cfgs(ConsistencyMode.SSP, 0, deadline=0.2)
        servers, (wa, wb) = _cluster(van, cfgs)
        wa.consist_hello(table="w")
        wb.consist_hello(table="w")
        engine = SloEngine(
            consistency_plane_specs(gate_wait_p99_ms=1.0, shed_per_s=1e9)
        )
        agg = TelemetryAggregator(slo=engine)
        pub_w = TelemetryPublisher("W0", None, sources=[wa])
        pub_s = TelemetryPublisher("S0", None, sources=[servers[0]])
        # a p99 spec reads the DELTA histogram across the window, so the
        # breach needs gate waits on both sides of an ingest: park once,
        # frame, park again, frame
        _step(wa, KEYS, GRADS)
        _step(wa, KEYS, GRADS)  # parks 0.2 s, then forces: a real gate wait
        assert wa.consist_waits > 0
        agg.ingest("W0", pub_w.frame())
        agg.ingest("S0", pub_s.frame())
        _step(wa, KEYS, GRADS)  # parks again (wb never advances)
        agg.ingest("W0", pub_w.frame())
        agg.ingest("S0", pub_s.frame())
        v = engine.evaluate()["W0"]
        # the ~200 ms park breaches a 1 ms gate-wait ceiling
        assert "gate-wait-p99" in v.observed
        assert v.observed["gate-wait-p99"] > 1.0
        assert not v.healthy and "gate-wait-p99" in v.breaches
        # the server's mode/bound gauges surface as derived row fields
        row = agg.latest()["S0"]
        assert row["consist_mode"] == 2 and row["consist_bound"] == 0
    finally:
        van.close()


def test_pstop_renders_mode_bound_and_gate_columns():
    rows = {
        "S0": {
            "node": "S0", "seq": 3, "t_ingest": 10.0,
            "consist_mode": 2, "consist_bound": 4, "counters": {},
        },
        "S1": {
            "node": "S1", "seq": 3, "t_ingest": 10.0,
            "consist_mode": 3, "consist_bound": -1, "counters": {},
        },
        "W0": {
            "node": "W0", "seq": 3, "t_ingest": 10.0, "counters": {},
            # rows carry the aggregator's folded digest STATS, not raw digests
            "digests": {
                "consist.gate_wait": {"count": 4, "p50": 0.01, "p99": 0.05}
            },
        },
    }
    out = "\n".join(pstop.render(rows, now=10.0))
    assert "MODE" in out and "BOUND" in out and "GATEms" in out
    s0 = next(l for l in out.splitlines() if l.startswith("S0"))
    assert " ssp " in s0 and " 4 " in s0
    s1 = next(l for l in out.splitlines() if l.startswith("S1"))
    assert " asp " in s1 and " inf " in s1
    w0 = next(l for l in out.splitlines() if l.startswith("W0"))
    # the digest p99 lands in GATEms as a millisecond figure
    assert pstop._consist_columns(rows["W0"])[2] > 0


def test_postmortem_anchors_on_gate_never_released(tmp_path):
    """A ``consist.gate`` with no later ``consist.release`` for the same
    (server, sender, table) is the deadlock signature — it anchors the
    merged report exactly like a journaled anomaly."""
    flightrec.configure(enabled=True, clear=True)
    flightrec.record(
        "consist.gate", node="S0", sender="W1", table="w",
        step=9, fleet_min=2,
    )
    paths = flightrec.dump(str(tmp_path), reason="test")
    merged = postmortem.merge_bundles(paths)
    gates = postmortem.unreleased_gates(merged)
    assert len(gates) == 1 and gates[0]["sender"] == "W1"
    rep = "\n".join(postmortem.report(merged))
    assert "consistency gate never released" in rep
    # a matching release clears the anchor
    flightrec.record("consist.release", node="S0", sender="W1", table="w")
    paths = flightrec.dump(str(tmp_path / "b"), reason="test")
    assert postmortem.unreleased_gates(postmortem.merge_bundles(paths)) == []
    assert "consist.shed" in postmortem.ANOMALY_KINDS


def test_scenario_phase_knob_compiles_and_applies():
    from parameter_server_tpu.scenario import dsl
    from parameter_server_tpu.scenario.runner import ScenarioRunner

    sc = dsl.Scenario(
        name="consist-drill", seed=7, nodes=4,
        phases=(
            dsl.Phase("warm", 10.0),
            dsl.Phase(
                "ssp", 10.0, consistency_mode="ssp", consistency_bound=4
            ),
            dsl.Phase("bsp", 10.0, consistency_mode="bsp"),
        ),
    )
    evs = [
        e for e in dsl.compile_schedule(sc) if e["event"] == "phase"
    ]
    assert "consistency_mode" not in evs[0]
    assert evs[1]["consistency_mode"] == "ssp"
    assert evs[1]["consistency_bound"] == 4
    assert "consistency_bound" not in evs[2]
    with pytest.raises(ValueError):
        dsl.Phase("bad", 5.0, consistency_mode="tso")
    runner = ScenarioRunner(sc, autoscale=False)
    seen = []
    runner.on_consistency_mode.append(lambda m, b: seen.append((m, b)))
    for e in evs:
        runner._apply_event(e)
    assert seen == [("ssp", 4), ("bsp", None)]
    assert runner.consistency_mode == "bsp"


# ------------------------------------------------- 6. elastic wiring


def test_elastic_trainer_announces_and_retunes():
    """ElasticTrainer end-to-end on a WIRE-gated table: every worker is
    registered with the servers' FleetClocks before training, and an
    attached BoundTuner's wire-bottleneck verdict widens the bound
    fleet-wide mid-run (visible in the server gauge + consist.retune)."""
    from parameter_server_tpu.core.manager import launch_local_cluster
    from parameter_server_tpu.core.messages import server_id, worker_id
    from parameter_server_tpu.data.synthetic import SyntheticCTR
    from parameter_server_tpu.learner.elastic import ElasticTrainer
    from parameter_server_tpu.utils.keys import HashLocalizer

    flightrec.configure(enabled=True, clear=True)
    van = LoopbackVan()
    try:
        sched, managers, posts = launch_local_cluster(
            van, num_workers=2, num_servers=2, heartbeat_timeout=5.0
        )
        rows = 2000
        ccfg = ConsistencyConfig(mode=ConsistencyMode.SSP, max_delay=2)
        cfgs = {
            "w": TableConfig(
                name="w", rows=rows, dim=1,
                optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
                consistency=ccfg,
            )
        }
        loc = {"w": HashLocalizer(rows)}
        servers = {
            server_id(i): KVServer(
                posts[server_id(i)], cfgs, i, 2
            )
            for i in range(2)
        }
        workers = {
            worker_id(i): KVWorker(
                posts[worker_id(i)], cfgs, 2, localizers=loc, min_bucket=16
            )
            for i in range(2)
        }
        data = SyntheticCTR(key_space=5000, nnz=8, batch_size=64, seed=0)
        shards = [[data.next_batch() for _ in range(2)] for _ in range(6)]
        tuner = BoundTuner(ccfg, min_bound=1, max_bound=16)
        trainer = ElasticTrainer(
            workers, sched, shards, ccfg,
            managers=managers,
            bound_tuner=tuner,
            wire_bottleneck=lambda: True,  # forced verdict: must widen
            retune_interval_s=0.0,
            timeout=30.0,
        )
        losses = trainer.run()
        assert losses
        for sid, s in servers.items():
            c = s.counters()
            # both workers announced up front (clock registered them even
            # if no stamped data request reached this shard yet)
            assert c["consist_clock_size"] == 2, (sid, c)
            # the tuner widened 4 -> 8 and the consist_set broadcast
            # landed on every server
            assert c["consist_bound"] > ccfg.max_delay, (sid, c)
        retunes = [
            e for e in flightrec.get().events()
            if e["kind"] == "consist.retune"
        ]
        assert retunes and "widen" in retunes[0]["why"]
        assert tuner.retunes >= 1
    finally:
        van.close()
