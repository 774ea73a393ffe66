"""Hierarchical push (ISSUE 15): worker-group pre-reduction before the wire.

Covers the group plane end to end:

1. config surface: ``GroupConfig`` / ``WorkerGroup`` validation, the
   deterministic per-``(table, step)`` leader election (rotate + fixed),
   and the ``GROUP_KEY`` mirror in ``core/filters.py``;
2. ``GroupReducer``: same-keys reduction, sorted-union merge, partial
   take / stale flush, duplicate-deposit idempotence;
3. cluster parity: a size-2 group applies EXACTLY the sum the direct
   pushes apply, as ONE wire PUSH per server booked as one logical apply
   (``group_pushes`` / ``group_members``), with fewer inbound requests;
4. staleness (ISSUE 10 interaction): barrier-disciplined group arms at
   sizes 2 and 4 must not regress staleness p99 vs direct — the done
   notify advances EVERY member's ``_last_push_version``;
5. chaos: leader killed mid-step degrades to direct per-worker push
   within the same step, bitwise-equal to the clean fallback path;
6. EF interaction (PR 14): rotate-elected groups stamp ``ef="bypass"``
   (codec skips the frame — residuals are per ``(sender, table)`` and a
   rotating sender would shred them); fixed-elected groups quantize
   under the pinned leader's residual;
7. telemetry satellites: per-verb ``inbound_totals``, the aggregator's
   ``grp_pct`` derivation, and pstop's GRP% column.
"""

import threading

import numpy as np
import pytest

from parameter_server_tpu.config import (
    GroupConfig,
    OptimizerConfig,
    TableConfig,
)
from parameter_server_tpu.core import filters, flightrec
from parameter_server_tpu.core.coalesce import CoalescingVan, GroupReducer
from parameter_server_tpu.core.fleet import FleetMonitor
from parameter_server_tpu.core.netmon import MeteredVan
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.telemetry import TelemetryAggregator
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.kv.routing import GROUP_KEY, WorkerGroup
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.kv.worker import KVWorker

ROWS = 1 << 12


def _cfgs(lr=1.0, dim=2):
    return {
        "w": TableConfig(
            name="w", rows=ROWS, dim=dim,
            optimizer=OptimizerConfig(kind="sgd", learning_rate=lr),
        )
    }


def _cluster(cfgs, worker_names, *, num_servers=2, group=None, group_cfg=None):
    metered = MeteredVan(LoopbackVan())
    van = CoalescingVan(metered)
    servers = [
        KVServer(Postoffice(f"S{s}", van), cfgs, s, num_servers)
        for s in range(num_servers)
    ]
    workers = [
        KVWorker(
            Postoffice(n, van), cfgs, num_servers,
            group=group, group_cfg=group_cfg,
        )
        for n in worker_names
    ]
    return van, metered, servers, workers


def _concurrent_push(workers, table, keys, grads, timeout=30):
    """Every group member must be inside push_sync together (the
    rendezvous contract) — drive them with one thread per member."""
    errs = []

    def go(w, g):
        try:
            w.push_sync(table, keys, g, timeout=timeout)
        except Exception as e:  # noqa: BLE001 — surfaced to the test
            errs.append(e)

    ts = [
        threading.Thread(target=go, args=(w, g), daemon=True)
        for w, g in zip(workers, grads)
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs, errs


# ------------------------------------------------------------- config plane


def test_group_config_validation():
    cfg = GroupConfig(size=4, election="rotate", fallback="direct")
    assert cfg.fallback_timeout > 0
    with pytest.raises(ValueError, match="election"):
        GroupConfig(size=2, election="raft")
    with pytest.raises(ValueError, match="fallback"):
        GroupConfig(size=2, fallback="retry")
    with pytest.raises(ValueError, match="reduce"):
        GroupConfig(size=2, reduce="allgather")
    with pytest.raises(ValueError):
        GroupConfig(size=0)


def test_worker_group_validation_and_props():
    g = WorkerGroup(members=("W0", "W1", "W2"))
    assert g.size == 3
    assert g.gid == "W0+W1+W2"
    with pytest.raises(ValueError):
        WorkerGroup(members=())
    with pytest.raises(ValueError):
        WorkerGroup(members=("W0", "W0"))
    with pytest.raises(ValueError, match="election"):
        WorkerGroup(members=("W0", "W1"), election="paxos")


def test_leader_election_deterministic_and_rotating():
    g = WorkerGroup(members=("W0", "W1", "W2", "W3"))
    # deterministic: same (table, step) always elects the same member
    assert g.leader("w", 7) == g.leader("w", 7)
    # rotation: consecutive steps walk the ring, so over size steps every
    # member leads exactly once per table — the load-rotation contract
    leaders = [g.leader("w", s) for s in range(4)]
    assert sorted(leaders) == sorted(g.members)
    # different tables shift the ring phase (crc32 keying), same coverage
    leaders_v = [g.leader("v", s) for s in range(4)]
    assert sorted(leaders_v) == sorted(g.members)
    # salt rotates deterministically off the base election (fence retries)
    assert g.leader("w", 3, salt=1) == g.leader("w", 4)


def test_fixed_election_pins_until_salted():
    g = WorkerGroup(members=("W0", "W1"), election="fixed")
    assert all(g.leader("w", s) == "W0" for s in range(5))
    assert all(g.leader("v", s) == "W0" for s in range(5))
    # a fence retry (salt > 0) still rotates away from a fenced leader
    assert g.leader("w", 0, salt=1) in g.members


def test_group_key_mirrors_filters_module():
    # kv/routing.py owns the wire constant; core/filters.py mirrors it to
    # avoid a core -> kv import cycle.  They MUST stay identical.
    assert GROUP_KEY == filters._GROUP_KEY


# ------------------------------------------------------------ GroupReducer


def test_reducer_same_keys_sums_and_consumes():
    red = GroupReducer(2, node="T", mode="auto")
    keys = np.array([3, 5, 9], dtype=np.int64)
    a = np.array([[1.0], [2.0], [3.0]], np.float32)
    b = np.array([[10.0], [20.0], [30.0]], np.float32)
    assert red.deposit("w", 0, "W0", keys, a) is None
    out = red.deposit("w", 0, "W1", keys, b)
    assert out is not None
    rkeys, rvals, fanin = out
    assert fanin == 2
    np.testing.assert_array_equal(rkeys, keys)
    np.testing.assert_allclose(rvals, a + b)
    assert not red.pending()  # consumed
    # duplicate deposit after consumption starts a fresh set, not a crash
    assert red.deposit("w", 1, "W0", keys, a) is None


def test_reducer_union_merge_disjoint_keys():
    red = GroupReducer(2, node="T", mode="merge")
    k0 = np.array([1, 3], dtype=np.int64)
    k1 = np.array([1, 2], dtype=np.int64)
    v0 = np.array([[1.0], [5.0]], np.float32)
    v1 = np.array([[1.0], [7.0]], np.float32)
    assert red.deposit("w", 0, "W0", k0, v0) is None
    rkeys, rvals, fanin = red.deposit("w", 0, "W1", k1, v1)
    assert fanin == 2
    np.testing.assert_array_equal(rkeys, np.array([1, 2, 3]))
    np.testing.assert_allclose(rvals, np.array([[2.0], [7.0], [5.0]]))


def test_reducer_duplicate_member_deposit_ignored():
    red = GroupReducer(2, node="T")
    keys = np.array([1], dtype=np.int64)
    v = np.ones((1, 1), np.float32)
    assert red.deposit("w", 0, "W0", keys, v) is None
    assert red.deposit("w", 0, "W0", keys, 5 * v) is None  # dup: ignored
    rkeys, rvals, fanin = red.deposit("w", 0, "W1", keys, v)
    np.testing.assert_allclose(rvals, 2 * np.ones((1, 1)))
    assert fanin == 2


def test_reducer_take_partial_and_stale_flush():
    red = GroupReducer(3, node="T")
    keys = np.array([2, 4], dtype=np.int64)
    v = np.ones((2, 1), np.float32)
    assert red.deposit("w", 5, "W0", keys, v) is None
    part = red.take("w", 5)
    assert part is not None and part[2] == 1
    np.testing.assert_allclose(part[1], v)
    assert red.take("w", 5) is None  # consumed
    # stale flush: a set older than the deadline is drained with its step
    assert red.deposit("w", 6, "W0", keys, v) is None
    stale = red.take_stale(0.0)
    assert [(t, s) for t, s, _ in stale] == [("w", 6)]
    assert not red.pending()


# ------------------------------------------------- cluster: parity + wire


def _inbound_push(metered):
    tot = {"msgs": 0, "bytes": 0}
    for link, d in metered.links().items():
        if link.partition("->")[2].startswith("S"):
            vb = (d.get("verbs") or {}).get("PUSH")
            if vb:
                tot["msgs"] += vb["msgs"]
                tot["bytes"] += vb["bytes"]
    return tot


@pytest.mark.parametrize("size", [2, 4])
def test_group_push_applies_sum_once_with_fewer_requests(size):
    cfgs = _cfgs()
    keys = np.array([1, 5, 9, ROWS + 7], dtype=np.int64)
    # integer-valued grads: float addition is exact, so the group arm's
    # summed apply must match the direct arm's sequential applies BITWISE
    grads = [
        np.full((keys.size, 2), float(i + 1), np.float32) for i in range(size)
    ]

    def run(grouped):
        names = tuple(f"W{i}" for i in range(size))
        group = WorkerGroup(members=names) if grouped else None
        gcfg = (
            GroupConfig(size=size, fallback_timeout=10.0) if grouped else None
        )
        van, metered, servers, workers = _cluster(
            cfgs, names, group=group, group_cfg=gcfg
        )
        try:
            before = workers[0].pull_sync("w", keys, timeout=30).copy()
            _concurrent_push(workers, "w", keys, grads)
            after = workers[0].pull_sync("w", keys, timeout=30)
            return {
                "delta": after - before,
                "push": _inbound_push(metered),
                "group_pushes": sum(s.group_pushes for s in servers),
                "group_members": sum(s.group_members for s in servers),
                "pushes": sum(s.pushes for s in servers),
                "worker_counters": [w.counters() for w in workers],
            }
        finally:
            van.close()

    direct = run(False)
    grouped = run(True)
    # parity: sgd lr=1 applied the exact gradient sum either way
    np.testing.assert_array_equal(direct["delta"], grouped["delta"])
    np.testing.assert_array_equal(
        grouped["delta"], -sum(range(1, size + 1)) * np.ones((4, 2))
    )
    # one logical apply for the whole group, booked with its fan-in
    assert grouped["pushes"] == grouped["group_pushes"]
    assert grouped["group_members"] == size * grouped["group_pushes"]
    assert direct["group_pushes"] == 0
    # the wire saw 1/size of the PUSH requests (and bytes, same keys)
    assert grouped["push"]["msgs"] * size == direct["push"]["msgs"]
    assert grouped["push"]["bytes"] * size == direct["push"]["bytes"]
    # clean path: nobody degraded
    assert all(
        c.get("group_fallbacks", 0) == 0
        for c in grouped["worker_counters"]
    )


@pytest.mark.parametrize("size", [2, 4])
def test_staleness_p99_no_regression_vs_direct(size):
    """Barrier-disciplined training at group sizes 2 and 4: the merged
    ``staleness.w`` p99 of the grouped arm must not exceed the direct
    arm's.  Deterministic: with all pushes fenced behind a barrier before
    any pull, each arm's staleness sample multiset is fixed (direct: the
    k-th of N applies lags N-k versions; grouped: one logical apply that
    the done notify credits to EVERY member, so the lag is 0)."""
    cfgs = _cfgs()
    steps = 4
    rng = np.random.default_rng(7)
    keys = np.sort(rng.choice(ROWS, 32, replace=False)).astype(np.int64)
    g = np.ones((keys.size, 2), np.float32)

    def run(grouped):
        names = tuple(f"W{i}" for i in range(size))
        group = WorkerGroup(members=names) if grouped else None
        gcfg = (
            GroupConfig(size=size, fallback_timeout=10.0) if grouped else None
        )
        # ONE server so version arithmetic is single-stream
        van, _m, servers, workers = _cluster(
            cfgs, names, num_servers=1, group=group, group_cfg=gcfg
        )
        barrier = threading.Barrier(size)
        errs = []

        def drive(w):
            try:
                for _ in range(steps):
                    barrier.wait()
                    w.push_sync("w", keys, g, timeout=30)
                    barrier.wait()  # every apply lands before any pull
                    w.pull_sync("w", keys, timeout=30)
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        try:
            ts = [
                threading.Thread(target=drive, args=(w,), daemon=True)
                for w in workers
            ]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert not errs, errs
            from parameter_server_tpu.utils.trace import LatencyHistogram

            p99s = []
            for w in workers:
                d = w.staleness_digests().get("staleness.w")
                assert d is not None and d["count"] >= steps
                p99s.append(LatencyHistogram.from_dict(d).percentile(0.99))
            # one logical apply per step grouped, one per member direct
            assert servers[0].pushes == (steps if grouped else steps * size)
            return max(p99s)
        finally:
            van.close()

    direct_p99 = run(False)
    grouped_p99 = run(True)
    assert grouped_p99 <= direct_p99
    # and the direct arm genuinely has staleness to beat at these sizes
    assert direct_p99 >= 1.0


# ------------------------------------------------------------------ chaos


@pytest.mark.chaos
def test_leader_death_falls_back_bitwise_equal_to_clean_path():
    """Kill the peer member mid-run: the survivor's remaining steps must
    degrade to direct per-worker push with NO loss, and the final table
    must be BITWISE equal to a clean run that pushes the same gradients
    directly — the seeded-chaos acceptance of ISSUE 15."""
    cfgs = _cfgs()
    keys = np.array([3, 11, 42, 1000], dtype=np.int64)
    steps, kill_at = 6, 3
    grads = [
        [
            np.full((keys.size, 2), float(1 + s), np.float32),
            np.full((keys.size, 2), float(10 + s), np.float32),
        ]
        for s in range(steps)
    ]

    def run(kill):
        names = ("W0", "W1")
        loop = LoopbackVan()
        van = CoalescingVan(MeteredVan(loop))
        flightrec.configure(enabled=True, clear=True)
        group = WorkerGroup(members=names)
        gcfg = GroupConfig(size=2, fallback_timeout=0.3)
        try:
            servers = [
                KVServer(Postoffice(f"S{s}", van), cfgs, s, 2)
                for s in range(2)
            ]
            workers = [
                KVWorker(Postoffice(n, van), cfgs, 2, group=group,
                         group_cfg=gcfg)
                for n in names
            ]
            # clean reference arm: an ungrouped worker pushes the
            # survivor's post-death gradients directly
            direct = KVWorker(Postoffice("W9", van), cfgs, 2)
            for s in range(kill_at):
                _concurrent_push(workers, "w", keys, grads[s])
            if kill:
                loop.disconnect("W1")
                for s in range(kill_at, steps):
                    # survivor keeps its group: leader steps flush a
                    # partial set (member_timeout), member steps detect
                    # the dead leader and push direct — same-step, no loss
                    workers[0].push_sync("w", keys, grads[s][0], timeout=30)
            else:
                for s in range(kill_at, steps):
                    direct.push_sync("w", keys, grads[s][0], timeout=30)
            final = direct.pull_sync("w", keys, timeout=30) if not kill \
                else workers[0].pull_sync("w", keys, timeout=30)
            fallbacks = sum(
                w.counters().get("group_fallbacks", 0) for w in workers
            )
            reasons = {
                e.get("reason")
                for e in flightrec.get().events()
                if e["kind"] == "group.fallback"
            }
            return np.asarray(final), fallbacks, reasons
        finally:
            van.close()
            flightrec.configure(enabled=True, clear=True)

    clean, clean_fallbacks, _ = run(kill=False)
    chaos, chaos_fallbacks, reasons = run(kill=True)
    # bitwise: every degraded step applied exactly the survivor's gradient
    np.testing.assert_array_equal(chaos, clean)
    # exact loss parity follows from bitwise weights
    assert float(np.sum(chaos ** 2)) == float(np.sum(clean ** 2))
    assert clean_fallbacks == 0
    assert chaos_fallbacks == steps - kill_at
    assert reasons <= {"member_timeout", "dead_leader", "stale_set"}
    assert reasons  # at least one degradation path exercised


# ------------------------------------------------------------ EF gating


def _group_push_msg(ef):
    from parameter_server_tpu.core.messages import Message, Task, TaskKind

    return Message(
        task=Task(
            TaskKind.PUSH,
            "kv",
            payload={
                "table": "w",
                GROUP_KEY: {"id": "W0+W1", "n": 2, "step": 0, "ef": ef},
            },
        ),
        sender="W0",
        recver="S0",
        keys=np.array([1, 2], dtype=np.int32),
        values=[np.array([[1.5], [2.5]], np.float32)],
    )


def test_ef_bypass_skips_codec_for_rotating_groups():
    from parameter_server_tpu.config import WireCompressionConfig
    from parameter_server_tpu.core.filters import QuantizingFilter

    codec = QuantizingFilter(
        default=WireCompressionConfig(codec="int8", error_feedback=True)
    )
    msg = _group_push_msg("bypass")
    out = codec.encode(msg)
    # frame untouched: float32 planes, no residual store created
    assert out.values[0].dtype == np.float32
    np.testing.assert_array_equal(out.values[0], msg.values[0])
    assert codec.counters().get("compress_wire_bytes", 0) == 0
    assert not codec._residuals


def test_ef_leader_mode_quantizes_under_pinned_residual():
    from parameter_server_tpu.config import WireCompressionConfig
    from parameter_server_tpu.core.filters import QuantizingFilter

    codec = QuantizingFilter(
        default=WireCompressionConfig(codec="int8", error_feedback=True)
    )
    out = codec.encode(_group_push_msg("leader"))
    assert out.values[0].dtype != np.float32  # quantized
    # the residual belongs to the PINNED leader's (sender, table) store —
    # fixed election means that store owns the whole group's residual
    assert set(codec._residuals) == {("W0", "w")}


def test_fixed_election_worker_stamps_leader_ef():
    names = ("W0", "W1")
    group = WorkerGroup(members=names, election="fixed")
    gcfg = GroupConfig(size=2, election="fixed", fallback_timeout=10.0)
    van, metered, servers, workers = _cluster(
        _cfgs(), names, group=group, group_cfg=gcfg
    )
    try:
        assert all(w._group_ef == "leader" for w in workers)
        keys = np.array([4, 8], dtype=np.int64)
        grads = [np.ones((2, 2), np.float32)] * 2
        _concurrent_push(workers, "w", keys, grads)
        # fixed election: W0 leads every step, so only W0 touches servers
        push_senders = {
            link.partition("->")[0]
            for link, d in metered.links().items()
            if link.partition("->")[2].startswith("S")
            and (d.get("verbs") or {}).get("PUSH")
        }
        assert push_senders == {"W0"}
    finally:
        van.close()


def test_rotate_election_worker_stamps_bypass_ef():
    names = ("W0", "W1")
    group = WorkerGroup(members=names)
    van, _m, _s, workers = _cluster(
        _cfgs(), names, group=group,
        group_cfg=GroupConfig(size=2, fallback_timeout=10.0),
    )
    try:
        assert all(w._group_ef == "bypass" for w in workers)
    finally:
        van.close()


# ------------------------------------------------------- telemetry plane


def test_inbound_totals_aggregates_per_verb():
    fleet = FleetMonitor()
    fleet.observe(
        "W0",
        {"links": {"W0->S0": {
            "msgs": 5, "bytes": 500,
            "verbs": {"PUSH": {"msgs": 3, "bytes": 300},
                      "PULL": {"msgs": 2, "bytes": 200}},
        }}},
        now=1.0,
    )
    fleet.observe(
        "W1",
        {"links": {"W1->S0": {
            "msgs": 1, "bytes": 50,
            "verbs": {"PUSH": {"msgs": 1, "bytes": 50}},
        }}},
        now=1.0,
    )
    tot = fleet.inbound_totals()["S0"]
    assert tot["bytes"] == 550 and tot["msgs"] == 6
    assert tot["verbs"]["PUSH"] == {"msgs": 4, "bytes": 350}
    assert tot["verbs"]["PULL"] == {"msgs": 2, "bytes": 200}


def test_inbound_totals_tolerates_verbless_digests():
    fleet = FleetMonitor()
    fleet.observe(
        "W0", {"links": {"W0->S0": {"msgs": 2, "bytes": 20}}}, now=1.0
    )
    tot = fleet.inbound_totals()["S0"]
    assert tot == {"bytes": 20, "msgs": 2, "verbs": {}}


def test_aggregator_derives_grp_pct():
    agg = TelemetryAggregator()
    assert agg.ingest(
        "S0",
        {"seq": 1, "t_mono_s": 1.0,
         "counters": {"group_pushes": 5, "group_members": 20}},
        now=1.0,
    )
    row = agg.latest()["S0"]
    assert row["grp_pct"] == 25.0
    # no group traffic -> no column (pstop renders '-')
    assert agg.ingest("W0", {"seq": 1, "t_mono_s": 1.0}, now=1.0)
    assert "grp_pct" not in agg.latest()["W0"]


def test_pstop_renders_grp_column():
    import pathlib
    import sys

    sys.path.insert(
        0, str(pathlib.Path(__file__).resolve().parent.parent / "tools")
    )
    import pstop

    latest = {
        "S0": {"seq": 3, "ingest_t": 1.0, "grp_pct": 25.0},
        "W0": {"seq": 2, "ingest_t": 1.0},
    }
    lines = pstop.render(latest)
    header = lines[0]
    assert "GRP%" in header
    assert header.index("CMPR%") < header.index("GRP%") < header.index(
        "SHED/S"
    )
    s_row = next(ln for ln in lines if ln.startswith("S0"))
    w_row = next(ln for ln in lines if ln.startswith("W0"))
    assert "25.0" in s_row
    # the non-server row renders '-' in the GRP% slot, not a crash
    assert "25.0" not in w_row
