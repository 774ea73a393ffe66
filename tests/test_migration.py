"""Live shard migration + elastic rebalancing (ISSUE 6 tentpole).

Five scenarios on the acceptance list:

1. end-to-end ``ShardMigrator.migrate`` moves value AND optimizer state
   bitwise, shrinking the donor and growing the recipient;
2. pushes landing mid-stream ride the dirty DELTA shipped inside the
   bounded ``migrate_commit`` freeze — nothing lost, nothing doubled;
3. a worker routed by a stale table is REJECTED (typed fence), adopts the
   attached table, and re-submits only the fenced positions — under seeded
   chaos the final model is bitwise-equal to the fault-free run;
4. the closed loop: a Zipfian-hot workload drives ``FleetMonitor`` inbound
   byte ranking -> ``RebalancePolicy`` splits the hot range mid-training
   with loss-trajectory and push-apply parity, and the hot server's
   inbound byte share measurably drops;
5. ``scale_up`` / ``drain_down`` grow and retire servers live with zero
   loss and a bounded freeze; a donor killed mid-stream falls back to the
   PR-4 same-id restart path and the migration re-runs idempotently.
"""

import time

import numpy as np
import pytest

import jax.numpy as jnp

from parameter_server_tpu.config import OptimizerConfig, TableConfig
from parameter_server_tpu.core.chaos import ChaosVan
from parameter_server_tpu.core.fleet import FleetMonitor
from parameter_server_tpu.core.netmon import MeteredVan
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.resender import ReliableVan
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.data.synthetic import SyntheticCTR
from parameter_server_tpu.kv import replica as replica_lib
from parameter_server_tpu.kv.migrate import ShardMigrator
from parameter_server_tpu.kv.routing import RoutingTable
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.kv.worker import KVWorker
from parameter_server_tpu.learner.elastic import (
    RebalanceConfig,
    RebalancePolicy,
    drain_down,
    scale_up,
)
from parameter_server_tpu.models import linear
from parameter_server_tpu.utils.keys import HashLocalizer

pytestmark = pytest.mark.migration

ROWS = 1 << 10
NUM_SERVERS = 2
STEPS = 12


def _table_cfgs():
    return {
        "w": TableConfig(
            name="w", rows=ROWS, dim=1,
            optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
        )
    }


def _batches():
    data = SyntheticCTR(key_space=4 * ROWS, nnz=8, batch_size=128, seed=3)
    return [data.next_batch() for _ in range(STEPS)]


def _train(worker, batches, on_step=None):
    losses = []
    for i, (keys, labels) in enumerate(batches):
        w_pos = worker.pull_sync("w", keys, timeout=60)
        g, _gb, loss = linear.grad_rows(jnp.asarray(w_pos), jnp.asarray(labels))
        worker.push_sync("w", keys, np.asarray(g) / labels.shape[0], timeout=60)
        losses.append(float(loss))
        if on_step is not None:
            on_step(i)
    return losses


def _clean_reference(batches):
    """Fault-free fixed-topology run: losses, applied pushes, full table."""
    van = LoopbackVan()
    try:
        servers = [
            KVServer(Postoffice(f"S{s}", van), _table_cfgs(), s, NUM_SERVERS)
            for s in range(NUM_SERVERS)
        ]
        worker = KVWorker(Postoffice("W0", van), _table_cfgs(), NUM_SERVERS)
        losses = _train(worker, batches)
        value, state = _assemble(worker.routing, dict(enumerate(servers)))
        return losses, sum(s.pushes for s in servers), value, state
    finally:
        van.close()


def _reliable_stack(*, seed=0, timeout=0.05, max_retries=60, **chaos_kw):
    chaos = ChaosVan(LoopbackVan(), seed=seed, **chaos_kw)
    van = ReliableVan(
        chaos, timeout=timeout, backoff=1.0, max_retries=max_retries,
        seed=seed,
    )
    return van, chaos


def _assemble(routing: RoutingTable, servers_by_index, table="w"):
    """Full ``[rows, dim]`` value + optimizer state, stitched per segment."""
    tr = routing.tables[table]
    value = None
    state = None
    for i, owner in enumerate(tr.owners):
        lo, hi = tr.offsets[i], tr.offsets[i + 1]
        v, st = servers_by_index[owner].export_range(table, lo, hi)
        if value is None:
            value = np.zeros((tr.rows,) + v.shape[1:], v.dtype)
            state = {
                k: np.zeros((tr.rows,) + a.shape[1:], a.dtype)
                for k, a in st.items()
            }
        value[lo:hi] = v
        for k, a in st.items():
            state[k][lo:hi] = a
    return value, state


def _keys_hashing_into(lo, hi, count, *, start=0):
    """Raw keys whose HashLocalizer slot lands in global rows [lo, hi)."""
    loc = HashLocalizer(ROWS)
    found = []
    k = start
    while len(found) < count:
        cand = np.arange(k, k + 4096, dtype=np.int64)
        slots = loc.assign(cand.astype(np.uint64))
        hit = cand[(slots >= lo) & (slots < hi)]
        found.extend(int(x) for x in hit)
        k += 4096
    return np.asarray(found[:count], dtype=np.int64)


# ------------------------------------------------------ 1. basic migration


def test_migrate_moves_value_and_optimizer_state_bitwise():
    batches = _batches()
    ref_losses, _ref_applied, ref_value, ref_state = _clean_reference(batches)

    van = LoopbackVan()
    try:
        servers = {
            s: KVServer(Postoffice(f"S{s}", van), _table_cfgs(), s, NUM_SERVERS)
            for s in range(NUM_SERVERS)
        }
        worker = KVWorker(Postoffice("W0", van), _table_cfgs(), NUM_SERVERS)
        mig = ShardMigrator(Postoffice("M0", van), chunk_rows=128)
        routing = worker.routing
        moved = {}

        def on_step(i):
            if i != STEPS // 2:
                return
            # move the tail half of S1's range to S0, live
            new_routing = mig.migrate(routing, "w", 768, ROWS, 0)
            assert new_routing.epoch == routing.epoch + 1
            assert worker.adopt_routing(new_routing)
            moved["routing"] = new_routing

        losses = _train(worker, batches, on_step=on_step)
        np.testing.assert_allclose(losses, ref_losses, rtol=1e-7, atol=0)

        routing = moved["routing"]
        assert routing.tables["w"].owned_segments(0) == [(0, 512), (768, ROWS)]
        assert routing.tables["w"].owned_segments(1) == [(512, 768)]
        value, state = _assemble(routing, servers)
        np.testing.assert_array_equal(value, ref_value)
        for k in ref_state:
            np.testing.assert_array_equal(state[k], ref_state[k])

        assert servers[1].rows_migrated_out == 256
        assert servers[0].rows_migrated_in >= 256  # chunks + dirty delta
        assert mig.migrations == 1 and mig.rows_moved == 256
        assert servers[1].migration_freeze_last_s >= 0.0
        # the freeze is the delta export, NOT the 256-row stream: bounded
        assert servers[1].migration_freeze_last_s < 5.0
    finally:
        van.close()


# ------------------------------------ 2. dirty delta inside the commit fence


def test_push_between_chunks_rides_commit_delta():
    """Rows dirtied AFTER their chunk shipped are re-sent in the commit
    freeze — the recipient's final state includes the late push exactly
    once (compared bitwise against a migration-free twin cluster)."""
    cfgs = _table_cfgs()
    lo, hi = 768, ROWS
    hot = _keys_hashing_into(lo, hi, 32)

    van = LoopbackVan()
    ref_van = LoopbackVan()
    try:
        servers = {
            s: KVServer(Postoffice(f"S{s}", van), cfgs, s, NUM_SERVERS)
            for s in range(NUM_SERVERS)
        }
        worker = KVWorker(Postoffice("W0", van), cfgs, NUM_SERVERS)
        ref_servers = {
            s: KVServer(Postoffice(f"S{s}", ref_van), cfgs, s, NUM_SERVERS)
            for s in range(NUM_SERVERS)
        }
        ref_worker = KVWorker(Postoffice("W0", ref_van), cfgs, NUM_SERVERS)

        ones = np.ones(hot.size, np.float32)
        worker.push_sync("w", hot, ones, timeout=60)
        ref_worker.push_sync("w", hot, ones, timeout=60)

        mig = ShardMigrator(Postoffice("M0", van), chunk_rows=128)
        routing = worker.routing
        new_routing = routing.move("w", lo, hi, 0)
        mid = "test:delta:0"
        mig._rpc("S1", {"op": "migrate_begin", "mid": mid, "table": "w",
                        "lo": lo, "hi": hi})
        for a in range(lo, hi, 128):
            mig._rpc("S1", {"op": "migrate_send", "mid": mid, "to": "S0",
                            "lo": a, "hi": a + 128})
        # every chunk has shipped; NOW dirty some of the migrating rows
        worker.push_sync("w", hot, 2 * ones, timeout=60)
        ref_worker.push_sync("w", hot, 2 * ones, timeout=60)
        mig._rpc("S1", {"op": "migrate_commit", "mid": mid, "to": "S0",
                        "routing": new_routing.to_payload()})

        assert worker.adopt_routing(new_routing)
        value, state = _assemble(new_routing, servers)
        ref_value, ref_state = _assemble(ref_worker.routing, ref_servers)
        np.testing.assert_array_equal(value, ref_value)
        for k in ref_state:
            np.testing.assert_array_equal(state[k], ref_state[k])
        # the counter is DISTINCT rows handed over, not chunk+delta traffic
        assert servers[0].rows_migrated_in == hi - lo
        assert servers[1].migration_freeze_last_s > 0.0
    finally:
        van.close()
        ref_van.close()


# --------------------------------------- 3. fencing under seeded packet loss


@pytest.mark.chaos
@pytest.mark.parametrize("seed", [0])
def test_stale_worker_is_fenced_not_lost_under_chaos(seed):
    """The worker is NOT told about a mid-run migration: its next requests
    carry the old epoch and are rejected with the new table attached.  The
    fence loop converges, and under seeded 5% drop the final model is
    bitwise-equal to the fault-free fixed-topology run — rejected, never
    lost, never double-applied."""
    batches = _batches()
    ref_losses, _ref_applied, ref_value, ref_state = _clean_reference(batches)

    van, chaos = _reliable_stack(seed=seed, timeout=0.1, drop=0.05)
    try:
        servers = {
            s: KVServer(Postoffice(f"S{s}", van), _table_cfgs(), s, NUM_SERVERS)
            for s in range(NUM_SERVERS)
        }
        worker = KVWorker(Postoffice("W0", van), _table_cfgs(), NUM_SERVERS)
        mig = ShardMigrator(Postoffice("M0", van), chunk_rows=256)
        moved = {}

        def on_step(i):
            if i != STEPS // 2:
                return
            # migrate WITHOUT informing the worker — it must discover the
            # new table from fence rejects alone
            moved["routing"] = mig.migrate(worker.routing, "w", 768, ROWS, 0)

        losses = _train(worker, batches, on_step=on_step)
        np.testing.assert_allclose(losses, ref_losses, rtol=1e-7, atol=0)
        assert sum(s.fenced_rejects for s in servers.values()) > 0
        assert worker.refresh_retries > 0
        assert worker.routing.epoch == moved["routing"].epoch  # converged

        value, state = _assemble(moved["routing"], servers)
        np.testing.assert_array_equal(value, ref_value)
        for k in ref_state:
            np.testing.assert_array_equal(state[k], ref_state[k])
        assert chaos.injected_drops > 0  # the run was actually lossy
        assert van.flush(10)
    finally:
        van.close()


# ------------------------------------- 4. monitor-driven elastic rebalancing


def test_zipfian_skew_triggers_rebalance_with_parity():
    """ISSUE 6 acceptance e2e: a Zipfian-hot workload concentrates inbound
    bytes on S1; the FleetMonitor->RebalancePolicy loop splits the hot
    range off mid-training.  Zero lost/double-applied pushes (loss
    trajectory AND push-apply counts exactly match the no-rebalance run),
    and the hot server's inbound byte share drops measurably."""
    cfgs = _table_cfgs()
    rs = np.random.RandomState(7)
    hot = _keys_hashing_into(896, ROWS, 96)  # inside S1's tail half
    cold = rs.randint(0, 4 * ROWS, size=4096).astype(np.int64)
    batches = []
    for _ in range(STEPS):
        pick = rs.rand(128, 8) < 0.85
        keys = np.where(
            pick,
            hot[rs.randint(0, hot.size, size=(128, 8))],
            cold[rs.randint(0, cold.size, size=(128, 8))],
        )
        labels = rs.randint(0, 2, size=128).astype(np.float32)
        batches.append((keys, labels))

    ref_losses, ref_applied, ref_value, ref_state = _clean_reference(batches)

    metered = MeteredVan(LoopbackVan())
    try:
        servers = {
            s: KVServer(Postoffice(f"S{s}", metered), cfgs, s, NUM_SERVERS)
            for s in range(NUM_SERVERS)
        }
        worker = KVWorker(Postoffice("W0", metered), cfgs, NUM_SERVERS)
        monitor = FleetMonitor()
        mig = ShardMigrator(Postoffice("M0", metered), chunk_rows=256)
        policy = RebalancePolicy(
            monitor, mig, config=RebalanceConfig(hot_share=0.6)
        )
        state = {"routing": worker.routing, "at_move": None}

        def on_step(i):
            if state["at_move"] is not None:
                return  # one move is the scenario; fresh-window reuse would
                # chase the stale pre-move skew
            monitor.observe("W0", {"links": metered.links()})
            routing, moved_now = policy.maybe_rebalance(state["routing"])
            if moved_now:
                state["routing"] = routing
                state["at_move"] = (i, monitor.inbound_totals())
                # scheduler ROUTING broadcast stand-in: adopt eagerly so
                # parity is exact (fences would still converge, but each
                # fence round adds empty-leg re-pushes to the counters)
                assert worker.adopt_routing(routing)

        losses = _train(worker, batches, on_step=on_step)
        assert state["at_move"] is not None, "skew never triggered a move"
        move_step, totals_mid = state["at_move"]
        assert move_step < STEPS - 2  # moved mid-run, with steps left after
        assert policy.moves and policy.moves[0]["frm"] == 1
        assert policy.moves[0]["share"] >= 0.6

        # parity: identical trajectory and applied-push counts
        np.testing.assert_allclose(losses, ref_losses, rtol=1e-7, atol=0)
        applied = sum(s.pushes for s in servers.values())
        assert applied == ref_applied
        value, st = _assemble(state["routing"], servers)
        np.testing.assert_array_equal(value, ref_value)
        for k in ref_state:
            np.testing.assert_array_equal(st[k], ref_state[k])

        # the hot server's inbound byte share dropped measurably
        monitor.observe("W0", {"links": metered.links()})
        totals_end = monitor.inbound_totals()

        def share(totals_a, totals_b):
            delta = {
                s: totals_b.get(f"S{s}", {}).get("bytes", 0)
                - totals_a.get(f"S{s}", {}).get("bytes", 0)
                for s in range(NUM_SERVERS)
            }
            return delta[1] / max(sum(delta.values()), 1)

        before = share({}, totals_mid)  # cumulative up to the move
        after = share(totals_mid, totals_end)  # the post-move window
        assert before > 0.6
        assert after < before - 0.2
    finally:
        metered.close()


# ----------------------------------------------- 5a. scale up + drain down


def test_scale_up_then_drain_down_zero_loss():
    """Grow to a third server live, then retire S1 live: the trajectory
    never deviates from the fixed 2-server run, the final model is
    bitwise-identical, every freeze was bounded, and the retired identity
    serves nothing."""
    cfgs = _table_cfgs()
    batches = _batches()
    ref_losses, _ref_applied, ref_value, ref_state = _clean_reference(batches)

    van = LoopbackVan()
    try:
        servers = {
            s: KVServer(Postoffice(f"S{s}", van), cfgs, s, NUM_SERVERS)
            for s in range(NUM_SERVERS)
        }
        worker = KVWorker(Postoffice("W0", van), cfgs, NUM_SERVERS)
        mig = ShardMigrator(Postoffice("M0", van), chunk_rows=128)
        state = {"routing": worker.routing}

        def on_step(i):
            if i == STEPS // 3:
                new_server, routing = scale_up(
                    van, cfgs, state["routing"], 2,
                    migrator=mig, num_servers=3,
                )
                servers[2] = new_server
                state["routing"] = routing
                assert worker.adopt_routing(routing)
                assert routing.tables["w"].server_rows(2) > 0
            if i == 2 * STEPS // 3:
                routing = drain_down(
                    van, state["routing"], 1, migrator=mig
                )
                state["routing"] = routing
                assert worker.adopt_routing(routing)

        losses = _train(worker, batches, on_step=on_step)
        np.testing.assert_allclose(losses, ref_losses, rtol=1e-7, atol=0)

        routing = state["routing"]
        assert 1 not in routing.servers()
        assert routing.tables["w"].server_rows(1) == 0
        value, st = _assemble(routing, servers)
        np.testing.assert_array_equal(value, ref_value)
        for k in ref_state:
            np.testing.assert_array_equal(st[k], ref_state[k])
        for s in servers.values():
            assert s.migration_freeze_last_s < 5.0  # bounded, never a pause
        # the retired identity's endpoints are gone
        assert "S1" not in van._endpoints
    finally:
        van.close()


# ------------------------------------ 5b. donor killed mid-stream (chaos)


@pytest.mark.chaos
@pytest.mark.parametrize("seed", [0, 1])
def test_donor_killed_mid_stream_migration_restarts_idempotently(seed):
    """ISSUE 6 satellite: the donor dies BETWEEN migrate_send chunks under
    seeded 5% drop.  Recovery is the PR-4 same-id restart (shard from the
    sync standby), after which the migration re-runs from scratch with a
    fresh id — stale staged chunks are superseded, and the loss trajectory
    and push-apply counts exactly match the fault-free run."""
    batches = _batches()
    ref_losses, ref_applied, ref_value, ref_state = _clean_reference(batches)

    van, chaos = _reliable_stack(seed=seed, timeout=0.1, drop=0.05)
    try:
        cfgs = _table_cfgs()
        primaries, standbys = replica_lib.make_replicated_servers(
            van, cfgs, NUM_SERVERS, sync=True
        )
        worker = KVWorker(Postoffice("W0", van), cfgs, NUM_SERVERS)
        mig = ShardMigrator(Postoffice("M0", van), chunk_rows=64)
        s1_instances = [primaries[1]]
        state = {"routing": worker.routing}

        def on_step(i):
            if i != STEPS // 2:
                return
            routing = state["routing"]
            # stream PART of the range, then kill the donor mid-migration
            mid = f"test:kill:{seed}"
            mig._rpc("S1", {"op": "migrate_begin", "mid": mid, "table": "w",
                            "lo": 768, "hi": ROWS})
            mig._rpc("S1", {"op": "migrate_send", "mid": mid, "to": "S0",
                            "lo": 768, "hi": 832})
            for endpoint in ("S1", "S1.fw", "S1.mig"):
                van.unbind(endpoint)
            van.restart_node("S1")
            new_s1, source = replica_lib.restart_same_id(
                van, cfgs, 1, NUM_SERVERS, standby=standbys[1]
            )
            assert source == "replica"
            # ownership never changed: the restarted donor holds the FULL
            # pre-migration shard at the old epoch
            assert new_s1.routing.epoch == routing.epoch
            s1_instances.append(new_s1)
            # re-run the whole migration; the fresh id supersedes the
            # stale staged chunks on the recipient
            new_routing = mig.migrate(routing, "w", 768, ROWS, 0)
            state["routing"] = new_routing
            assert worker.adopt_routing(new_routing)

        losses = _train(worker, batches, on_step=on_step)
        assert len(s1_instances) == 2
        np.testing.assert_allclose(losses, ref_losses, rtol=1e-7, atol=0)
        applied = primaries[0].pushes + sum(s.pushes for s in s1_instances)
        assert applied == ref_applied  # zero lost, zero double-applied

        servers = {0: primaries[0], 1: s1_instances[-1]}
        value, st = _assemble(state["routing"], servers)
        np.testing.assert_array_equal(value, ref_value)
        for k in ref_state:
            np.testing.assert_array_equal(st[k], ref_state[k])
        assert s1_instances[-1].rows_migrated_out == 256
        assert van.flush(10)
        assert van.gave_up == 0
        assert chaos.injected_drops > 0
    finally:
        van.close()


# --------------------------------------------------- scheduler ROUTING verb


def test_scheduler_routing_broadcast_reaches_managers_and_workers():
    """Manager.set_routing: the scheduler broadcasts the table; peers store
    it, fire on_routing, and a wired worker adopts eagerly (no fence
    round-trip needed to converge)."""
    from parameter_server_tpu.core.manager import launch_local_cluster

    van, _chaos = _reliable_stack(seed=0, timeout=0.1)
    try:
        sched, managers, posts = launch_local_cluster(
            van, num_workers=1, num_servers=NUM_SERVERS, heartbeat_timeout=30
        )
        cfgs = _table_cfgs()
        worker = KVWorker(posts["W0"], cfgs, NUM_SERVERS)
        managers["W0"].on_routing.append(worker.adopt_routing)

        rt = RoutingTable.uniform(cfgs, NUM_SERVERS).move("w", 768, ROWS, 0)
        sched.set_routing(rt)

        deadline = time.time() + 5
        while time.time() < deadline:
            if worker.routing.epoch == rt.epoch:
                break
            time.sleep(0.01)
        assert worker.routing.epoch == rt.epoch
        assert worker.routing.tables["w"] == rt.tables["w"]
        assert managers["W0"].routing.epoch == rt.epoch
        # stale (lower-epoch) broadcast is ignored everywhere
        sched.routing = None
        sched.set_routing(RoutingTable.uniform(cfgs, NUM_SERVERS))
        time.sleep(0.1)
        assert worker.routing.epoch == rt.epoch
    finally:
        van.close()


# ------------------------------------------------------- counters satellite


def test_migration_counters_merge_into_dashboard_group():
    from parameter_server_tpu.utils.metrics import CounterGroup

    van = LoopbackVan()
    try:
        cfgs = _table_cfgs()
        servers = {
            s: KVServer(Postoffice(f"S{s}", van), cfgs, s, NUM_SERVERS)
            for s in range(NUM_SERVERS)
        }
        worker = KVWorker(Postoffice("W0", van), cfgs, NUM_SERVERS)
        mig = ShardMigrator(Postoffice("M0", van), chunk_rows=128)
        group = CounterGroup(*servers.values(), worker, mig)

        new_routing = mig.migrate(worker.routing, "w", 768, ROWS, 0)
        # a stale push: fenced once, then adopted and re-applied
        keys = _keys_hashing_into(768, ROWS, 8)
        worker.push_sync("w", keys, np.ones(keys.size, np.float32), timeout=60)
        assert worker.routing.epoch == new_routing.epoch

        got = group.counters()
        assert got["rows_migrated_out"] == 256
        assert got["rows_migrated_in"] >= 256
        assert got["fenced_rejects"] > 0
        assert got["refresh_retries"] > 0
        assert got["rows_moved"] == 256
        assert got["migrations"] == 1
        assert got["migration_freeze_s"] > 0.0
    finally:
        van.close()


# ------------------------- 7. hand-over of a dim-1 shard keeps its host forms


def test_dim1_hand_over_gives_and_takes_column_rows():
    """The table of this file is dim 1, so its planes are flat on the device
    (PR 26); the snapshot, the row hand-over and the migration still give and
    take ``[rows(+1), 1]`` NumPy arrays, and the planes stay flat through a
    live migration that resizes both shards."""
    van = LoopbackVan()
    try:
        servers = {
            s: KVServer(Postoffice(f"S{s}", van), _table_cfgs(), s, NUM_SERVERS)
            for s in range(NUM_SERVERS)
        }
        worker = KVWorker(Postoffice("W0", van), _table_cfgs(), NUM_SERVERS)
        _train(worker, _batches()[:3])

        def flat(srv):
            t = srv.tables["w"]
            return [t.value.shape, t.state["sum_sq"].shape] == [(t.rows + 1,)] * 2

        assert all(flat(s) for s in servers.values())
        snap = servers[1].export_shard()["w"]
        assert snap["value"].shape == (513, 1)
        assert snap["state"]["sum_sq"].shape == (513, 1)
        v, st = servers[1].export_range("w", 600, 640)
        assert v.shape == (40, 1) and st["sum_sq"].shape == (40, 1)
        np.testing.assert_array_equal(v, snap["value"][600 - 512 : 640 - 512])
        assert np.abs(snap["value"]).max() > 0 and snap["state"]["sum_sq"].max() > 0

        # a twin adopts the snapshot wholesale and is bit-identical, flat
        van2 = LoopbackVan()
        try:
            twin = KVServer(Postoffice("S1", van2), _table_cfgs(), 1, NUM_SERVERS)
            twin.import_shard({"w": snap})
            assert flat(twin)
            again = twin.export_shard()["w"]
            np.testing.assert_array_equal(again["value"], snap["value"])
            np.testing.assert_array_equal(
                again["state"]["sum_sq"], snap["state"]["sum_sq"]
            )
        finally:
            van2.close()

        before, before_s = _assemble(worker.routing, servers)
        assert before.shape == (ROWS, 1)
        mig = ShardMigrator(Postoffice("M0", van), chunk_rows=128)
        routing = mig.migrate(worker.routing, "w", 768, ROWS, 0)
        assert worker.adopt_routing(routing)
        assert servers[0].tables["w"].rows == 768
        assert servers[1].tables["w"].rows == 256
        assert all(flat(s) for s in servers.values())
        after, after_s = _assemble(routing, servers)
        np.testing.assert_array_equal(after, before)
        np.testing.assert_array_equal(after_s["sum_sq"], before_s["sum_sq"])
        _train(worker, _batches()[3:5])  # the resized shards apply and serve
        assert all(flat(s) for s in servers.values())
    finally:
        van.close()
