"""Durability plane (PR 16): partitioned incremental snapshot drills.

The acceptance contract, as tests:

1. a REBALANCED (non-uniform) fleet snapshots mid-training and restores
   onto a DIFFERENT server count with bitwise parity, optimizer slots
   included — pushes after the restore continue bit-identically;
2. an incremental chain (full -> delta -> delta) replays to the same bits
   as a one-shot full snapshot of the same state;
3. the snapshot is non-blocking: pushes land between the per-segment bulk
   writes, and the only freeze (the ``snap_commit`` delta export) is
   bounded by the dirty set — measured smaller than a full-table
   export+write would be;
4. a server dying mid-snapshot can never corrupt the restore point: the
   manifest is written LAST, so a torn run leaves no manifest and
   ``latest_snapshot`` still returns the previous step;
5. CRC armor: ``finalize_snapshot`` refuses a torn segment file, and
   ``read_snapshot``/``latest_snapshot`` reject a corrupted manifest;
6. restore-source ordering on a same-id restart: replica chain >
   partitioned snapshot > legacy checkpoint > cold, with corrupt
   snapshots falling through instead of wedging the restart;
7. the legacy uniform-format guard raises the TYPED
   ``CheckpointLayoutError`` (satellite: callers can tell "layout refused"
   from real IO failures);
8. retention never deletes an incremental chain's base out from under it,
   and sweeps aborted (manifest-less) snapshot dirs.
"""

import json
import os
import time

import numpy as np
import pytest

from parameter_server_tpu import checkpoint
from parameter_server_tpu.config import (
    CheckpointConfig,
    OptimizerConfig,
    TableConfig,
)
from parameter_server_tpu.core.messages import Message, Task, TaskKind
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.kv import replica as replica_lib
from parameter_server_tpu.kv.migrate import ShardMigrator
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.kv.worker import KVWorker
from parameter_server_tpu.utils.keys import HashLocalizer

pytestmark = pytest.mark.checkpoint

ROWS = 1024
DIM = 4
SEED = 1234


def _cfgs(rows=ROWS, dim=DIM):
    return {
        "w": TableConfig(
            name="w", rows=rows, dim=dim,
            optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.5),
        )
    }


def _cluster(van, num_servers, *, cfgs=None, worker_name="W0"):
    cfgs = cfgs or _cfgs()
    servers = [
        KVServer(Postoffice(f"S{i}", van), cfgs, i, num_servers)
        for i in range(num_servers)
    ]
    worker = KVWorker(
        Postoffice(worker_name, van), cfgs, num_servers, min_bucket=16
    )
    return servers, worker


def _push(worker, *, seed, count=256, rows=ROWS, dim=DIM):
    rng = np.random.RandomState(seed)
    keys = np.unique(
        rng.randint(0, 1 << 31, size=count).astype(np.uint64)
    )
    grads = rng.randn(keys.size, dim).astype(np.float32)
    worker.push_sync("w", keys, grads, timeout=30)
    return keys, grads


def _keys_hashing_into(lo, hi, count, *, rows=ROWS, start=0):
    """Raw keys whose HashLocalizer slot lands in global rows [lo, hi)."""
    loc = HashLocalizer(rows)
    found = []
    k = start
    while len(found) < count:
        cand = np.arange(k, k + 4096, dtype=np.int64)
        slots = loc.assign(cand.astype(np.uint64))
        hit = cand[(slots >= lo) & (slots < hi)]
        found.extend(int(x) for x in hit)
        k += 4096
    return np.asarray(found[:count], dtype=np.uint64)


def _push_keys(worker, keys, *, seed, dim=DIM):
    grads = np.random.RandomState(seed).randn(
        keys.size, dim
    ).astype(np.float32)
    worker.push_sync("w", keys, grads, timeout=30)
    return grads


# ------------------------------------------------- 1. reshard-restore parity


@pytest.mark.parametrize("incremental", [False, True], ids=["full", "chain"])
def test_rebalanced_snapshot_restores_to_any_fleet_shape(
    tmp_path, record_property, incremental
):
    """``chain``: the restore point is an incremental snapshot that carries
    the untouched segments' files from its base by reference, and another
    fleet shape reads them through that reference."""
    record_property("chaos_seed", SEED)
    van = LoopbackVan()
    try:
        servers, worker = _cluster(van, 3)
        keys, _ = _push(worker, seed=SEED)
        # rebalance live: move the tail of S2's range onto S0, so the
        # layout is one the legacy uniform format cannot express
        mig = ShardMigrator(Postoffice("M0", van), chunk_rows=128)
        new_routing = mig.migrate(worker.routing, "w", 800, ROWS, 0)
        assert worker.adopt_routing(new_routing)
        _push(worker, seed=SEED + 1)

        segments = worker.routing.tables["w"].segments()
        if incremental:
            worker.save_snapshot(str(tmp_path), 6)
            # writes confined to one segment: the others' clocks stand still
            hot = _keys_hashing_into(segments[0][0], segments[0][1], 24)
            _push_keys(worker, hot, seed=SEED + 3)
        summary = worker.save_snapshot(
            str(tmp_path), 7, base_step=6 if incremental else None
        )
        assert summary["segments"] == len(segments)
        assert summary["carried"] == (len(segments) - 1 if incremental else 0)
        ref = np.asarray(worker.pull_sync("w", keys, timeout=30))

        extra = np.random.RandomState(SEED + 2).randn(
            keys.size, DIM
        ).astype(np.float32)
        worker.push_sync("w", keys, extra, timeout=30)
        ref_after = np.asarray(worker.pull_sync("w", keys, timeout=30))

        for n in (2, 5):
            van2 = LoopbackVan()
            try:
                _s2, w2 = _cluster(van2, n)
                w2.load_snapshot(str(tmp_path), 7)
                got = np.asarray(w2.pull_sync("w", keys, timeout=30))
                np.testing.assert_array_equal(ref, got)
                # optimizer slots restored bitwise: the SAME gradient must
                # produce the SAME adagrad step as the writer fleet took
                w2.push_sync("w", keys, extra, timeout=30)
                got_after = np.asarray(w2.pull_sync("w", keys, timeout=30))
                np.testing.assert_array_equal(ref_after, got_after)
            finally:
                van2.close()
    finally:
        van.close()


# ------------------------------------------- 2. incremental chain == full


def test_incremental_chain_bitwise_equals_full_snapshot(tmp_path):
    root = str(tmp_path)
    van = LoopbackVan()
    try:
        _servers, worker = _cluster(van, 3)
        _push(worker, seed=SEED)
        worker.save_snapshot(root, 1)
        # incremental writes confined to the FIRST segment so the other
        # two segments' version clocks stand still and their files carry
        seg0 = worker.routing.tables["w"].segments()[0]
        hot = _keys_hashing_into(seg0[0], seg0[1], 24)
        _push_keys(worker, hot, seed=SEED + 1)
        inc2 = worker.save_snapshot(root, 2, base_step=1)
        _push_keys(worker, hot, seed=SEED + 2)
        inc3 = worker.save_snapshot(root, 3, base_step=2)
        # the small follow-up pushes only touch a few segments: the chain
        # must actually carry, or this test is vacuously "incremental"
        assert inc2["carried"] + inc3["carried"] > 0
        full = worker.save_snapshot(root, 9)  # one-shot, no base
        m_chain = checkpoint.read_snapshot(root, 3)
        m_full = checkpoint.read_snapshot(root, 9)
        assert m_chain["base_step"] == 2 and m_full["base_step"] is None
        v_c, s_c = checkpoint.snapshot_rows(root, m_chain, "w", 0, ROWS)
        v_f, s_f = checkpoint.snapshot_rows(root, m_full, "w", 0, ROWS)
        np.testing.assert_array_equal(v_c, v_f)
        assert sorted(s_c) == sorted(s_f)
        for k in s_c:
            np.testing.assert_array_equal(s_c[k], s_f[k])
        assert full["carried"] == 0
    finally:
        van.close()


# --------------------------- 3. non-blocking: dirty-delta-bounded freeze


def test_commit_freeze_is_delta_bounded(tmp_path):
    root = str(tmp_path)
    cfgs = _cfgs(rows=3 * 4096, dim=32)
    van = LoopbackVan()
    try:
        servers, worker = _cluster(van, 3, cfgs=cfgs)

        def control(payloads_by_server):
            msgs = [
                Message(
                    task=Task(TaskKind.CONTROL, worker.name, payload=p),
                    recver=f"S{s}",
                )
                for s, p in payloads_by_server
            ]
            return worker._control_round(msgs, "snap", 30)

        _push(worker, seed=SEED, count=2048, rows=3 * 4096, dim=32)
        sid = "freeze-drill"
        control([(s, {"op": "snap_begin", "sid": sid}) for s in range(3)])
        # writes DURING the open window dirty rows against the files
        k1, g1 = _push(worker, seed=SEED + 1, count=64, dim=32)
        writes = [
            (
                owner,
                {"op": "snap_write", "sid": sid, "root": root, "step": 1,
                 "table": "w", "lo": lo, "hi": hi},
            )
            for lo, hi, owner in worker.routing.tables["w"].segments()
        ]
        entries = [dict(r.task.payload["entry"]) for r in control(writes)]
        # ... and writes AFTER a segment file is on disk go stale against
        # it — exactly what the commit's delta log must re-export
        k2, g2 = _push(worker, seed=SEED + 2, count=64, dim=32)
        deltas, freeze_by_server = [], {}
        for r in control(
            [(s, {"op": "snap_commit", "sid": sid, "root": root, "step": 1})
             for s in range(3)]
        ):
            pl = r.task.payload
            deltas.extend(pl["deltas"])
            freeze_by_server[len(freeze_by_server)] = float(pl["freeze_s"])
        assert sum(d["rows"] for d in deltas) > 0
        # the freeze bound: every server's delta export must beat what a
        # BLOCKING snapshot would have frozen for (full shard export +
        # segment write, measured on the largest shard here and now)
        lo, hi = 0, 4096
        t0 = time.perf_counter()
        v, st = servers[0].export_range("w", lo, hi)
        checkpoint.write_segment_file(root, 99, "w", lo, hi, v, st)
        full_freeze = time.perf_counter() - t0
        assert max(freeze_by_server.values()) < full_freeze, (
            freeze_by_server, full_freeze
        )
        checkpoint.finalize_snapshot(
            root, 1, worker.routing.to_payload(), entries, deltas
        )
        # delta ordering proof: the mid-window pushes survive the restore
        ref = np.asarray(worker.pull_sync("w", k2, timeout=30))
        van2 = LoopbackVan()
        try:
            _s2, w2 = _cluster(van2, 2, cfgs=cfgs)
            w2.load_snapshot(root, 1)
            np.testing.assert_array_equal(
                ref, np.asarray(w2.pull_sync("w", k2, timeout=30))
            )
        finally:
            van2.close()
    finally:
        van.close()


# ------------------------------------------------ 4. kill mid-snapshot


def test_kill_mid_snapshot_leaves_previous_restore_point(
    tmp_path, monkeypatch, record_property
):
    record_property("chaos_seed", SEED)
    root = str(tmp_path)
    van = LoopbackVan()
    try:
        servers, worker = _cluster(van, 3)
        keys, _ = _push(worker, seed=SEED)
        worker.save_snapshot(root, 1)
        assert checkpoint.latest_snapshot(root) == 1
        _push(worker, seed=SEED + 1)

        real_write = checkpoint.write_segment_file
        calls = {"n": 0}

        def dying_write(*a, **kw):
            calls["n"] += 1
            if calls["n"] == 2:  # first segment lands, then the "crash"
                raise OSError("server killed mid-snapshot")
            return real_write(*a, **kw)

        monkeypatch.setattr(checkpoint, "write_segment_file", dying_write)
        with pytest.raises(RuntimeError):
            worker.save_snapshot(root, 2)
        monkeypatch.undo()

        # the manifest is written LAST: a torn run leaves none, so the
        # previous snapshot stays the restore point and every server's
        # dirty tracking was released by the abort broadcast
        assert not os.path.exists(
            os.path.join(root, "snap_000002", "MANIFEST.json")
        )
        assert checkpoint.latest_snapshot(root) == 1
        assert all(not s._snapshots for s in servers)

        # the plane is not wedged: the next snapshot commits and restores
        worker.save_snapshot(root, 3)
        assert checkpoint.latest_snapshot(root) == 3
        ref = np.asarray(worker.pull_sync("w", keys, timeout=30))
        van2 = LoopbackVan()
        try:
            _s2, w2 = _cluster(van2, 2)
            w2.load_snapshot(root, 3)
            np.testing.assert_array_equal(
                ref, np.asarray(w2.pull_sync("w", keys, timeout=30))
            )
        finally:
            van2.close()
        # retention sweeps the aborted step-2 orphan dir (no manifest)
        checkpoint.retain_snapshots(root, 2)
        assert not os.path.isdir(os.path.join(root, "snap_000002"))
    finally:
        van.close()


# ------------------------------------------------------- 5. CRC armor


def test_finalize_refuses_torn_segment_file(tmp_path):
    root = str(tmp_path)
    rng = np.random.RandomState(0)
    v = rng.randn(8, 4).astype(np.float32)
    st = {"g2": rng.rand(8, 4).astype(np.float32)}
    e1 = checkpoint.write_segment_file(root, 1, "w", 0, 8, v, st)
    e2 = checkpoint.write_segment_file(
        root, 1, "w", 8, 16, v, {"g2": st["g2"]}
    )
    routing = {"tables": {"w": {"rows": 16}}}
    # tear the second file (truncate: the torn-write shape a crash leaves)
    path = os.path.join(root, e2["file"])
    with open(path, "r+b") as f:
        f.truncate(os.path.getsize(path) // 2)
    with pytest.raises(checkpoint.CheckpointCorruptError):
        checkpoint.finalize_snapshot(root, 1, routing, [e1, e2], [])
    assert checkpoint.latest_snapshot(root) is None
    # a missing file is refused too (the entry names it, the disk lost it)
    os.unlink(path)
    with pytest.raises(FileNotFoundError):
        checkpoint.finalize_snapshot(root, 1, routing, [e1, e2], [])
    # and a coverage gap can never commit
    with pytest.raises(checkpoint.CheckpointCorruptError):
        checkpoint.finalize_snapshot(root, 1, routing, [e1], [])


def test_corrupt_manifest_is_rejected_and_skipped(tmp_path):
    root = str(tmp_path)
    van = LoopbackVan()
    try:
        _servers, worker = _cluster(van, 2)
        _push(worker, seed=SEED)
        worker.save_snapshot(root, 1)
        _push(worker, seed=SEED + 1)
        worker.save_snapshot(root, 2)
        # flip payload bytes but keep valid JSON: only the CRC can tell
        mpath = os.path.join(root, "snap_000002", "MANIFEST.json")
        with open(mpath) as f:
            doc = json.load(f)
        doc["segments"][0]["crc"] = int(doc["segments"][0]["crc"]) ^ 0xBEEF
        with open(mpath, "w") as f:
            json.dump(doc, f)
        with pytest.raises(checkpoint.CheckpointCorruptError):
            checkpoint.read_snapshot(root, 2)
        # latest_snapshot skips the corrupt head and serves the older one
        assert checkpoint.latest_snapshot(root) == 1
        # non-JSON garbage is CheckpointCorruptError as well, not a decode
        # crash in the restore path
        with open(mpath, "w") as f:
            f.write("{ torn")
        with pytest.raises(checkpoint.CheckpointCorruptError):
            checkpoint.read_snapshot(root, 2)
    finally:
        van.close()


# ---------------------------------------------- 6. restore-source ordering


def test_restart_restore_source_ordering(tmp_path):
    root = str(tmp_path)
    cfgs = _cfgs()
    van = LoopbackVan()
    try:
        _servers, worker = _cluster(van, 1)
        keys, _ = _push(worker, seed=SEED)
        worker.save_model(root, 1)  # legacy uniform checkpoint
        _push(worker, seed=SEED + 1)
        worker.save_snapshot(root, 2)  # partitioned, newer state
        ref = np.asarray(worker.pull_sync("w", keys, timeout=30))

        # partitioned beats legacy
        s, source = replica_lib.restart_same_id(
            van, cfgs, 0, 1, ckpt_root=root
        )
        assert source == "partitioned"
        got = np.asarray(worker.pull_sync("w", keys, timeout=30))
        np.testing.assert_array_equal(ref, got)

        # a live standby beats the partitioned snapshot
        standby = KVServer(Postoffice("R0", van), cfgs, 0, 1)
        standby.import_shard(s.export_shard())
        _s2, source = replica_lib.restart_same_id(
            van, cfgs, 0, 1, standby=standby, ckpt_root=root
        )
        assert source == "replica"

        # corrupt every snapshot manifest: fall through to legacy
        for step in checkpoint.list_snapshots(root):
            with open(
                os.path.join(root, f"snap_{step:06d}", "MANIFEST.json"), "w"
            ) as f:
                f.write("not json")
        _s3, source = replica_lib.restart_same_id(
            van, cfgs, 0, 1, ckpt_root=root
        )
        assert source == "checkpoint"

        # nothing on disk at all: cold
        _s4, source = replica_lib.restart_same_id(
            van, cfgs, 0, 1, ckpt_root=str(tmp_path / "empty")
        )
        assert source == "cold"
    finally:
        van.close()


def test_restart_after_migration_adopts_snapshot_routing(tmp_path):
    """Same-id restart on a MIGRATED fleet must rejoin at the snapshot's
    routing epoch: a fresh server starts at uniform epoch 0 and would not
    own its migrated segments — every worker leg into them would fence
    forever (found by driving the full kill/restart flow end-to-end)."""
    root = str(tmp_path)
    cfgs = _cfgs()
    van = LoopbackVan()
    try:
        servers, worker = _cluster(van, 3)
        keys, _ = _push(worker, seed=SEED)
        # move the tail of S2's range onto S0, then snapshot the new shape
        mig = ShardMigrator(Postoffice("M0", van), chunk_rows=128)
        assert worker.adopt_routing(
            mig.migrate(worker.routing, "w", 800, ROWS, 0)
        )
        _push(worker, seed=SEED + 1)
        worker.save_snapshot(root, 1)
        ref = np.asarray(worker.pull_sync("w", keys, timeout=30))
        van.unbind("S0")
        van.unbind("S0.fw")
        srv, source = replica_lib.restart_same_id(
            van, cfgs, 0, 3, ckpt_root=root
        )
        assert source == "partitioned"
        assert srv.routing.epoch == worker.routing.epoch
        got = np.asarray(worker.pull_sync("w", keys, timeout=30))
        np.testing.assert_array_equal(ref, got)
        # training continues through the restored, re-fenced server
        _push(worker, seed=SEED + 2)
        after = np.asarray(worker.pull_sync("w", keys, timeout=30))
        assert not np.array_equal(ref, after)
    finally:
        van.close()


# --------------------------------------- 7. typed layout error + auto mode


def test_legacy_guard_raises_typed_layout_error(tmp_path):
    van = LoopbackVan()
    try:
        servers, worker = _cluster(van, 2)
        _push(worker, seed=SEED)
        mig = ShardMigrator(Postoffice("M0", van), chunk_rows=128)
        assert worker.adopt_routing(
            mig.migrate(worker.routing, "w", 900, ROWS, 0)
        )
        with pytest.raises(checkpoint.CheckpointLayoutError):
            servers[0].save_checkpoint(str(tmp_path), 1)
        # typed but still a RuntimeError: the wire contract (server errors
        # stringify) and legacy except clauses keep working
        assert issubclass(
            checkpoint.CheckpointLayoutError, RuntimeError
        )
        # the partitioned plane takes the same layout without complaint
        worker.save_snapshot(str(tmp_path), 1)
        assert checkpoint.latest_snapshot(str(tmp_path)) == 1
    finally:
        van.close()


def test_elastic_auto_mode_picks_the_right_plane(tmp_path):
    from parameter_server_tpu.learner.elastic import ElasticTrainer

    root = str(tmp_path)
    van = LoopbackVan()
    try:
        _servers, worker = _cluster(van, 2)
        trainer = ElasticTrainer.__new__(ElasticTrainer)
        trainer.ckpt_root = root
        trainer.ckpt_config = CheckpointConfig(mode="auto")
        # uniform layout, no chain: legacy keeps old readers working
        assert trainer._use_partitioned(worker) is False
        # an existing chain is always extended, layout regardless
        worker.save_snapshot(root, 1)
        assert trainer._use_partitioned(worker) is True
        # explicit modes override the heuristic
        trainer.ckpt_config = CheckpointConfig(mode="legacy")
        assert trainer._use_partitioned(worker) is False
        trainer.ckpt_config = CheckpointConfig(mode="partitioned")
        assert trainer._use_partitioned(worker) is True
        # a migrated layout forces the partitioned plane in auto
        trainer.ckpt_config = CheckpointConfig(mode="auto")
        trainer.ckpt_root = str(tmp_path / "fresh")
        mig = ShardMigrator(Postoffice("M0", van), chunk_rows=128)
        assert worker.adopt_routing(
            mig.migrate(worker.routing, "w", 900, ROWS, 0)
        )
        assert trainer._use_partitioned(worker) is True
    finally:
        van.close()


def test_checkpoint_config_validation():
    with pytest.raises(ValueError):
        CheckpointConfig(interval_s=0)
    with pytest.raises(ValueError):
        CheckpointConfig(max_delta_rows=0)
    with pytest.raises(ValueError):
        CheckpointConfig(retention=-1)
    with pytest.raises(ValueError):
        CheckpointConfig(mode="sometimes")


# ------------------------------------------------- 8. retention + chains


def test_retention_preserves_incremental_chain_bases(tmp_path):
    root = str(tmp_path)
    van = LoopbackVan()
    try:
        _servers, worker = _cluster(van, 3)
        keys, _ = _push(worker, seed=SEED)
        worker.save_snapshot(root, 1)
        worker.save_snapshot(root, 2, base_step=1)  # carries everything
        worker.save_snapshot(root, 3, base_step=2)
        ref = np.asarray(worker.pull_sync("w", keys, timeout=30))
        checkpoint.retain_snapshots(root, 1)
        # only step 3 is "kept", but its carried files live in snap dir 1:
        # the chain base must survive, and the restore must still verify
        assert checkpoint.list_snapshots(root)[-1] == 3
        assert os.path.isdir(os.path.join(root, "snap_000001"))
        van2 = LoopbackVan()
        try:
            _s2, w2 = _cluster(van2, 2)
            w2.load_snapshot(root, 3)
            np.testing.assert_array_equal(
                ref, np.asarray(w2.pull_sync("w", keys, timeout=30))
            )
        finally:
            van2.close()
        checkpoint.retain_snapshots(root, 0)
        assert checkpoint.list_snapshots(root) == []
    finally:
        van.close()


# ------------------------------------------------- observability plumbing


def test_ckpt_counters_and_events_flow(tmp_path):
    from parameter_server_tpu.core import flightrec
    from parameter_server_tpu.utils.slo import durability_plane_specs

    flightrec.configure(enabled=True, clear=True)
    van = LoopbackVan()
    try:
        servers, worker = _cluster(van, 2)
        before = servers[0].counters()
        assert before["ckpt_commits"] == 0 and before["ckpt_age_s"] >= 0.0
        _push(worker, seed=SEED)
        worker.save_snapshot(str(tmp_path), 1)
        after = servers[0].counters()
        assert after["ckpt_commits"] == 1
        # the age gauge re-bases on commit: it must be (near) zero now and
        # strictly below the pre-commit construction-based age
        assert after["ckpt_age_s"] <= before["ckpt_age_s"] + 1.0
        kinds = {e["kind"] for e in flightrec.get().events()}
        assert {"ckpt.begin", "ckpt.segment", "ckpt.commit"} <= kinds
        spec = durability_plane_specs(max_age_s=120.0)[0]
        assert spec.metric == "ckpt_age_s" and spec.source == "gauge"
        # routing churn aborts open snapshots, visible as the postmortem
        # anomaly anchor
        mig = ShardMigrator(Postoffice("M0", van), chunk_rows=128)
        sid_msgs = [
            Message(
                task=Task(TaskKind.CONTROL, worker.name,
                          payload={"op": "snap_begin", "sid": "doomed"}),
                recver="S0",
            )
        ]
        worker._control_round(sid_msgs, "snap_begin", 30)
        assert worker.adopt_routing(
            mig.migrate(worker.routing, "w", 900, ROWS, 0)
        )
        assert not servers[0]._snapshots
        assert "ckpt.abort" in {e["kind"] for e in flightrec.get().events()}
        assert "ckpt.abort" in flightrec.anomaly_kinds()
    finally:
        van.close()
        flightrec.configure(enabled=True, clear=True)
