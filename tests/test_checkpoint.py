"""Checkpoint/resume: sharded save, elastic restore, commit marker.

Covers the SURVEY.md §5 checkpoint plan: table shards + optimizer-state rows
+ consistency clocks, restore under a *different* server count (elastic
re-shard), and the reference SaveModel broadcast path over the Van.
"""

import numpy as np
import pytest

from parameter_server_tpu import checkpoint
from parameter_server_tpu.config import OptimizerConfig, TableConfig
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.kv.table import KVTable
from parameter_server_tpu.kv.worker import KVWorker
from parameter_server_tpu.utils.keys import HashLocalizer


def _cfgs(rows=1000, dim=4, kind="adagrad"):
    return {
        "w": TableConfig(
            name="w",
            rows=rows,
            dim=dim,
            optimizer=OptimizerConfig(kind=kind, learning_rate=0.5),
        )
    }


def _cluster(van, cfgs, num_servers, worker_name="W0", localizers=None):
    servers = [
        KVServer(Postoffice(f"S{i}", van), cfgs, i, num_servers)
        for i in range(num_servers)
    ]
    worker = KVWorker(
        Postoffice(worker_name, van),
        cfgs,
        num_servers,
        min_bucket=16,
        localizers=localizers,
    )
    return servers, worker


def test_save_restore_roundtrip(tmp_path):
    van = LoopbackVan()
    try:
        cfgs = _cfgs()
        servers, worker = _cluster(van, cfgs, 2)
        keys = np.arange(0, 64, dtype=np.uint64) * 7919
        grads = np.random.RandomState(0).randn(64, 4).astype(np.float32)
        worker.wait(worker.push("w", keys, grads), timeout=10)
        before = worker.pull_sync("w", keys, timeout=10)

        worker.save_model(str(tmp_path), step=3, clocks=[1, 1], extras={"epoch": 2})

        # clobber the tables, then restore over the Van
        for s in servers:
            t = s.tables["w"]
            t.set_value(np.full((t.rows + 1, t.dim), 9.0, np.float32))
        worker.load_model(str(tmp_path), step=3)
        after = worker.pull_sync("w", keys, timeout=10)
        np.testing.assert_allclose(after, before, rtol=1e-6)

        info = checkpoint.read_info(str(tmp_path), 3)
        assert info.clocks == [1, 1]
        assert info.extras["epoch"] == 2
        # the key->row mapping is auto-recorded for offline eval
        assert info.extras["localizers"]["w"]["kind"] == "HashLocalizer"
        assert info.extras["localizers"]["w"]["hash_bits"] == 64
        assert checkpoint.latest_step(str(tmp_path)) == 3
    finally:
        van.close()


def test_optimizer_state_survives_resume(tmp_path):
    """Resume must continue the adagrad trajectory, not restart it."""
    van = LoopbackVan()
    try:
        cfgs = _cfgs(kind="adagrad")
        loc = {"w": HashLocalizer(1000)}
        servers, worker = _cluster(van, cfgs, 2, localizers=loc)
        keys = np.array([11, 22, 33], dtype=np.uint64)
        g = np.ones((3, 4), dtype=np.float32)
        worker.wait(worker.push("w", keys, g), timeout=10)
        worker.save_model(str(tmp_path), step=1)
        # continue training in the original cluster -> ground truth
        worker.wait(worker.push("w", keys, g), timeout=10)
        truth = worker.pull_sync("w", keys, timeout=10)

        # fresh cluster restores and takes the same second step
        van2 = LoopbackVan()
        try:
            servers2, worker2 = _cluster(van2, cfgs, 2, localizers=loc)
            worker2.load_model(str(tmp_path), step=1)
            worker2.wait(worker2.push("w", keys, g), timeout=10)
            resumed = worker2.pull_sync("w", keys, timeout=10)
            np.testing.assert_allclose(resumed, truth, rtol=1e-6)
        finally:
            van2.close()
    finally:
        van.close()


@pytest.mark.parametrize("new_servers", [1, 3, 4])
def test_elastic_restore_different_server_count(tmp_path, new_servers):
    """Save with 2 servers, restore with N: the elastic re-shard path."""
    van = LoopbackVan()
    try:
        cfgs = _cfgs(rows=500, dim=2, kind="sgd")
        loc = {"w": HashLocalizer(500)}
        servers, worker = _cluster(van, cfgs, 2, localizers=loc)
        keys = (np.arange(80, dtype=np.uint64) * 104729) % 100000
        grads = np.random.RandomState(1).randn(80, 2).astype(np.float32)
        worker.wait(worker.push("w", keys, grads), timeout=10)
        before = worker.pull_sync("w", keys, timeout=10)
        worker.save_model(str(tmp_path), step=7)
    finally:
        van.close()

    van2 = LoopbackVan()
    try:
        servers2, worker2 = _cluster(
            van2, cfgs, new_servers, worker_name="W0", localizers=loc
        )
        worker2.load_model(str(tmp_path), step=7)
        after = worker2.pull_sync("w", keys, timeout=10)
        np.testing.assert_allclose(after, before, rtol=1e-6)
    finally:
        van2.close()


def test_uncommitted_checkpoint_ignored(tmp_path):
    cfg = _cfgs(rows=100, dim=1)["w"]
    table = KVTable(cfg, rows=100)
    checkpoint.save_shard(str(tmp_path), 5, "w", table, 0, 1, 0)
    # no finalize -> invisible
    assert checkpoint.latest_step(str(tmp_path)) is None
    checkpoint.finalize(str(tmp_path), 5, 1, {"w": 100})
    assert checkpoint.latest_step(str(tmp_path)) == 5


def test_finalize_refuses_missing_shards(tmp_path):
    cfg = _cfgs(rows=100, dim=1)["w"]
    table = KVTable(cfg, rows=50)
    checkpoint.save_shard(str(tmp_path), 2, "w", table, 0, 2, 0)
    with pytest.raises(FileNotFoundError):
        checkpoint.finalize(str(tmp_path), 2, 2, {"w": 100})


def test_load_global_weights_and_retain(tmp_path):
    cfg = _cfgs(rows=100, dim=3)["w"]
    import jax.numpy as jnp

    full = np.arange(300, dtype=np.float32).reshape(100, 3)
    for step in (1, 2, 3):
        for s, (lo, hi) in enumerate(((0, 50), (50, 100))):
            t = KVTable(cfg, rows=hi - lo)
            buf = np.zeros((t.rows + 1, 3), np.float32)
            buf[: t.rows] = full[lo:hi] * step
            t.value = jnp.asarray(buf)
            checkpoint.save_shard(str(tmp_path), step, "w", t, s, 2, lo)
        checkpoint.finalize(str(tmp_path), step, 2, {"w": 100})
    got = checkpoint.load_global_weights(str(tmp_path), 2, "w")
    np.testing.assert_allclose(got, full * 2)
    checkpoint.retain(str(tmp_path), keep=1)
    assert checkpoint.list_steps(str(tmp_path)) == [3]


def test_save_model_failure_raises_not_hangs(tmp_path):
    """A server-side save error must surface as an exception on the worker
    (error reply), not an eternal wait() on the missing response leg."""
    van = LoopbackVan()
    try:
        cfgs = _cfgs(rows=100, dim=1)
        servers, worker = _cluster(van, cfgs, 2)
        bad = tmp_path / "not_a_dir"
        bad.write_text("file in the way")
        with pytest.raises(RuntimeError, match="failed on"):
            worker.save_model(str(bad / "ckpt"), step=1, timeout=30)
    finally:
        van.close()


def test_dense_checkpoint_roundtrip_and_reshard(tmp_path):
    """Dense segments save/restore, including under a new server count."""
    from parameter_server_tpu.kv.dense import DenseKVServer, DenseKVWorker

    van = LoopbackVan()
    try:
        opt = OptimizerConfig(kind="adagrad", learning_rate=0.5)
        total = 1000
        servers = [
            DenseKVServer(
                Postoffice(f"S{i}", van), {"m": (total, opt)}, i, 2
            )
            for i in range(2)
        ]
        worker = DenseKVWorker(Postoffice("W0", van), {"m": total}, 2)
        rng = np.random.RandomState(0)
        for _ in range(3):
            worker.wait(
                worker.push("m", rng.randn(total).astype(np.float32)),
                timeout=10,
            )
        before = worker.pull_sync("m", timeout=10)
        worker.save_model(str(tmp_path), step=4, clocks=[3])
    finally:
        van.close()

    # restore into a 3-server cluster: elastic re-shard of dense segments
    van2 = LoopbackVan()
    try:
        servers2 = [
            DenseKVServer(
                Postoffice(f"S{i}", van2), {"m": (total, opt)}, i, 3
            )
            for i in range(3)
        ]
        worker2 = DenseKVWorker(Postoffice("W0", van2), {"m": total}, 3)
        worker2.load_model(str(tmp_path), step=4)
        after = worker2.pull_sync("m", timeout=10)
        np.testing.assert_allclose(after, before, rtol=1e-6)
        # optimizer state restored too: a further identical push moves the
        # weights the same way it would have in the original cluster
        worker2.wait(
            worker2.push("m", np.ones(total, np.float32)), timeout=10
        )
        moved = worker2.pull_sync("m", timeout=10)
        assert np.abs(moved - after).max() > 1e-4
        assert checkpoint.read_info(str(tmp_path), 4).clocks == [3]
    finally:
        van2.close()


def test_retain_keep_zero_deletes_all(tmp_path):
    """retain(keep=0) deletes everything; negative keep raises (ADVICE r1)."""
    van = LoopbackVan()
    try:
        cfgs = _cfgs()
        _servers, worker = _cluster(van, cfgs, 2)
        for step in (1, 2, 3):
            worker.save_model(str(tmp_path), step=step)
        checkpoint.retain(str(tmp_path), keep=2)
        assert checkpoint.list_steps(str(tmp_path)) == [2, 3]
        checkpoint.retain(str(tmp_path), keep=0)
        assert checkpoint.list_steps(str(tmp_path)) == []
        with pytest.raises(ValueError):
            checkpoint.retain(str(tmp_path), keep=-1)
    finally:
        van.close()


def test_eval_reconstructs_manifest_localizer(tmp_path):
    """Offline eval must score with the TRAINING hash width, not a default.

    A 32-bit-hash table evaluated through the 64-bit default localizer
    mis-assigns essentially every key (VERDICT r2 weak #5); with the
    manifest-recorded metadata the same call scores correctly.
    """
    from parameter_server_tpu import evaluation
    from parameter_server_tpu.utils.keys import (
        localizer_from_meta,
        localizer_meta,
    )

    rows = 512
    loc32 = HashLocalizer(rows, seed=7, hash_bits=32)
    # meta roundtrip preserves the full construction
    rebuilt = localizer_from_meta(localizer_meta(loc32))
    keys = np.arange(1, 400, dtype=np.uint64) * 2654435761
    np.testing.assert_array_equal(rebuilt.assign(keys), loc32.assign(keys))

    van = LoopbackVan()
    try:
        cfgs = _cfgs(rows=rows, dim=1)
        _servers, worker = _cluster(van, cfgs, 2, localizers={"w": loc32})
        rng = np.random.RandomState(0)
        # teach the table a planted signal: weight +3 on half the keys
        pos_keys = keys[: keys.size // 2]
        neg_keys = keys[keys.size // 2 :]
        for _ in range(30):
            worker.wait(
                worker.push("w", pos_keys, -np.ones((pos_keys.size, 1), np.float32)),
                timeout=10,
            )
            worker.wait(
                worker.push("w", neg_keys, np.ones((neg_keys.size, 1), np.float32)),
                timeout=10,
            )
        worker.save_model(str(tmp_path), step=1)

        def batches():
            lab = np.concatenate([
                np.ones(pos_keys.size), np.zeros(neg_keys.size)
            ])
            ks = np.concatenate([pos_keys, neg_keys]).reshape(-1, 1)
            return [(ks, lab)]

        good = evaluation.evaluate_checkpoint(str(tmp_path), "w", batches())
        assert good["auc"] > 0.9  # manifest localizer -> rows line up
        # forcing the (wrong) 64-bit default must visibly degrade scoring
        bad = evaluation.evaluate_checkpoint(
            str(tmp_path), "w", batches(), hash_bits=64
        )
        assert bad["auc"] < good["auc"]
    finally:
        van.close()


def test_dim1_checkpoint_arrays_keep_their_shapes(tmp_path):
    """A dim-1 table holds flat planes on the device (PR 26) and its
    checkpoint is what it was: ``[rows, 1]`` arrays, no trash row.  So a
    checkpoint written before the planes were flat restores now, and one
    written now restores there."""
    van = LoopbackVan()
    try:
        cfgs = _cfgs(rows=600, dim=1)
        loc = {"w": HashLocalizer(600)}
        servers, worker = _cluster(van, cfgs, 2, localizers=loc)
        keys = np.arange(0, 64, dtype=np.uint64) * 7919
        grads = np.random.RandomState(0).randn(64, 1).astype(np.float32)
        worker.wait(worker.push("w", keys, grads), timeout=10)
        before = worker.pull_sync("w", keys, timeout=10)
        assert np.abs(before).max() > 0
        planes = [s.tables["w"].host_planes() for s in servers]
        worker.save_model(str(tmp_path), step=1)
    finally:
        van.close()
    for i, (value, state) in enumerate(planes):
        saved = checkpoint.load_arrays_shard(str(tmp_path), 1, "w", i, 2)
        assert saved["value"].shape == (300, 1)
        assert saved["state.sum_sq"].shape == (300, 1)
        np.testing.assert_array_equal(saved["value"], value[:300])
        np.testing.assert_array_equal(saved["state.sum_sq"], state["sum_sq"][:300])

    # the shard files as a writer with [rows + 1, 1] planes made them
    old = tmp_path / "old"
    for i, (value, state) in enumerate(planes):
        checkpoint.save_arrays_shard(
            str(old), 2, "w", i, 2, 300 * i, value[:300],
            {k: v[:300] for k, v in state.items()},
        )
    checkpoint.finalize(str(old), 2, 2, {"w": 600})
    for root, step in ((str(tmp_path), 1), (str(old), 2)):
        van2 = LoopbackVan()
        try:
            servers2, worker2 = _cluster(van2, cfgs, 3, localizers=loc)
            worker2.load_model(root, step=step)
            for s in servers2:
                t = s.tables["w"]
                assert t.value.shape == (t.rows + 1,)  # flat on the device
                assert t.state["sum_sq"].shape == (t.rows + 1,)
            after = worker2.pull_sync("w", keys, timeout=10)
            np.testing.assert_array_equal(after, before)
        finally:
            van2.close()
