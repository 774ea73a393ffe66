"""Chip bring-up invariants that the CPU can check (ISSUE 21).

What only a chip run can show lives in ``chip_smoke.py``; these pin the
rules that keep a device from hiding: one device per PS server and worker,
a compile cache that stays where it is put, device faults that fail the run
instead of shrinking the fleet, and no interpreter unless a test asks.
"""

import os

import jax
import numpy as np
import pytest

from parameter_server_tpu.config import (
    ConsistencyConfig,
    ConsistencyMode,
    OptimizerConfig,
    TableConfig,
)
from parameter_server_tpu.core.manager import launch_local_cluster
from parameter_server_tpu.core.messages import server_id, worker_id
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.data.synthetic import SyntheticCTR
from parameter_server_tpu.kv import server as server_mod
from parameter_server_tpu.kv import worker as worker_mod
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.kv.table import KVTable
from parameter_server_tpu.kv.worker import KVWorker
from parameter_server_tpu.learner import elastic
from parameter_server_tpu.learner.elastic import ElasticTrainer
from parameter_server_tpu.models import linear
from parameter_server_tpu.utils import platform
from parameter_server_tpu.utils.keys import HashLocalizer

ROWS = 256
CFGS = {
    "w": TableConfig(
        name="w", rows=ROWS, dim=1,
        optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
    )
}


def _cluster(van, num_workers, num_servers, **cluster_kw):
    """The async_lr app's cluster: scheduler + servers + workers on one Van."""
    sched, managers, posts = launch_local_cluster(
        van, num_workers=num_workers, num_servers=num_servers, **cluster_kw
    )
    servers = [
        KVServer(posts[server_id(i)], CFGS, i, num_servers)
        for i in range(num_servers)
    ]
    workers = {
        worker_id(j): KVWorker(
            posts[worker_id(j)], CFGS, num_servers, min_bucket=16,
            localizers={"w": HashLocalizer(ROWS)},
        )
        for j in range(num_workers)
    }
    return sched, managers, servers, workers


def _batches(n, seed=0):
    data = SyntheticCTR(key_space=1024, nnz=4, batch_size=32, seed=seed)
    return [data.next_batch() for _ in range(n)]


def _train_in_order(workers, batches):
    """The ElasticTrainer's worker step, one worker at a time: a fixed push
    order makes the loss trajectory a pure function of the arithmetic."""
    losses = []
    for step, (keys, labels) in enumerate(batches):
        kv = workers[worker_id(step % len(workers))]
        w_pos = kv.pull_sync("w", keys, timeout=30)
        g, _gb, loss = linear.grad_rows(
            jax.device_put(w_pos, kv.device), jax.device_put(labels, kv.device)
        )
        kv.push_sync("w", keys, np.asarray(g) / labels.shape[0], timeout=30)
        losses.append(float(loss))
    return losses


def test_four_servers_four_devices_same_trajectory(tmp_path, monkeypatch):
    """Server i and worker j live on local device i % n / j % n — shards,
    optimizer state, resizes and restores included — and spreading the
    cluster over four devices changes no bit of the loss trajectory."""
    devices = jax.local_devices()
    assert len(devices) >= 4  # conftest: 8 virtual CPU devices
    batches = _batches(8)
    van = LoopbackVan()
    try:
        _sched, _mgrs, servers, workers = _cluster(van, 4, 4)
        for i, srv in enumerate(servers):
            tbl = srv.tables["w"]
            assert srv.device == devices[i]
            assert tbl.value.devices() == {devices[i]} and tbl.value.committed
            assert all(
                s.devices() == {devices[i]} for s in tbl.state.values()
            )
        assert [w.device for w in workers.values()] == devices[:4]
        spread = _train_in_order(workers, batches)

        # a snapshot restore and a resize land where the shard lives
        kv = workers[worker_id(0)]
        kv.save_snapshot(str(tmp_path), 1, timeout=30)
        srv, tbl = servers[2], servers[2].tables["w"]
        before = np.asarray(tbl.value)
        srv.restore_snapshot(str(tmp_path), 1)
        np.testing.assert_array_equal(np.asarray(tbl.value), before)
        grown = np.zeros((tbl.rows + 9, 1), np.float32)
        tbl.resize(grown, {k: grown for k in tbl.state})
        for arr in (tbl.value, *tbl.state.values()):
            assert arr.devices() == {devices[2]} and arr.committed
        assert tbl.rows == grown.shape[0] - 1
    finally:
        van.close()

    # the same cluster with every role on device 0
    monkeypatch.setattr(server_mod, "role_device", lambda i: devices[0])
    monkeypatch.setattr(worker_mod, "role_device", lambda i: devices[0])
    van = LoopbackVan()
    try:
        _sched, _mgrs, servers, workers = _cluster(van, 4, 4)
        assert {s.device for s in servers} == {devices[0]}
        packed = _train_in_order(workers, batches)
    finally:
        van.close()
    assert spread == packed
    assert spread[-1] < spread[0]


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
    tmp_path, monkeypatch
):
    saved = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_persistent_cache_min_compile_time_secs,
    )
    try:
        # set from outside: jax reads the variable itself; nothing is
        # overridden in code
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
        assert platform.enable_compile_cache() == str(tmp_path / "c")
        assert jax.config.jax_compilation_cache_dir == saved[0]
        # unset: one fixed path inside the checkout, whatever the cwd
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        got = []
        for cwd in (tmp_path, repo):
            monkeypatch.chdir(cwd)
            got.append(platform.enable_compile_cache())
            assert jax.config.jax_compilation_cache_dir == got[-1]
        assert got == [os.path.join(repo, ".jax_cache")] * 2
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        jax.config.update("jax_compilation_cache_dir", saved[0])
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", saved[1]
        )


def _elastic_run(monkeypatch, *, break_step=None, break_pull=None):
    """Run a 2-worker ElasticTrainer; returns (trainer, exception or None)."""
    van = LoopbackVan()
    try:
        sched, managers, _servers, workers = _cluster(
            van, 2, 2, heartbeat_timeout=0.5
        )
        if break_step is not None:
            monkeypatch.setattr(elastic.linear, "grad_rows", break_step)
        if break_pull is not None:
            monkeypatch.setattr(workers[worker_id(1)], "pull_sync", break_pull)
        batches = _batches(8)
        trainer = ElasticTrainer(
            workers, sched, [batches[i : i + 2] for i in range(0, 8, 2)],
            ConsistencyConfig(mode=ConsistencyMode.ASP),
            managers=managers, heartbeat_interval=0.05, timeout=20.0,
        )
        try:
            trainer.run(poll=0.005)
        except Exception as e:  # noqa: BLE001 — the test inspects it
            return trainer, e
        return trainer, None
    finally:
        van.close()


def test_device_fault_fails_the_run_but_a_van_timeout_retires_the_worker(
    monkeypatch,
):
    def device_fault(*_a, **_k):
        raise jax.errors.JaxRuntimeError("INTERNAL: injected device fault")

    trainer, err = _elastic_run(monkeypatch, break_step=device_fault)
    assert isinstance(err, jax.errors.JaxRuntimeError), err
    assert "injected device fault" in str(err)
    assert trainer._killed == set()  # not mistaken for a partitioned worker
    monkeypatch.undo()

    def partitioned(*_a, **_k):
        raise TimeoutError("pull timed out")

    trainer, err = _elastic_run(monkeypatch, break_pull=partitioned)
    assert err is None
    assert trainer._killed == {worker_id(1)}
    assert trainer.pool.all_done()  # the survivor drew the requeued work


def test_pallas_table_off_tpu_must_ask_for_the_interpreter():
    cfg = TableConfig(
        name="e", rows=64, dim=128, scatter_impl="pallas",
        optimizer=OptimizerConfig(kind="sgd"),
    )
    with pytest.raises(ValueError, match="interpret=True"):
        KVTable(cfg)
    assert KVTable(cfg, interpret=True)._interpret is True
    assert KVTable(CFGS["w"])._interpret is False
