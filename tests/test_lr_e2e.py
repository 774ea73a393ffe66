"""End-to-end sparse LR convergence tests (SURVEY.md §4 golden-convergence)."""

import time

import numpy as np
import pytest

from parameter_server_tpu.config import (
    ConsistencyConfig,
    ConsistencyMode,
    OptimizerConfig,
    TableConfig,
)
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.data.synthetic import SyntheticCTR
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.kv.worker import KVWorker
from parameter_server_tpu.learner.sgd import AsyncLRLearner, LocalLRTrainer
from parameter_server_tpu.utils.metrics import auc


def _table_cfg(rows=1 << 16, kind="adagrad", lr=0.05):
    return TableConfig(
        name="w",
        rows=rows,
        dim=1,
        optimizer=OptimizerConfig(kind=kind, learning_rate=lr),
    )


def test_local_trainer_converges():
    data = SyntheticCTR(
        key_space=1 << 14, nnz=8, batch_size=512, seed=1, informative=0.3
    )
    trainer = LocalLRTrainer(_table_cfg(rows=1 << 14, lr=0.2), min_bucket=512)
    losses = []
    for keys, labels in data.batches(60):
        losses.append(trainer.step(keys, labels))
    head, tail = np.mean(losses[:10]), np.mean(losses[-10:])
    assert tail < head - 0.05, (head, tail)
    a = trainer.eval_auc(data.next_batch, 5)
    assert a > 0.70, a


def test_local_trainer_ftrl_converges():
    cfg = TableConfig(
        name="w",
        rows=1 << 14,
        dim=1,
        optimizer=OptimizerConfig(kind="ftrl", l1=0.001, ftrl_alpha=0.5),
    )
    data = SyntheticCTR(
        key_space=1 << 14, nnz=8, batch_size=512, seed=2, informative=0.3
    )
    trainer = LocalLRTrainer(cfg, min_bucket=512)
    losses = [trainer.step(*data.next_batch()) for _ in range(60)]
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.05


def test_auc_metric():
    labels = np.array([0, 0, 1, 1])
    assert auc(labels, np.array([0.1, 0.2, 0.8, 0.9])) == 1.0
    assert auc(labels, np.array([0.9, 0.8, 0.2, 0.1])) == 0.0
    assert abs(auc(labels, np.array([0.5, 0.5, 0.5, 0.5])) - 0.5) < 1e-9


@pytest.mark.parametrize(
    "mode,delay",
    [
        (ConsistencyMode.BSP, 0),
        (ConsistencyMode.SSP, 2),
        (ConsistencyMode.ASP, 0),
    ],
)
def test_async_learner_all_modes_converge(mode, delay):
    van = LoopbackVan()
    try:
        cfgs = {"w": _table_cfg(rows=1 << 14, lr=0.1)}
        _servers = [KVServer(Postoffice(f"S{i}", van), cfgs, i, 2) for i in range(2)]
        workers = [
            KVWorker(Postoffice(f"W{i}", van), cfgs, 2, min_bucket=256)
            for i in range(2)
        ]
        data = [
            SyntheticCTR(
                key_space=1 << 14, nnz=8, batch_size=256, seed=10 + i,
                informative=0.3,
            )
            for i in range(2)
        ]
        learner = AsyncLRLearner(
            workers, ConsistencyConfig(mode=mode, max_delay=delay)
        )
        losses = learner.run([d.next_batch for d in data], steps_per_worker=20)
        assert len(losses) == 40
        assert np.mean(losses[-8:]) < np.mean(losses[:8]) - 0.03
    finally:
        van.close()


@pytest.mark.parametrize(
    "mode,delay", [(ConsistencyMode.BSP, 0), (ConsistencyMode.SSP, 2)]
)
def test_four_jittered_workers_reach_heldout_auc(mode, delay):
    """Four workers whose batches arrive late now and then (a seeded 2 % of
    steps sleep 5 ms) train two AdaGrad shards under the gate; a fifth
    worker that never trains pulls held-out keys and must rank them."""
    n_workers, n_servers, steps, batch = 4, 2, 60, 256
    cfgs = {"w": _table_cfg(rows=1 << 17, lr=0.1)}

    def stream(seed, batch_size=batch):
        return SyntheticCTR(
            key_space=1 << 18, nnz=16, batch_size=batch_size, seed=seed,
            informative=0.3,
        )

    van = LoopbackVan()
    try:
        for s in range(n_servers):
            KVServer(Postoffice(f"S{s}", van), cfgs, s, n_servers)
        workers = [
            KVWorker(Postoffice(f"W{i}", van), cfgs, n_servers)
            for i in range(n_workers)
        ]
        eval_kv = KVWorker(Postoffice("WE", van), cfgs, n_servers)
        streams = [stream(100 + i) for i in range(n_workers)]
        jitter = [np.random.default_rng(1000 + i) for i in range(n_workers)]

        def batch_fn(i):
            def fn():
                if jitter[i].random() < 0.02:
                    time.sleep(0.005)
                return streams[i].next_batch()

            return fn

        held_out = stream(9999, batch_size=2048)
        eval_batches = [held_out.next_batch() for _ in range(4)]

        def heldout_auc():
            scores, ys = [], []
            for keys, labels in eval_batches:
                w_pos = np.asarray(eval_kv.pull_sync("w", keys, timeout=60))
                scores.append(w_pos.reshape(keys.shape).sum(axis=1))
                ys.append(labels)
            return auc(np.concatenate(ys), np.concatenate(scores))

        before = heldout_auc()
        learner = AsyncLRLearner(
            workers, ConsistencyConfig(mode=mode, max_delay=delay)
        )
        losses = learner.run(
            [batch_fn(i) for i in range(n_workers)], steps, timeout=120.0
        )
        assert len(losses) == n_workers * steps
        assert np.all(np.isfinite(losses))
        after = heldout_auc()
        assert after > 0.70 and after > before, (before, after)
    finally:
        van.close()


def test_bsp_matches_single_process_reference():
    """Golden test: BSP with 1 worker == LocalLRTrainer-style sequential SGD.

    Uses SGD (stateless) so the trajectories must agree step by step.
    """
    cfg_table = _table_cfg(rows=1 << 12, kind="sgd", lr=0.5)
    data_a = SyntheticCTR(
        key_space=1 << 12, nnz=4, batch_size=128, seed=42, informative=0.3
    )
    data_b = SyntheticCTR(
        key_space=1 << 12, nnz=4, batch_size=128, seed=42, informative=0.3
    )

    van = LoopbackVan()
    try:
        cfgs = {"w": cfg_table}
        _server = KVServer(Postoffice("S0", van), cfgs, 0, 1)
        worker = KVWorker(Postoffice("W0", van), cfgs, 1, min_bucket=256)
        learner = AsyncLRLearner(
            [worker], ConsistencyConfig(mode=ConsistencyMode.BSP)
        )
        van_losses = learner.run([data_a.next_batch], steps_per_worker=10)
    finally:
        van.close()

    local = LocalLRTrainer(cfg_table, min_bucket=256)
    local_losses = [local.step(*data_b.next_batch()) for _ in range(10)]
    # the van path has no bias term; losses still must track closely since
    # bias-free gradients dominate — compare weight-driven loss decrease
    np.testing.assert_allclose(van_losses, local_losses, atol=0.05)


@pytest.mark.parametrize("mode", ["rows", "dense", "dense_block"])
def test_local_trainer_steps_flat_planes_like_column_planes(mode):
    """``LocalLRTrainer`` hands ``KVTable``'s planes to ``models/linear.py``'s
    own jitted steps.  A dim-1 table's planes are flat (PR 26): every step
    keeps them flat, and gives the loss and the rows the same step gives on
    ``[rows + 1, 1]`` planes (``parallel/lr_spmd.py`` still holds such)."""
    import jax.numpy as jnp

    from parameter_server_tpu.models import linear
    from parameter_server_tpu.utils.keys import localize_to_slots

    rows = 2048
    cfg = _table_cfg(rows=rows)
    tr = LocalLRTrainer(
        cfg, mode="rows" if mode == "rows" else "dense",
        device_hash=mode == "dense_block",
    )
    t, opt = tr.table, tr.optimizer
    assert t.value.shape == (rows + 1,)
    col_v = jnp.zeros((rows + 1, 1), jnp.float32)
    col_s = {"sum_sq": jnp.zeros((rows + 1, 1), jnp.float32)}
    bias = jnp.zeros((1, 1), jnp.float32)
    bias_s = {"sum_sq": jnp.zeros((1, 1), jnp.float32)}
    rng = np.random.default_rng(2)
    keys = rng.integers(0, 1 << 20, size=(2, 64, 8), dtype=np.uint64)
    labels = rng.integers(0, 2, size=(2, 64)).astype(np.float32)
    if mode == "dense_block":
        losses = np.asarray(tr.step_block(keys, labels))
        col_v, col_s, bias, bias_s, want = linear.dense_scan_train_step(
            col_v, col_s, bias, bias_s, jnp.asarray(keys.astype(np.uint32)),
            jnp.asarray(labels), opt, rows, tr.localizer.seed,
        )
    else:
        losses, want = [], []
        for k, y in zip(keys, labels):
            losses.append(tr.step(k, y))
            if mode == "dense":
                col_v, col_s, bias, bias_s, loss = linear.dense_fused_train_step(
                    col_v, col_s, bias, bias_s,
                    jnp.asarray(tr.localizer.assign(k)), jnp.asarray(y), opt, rows,
                )
            else:
                slots, inverse, _n = localize_to_slots(
                    k, tr.localizer, min_bucket=tr.min_bucket
                )
                col_v, col_s, bias, bias_s, loss = linear.fused_train_step(
                    col_v, col_s, bias, bias_s, jnp.asarray(slots),
                    jnp.asarray(inverse), jnp.asarray(y), opt, slots.shape[0],
                )
            want.append(float(loss))
    np.testing.assert_allclose(losses, np.asarray(want), rtol=1e-6)
    assert t.value.shape == (rows + 1,) and t.state["sum_sq"].shape == (rows + 1,)
    assert col_v.shape == (rows + 1, 1)
    value, state = t.host_planes()
    np.testing.assert_allclose(value, np.asarray(col_v), rtol=1e-6, atol=1e-8)
    np.testing.assert_allclose(
        state["sum_sq"], np.asarray(col_s["sum_sq"]), rtol=1e-6, atol=1e-8
    )
    assert np.abs(value).max() > 0 and value[-1, 0] == 0.0
