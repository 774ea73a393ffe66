"""ResNet + dense PS path tests (BASELINE configs #2 and the KVLayer analogue)."""

import numpy as np
import optax
import pytest

import jax

from parameter_server_tpu.config import (
    ConsistencyConfig,
    ConsistencyMode,
    OptimizerConfig,
)
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.data.synthetic import SyntheticImages
from parameter_server_tpu.kv.dense import (
    DenseKVServer,
    DenseKVWorker,
    PytreeCodec,
    segment_offsets,
)
from parameter_server_tpu.learner.dense import AsyncDenseLearner, SpmdDenseTrainer
from parameter_server_tpu.models.resnet import ResNet, resnet18, resnet50
from parameter_server_tpu.parallel import mesh as mesh_lib


def _tiny_resnet(num_classes=10):
    return ResNet(
        stage_sizes=[1, 1], num_classes=num_classes, width=8, bottleneck=False,
        small_inputs=True,
    )


def _batch(rng, n=16, num_classes=10):
    images = rng.normal(size=(n, 16, 16, 3)).astype(np.float32)
    labels = rng.integers(0, num_classes, size=n).astype(np.int32)
    return images, labels


def test_resnet50_structure():
    """ResNet-50 must have the canonical parameter count (25.6M)."""
    model = resnet50(num_classes=1000)
    params = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0),
            np.zeros((1, 224, 224, 3), np.float32),
            train=False,
        )
    )["params"]
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert 25_500_000 < n < 25_700_000, n


def test_segment_offsets():
    off = segment_offsets(10, 3)
    np.testing.assert_array_equal(off, [0, 4, 7, 10])


def test_spmd_dense_trainer_learns():
    rng = np.random.default_rng(0)
    mesh = mesh_lib.make_mesh()  # 8-way DP
    model = _tiny_resnet()
    batch = _batch(rng, n=16)
    trainer = SpmdDenseTrainer(
        model, optax.sgd(0.3, momentum=0.9), mesh, batch
    )
    # memorize one small batch: loss must clearly fall
    losses = [trainer.step(*batch) for _ in range(30)]
    assert losses[-1] < losses[0] - 0.5, losses[::10]


def test_async_dense_learner_bsp():
    rng = np.random.default_rng(1)
    van = LoopbackVan()
    try:
        model = _tiny_resnet()
        batch = _batch(rng, n=32)
        import jax.numpy as jnp

        variables = model.init(
            jax.random.PRNGKey(0), jnp.asarray(batch[0][:1]), train=False
        )
        codec = PytreeCodec(variables["params"])
        total = codec.total
        specs_srv = {"model": (total, OptimizerConfig(kind="sgd", learning_rate=0.3))}
        workers = [
            DenseKVWorker(Postoffice(f"W{i}", van), {"model": total}, 2)
            for i in range(2)
        ]
        learner = AsyncDenseLearner(
            model,
            workers,
            ConsistencyConfig(mode=ConsistencyMode.BSP),
            batch,
        )
        servers = [
            DenseKVServer(
                Postoffice(f"S{i}", van),
                specs_srv,
                i,
                2,
                init_vectors={"model": learner.initial_vector()},
            )
            for i in range(2)
        ]
        fixed = [_batch(np.random.default_rng(10 + i), n=16) for i in range(2)]
        data = [lambda b=b: b for b in fixed]  # memorize a fixed batch each
        losses = learner.run(data, steps_per_worker=8)
        assert len(losses) == 16
        assert np.mean(losses[-4:]) < np.mean(losses[:4]) - 0.1
    finally:
        van.close()


def test_async_dense_learner_classifies_heldout_images():
    """Four workers train a norm-free CNN over the dense async plane from
    their own image streams; a fifth worker that never trains pulls the
    servers' current parameters, which must classify held-out images."""
    import flax.linen as nn
    import jax.numpy as jnp

    class TinyCNN(nn.Module):
        @nn.compact
        def __call__(self, x, train=False):
            x = nn.relu(nn.Conv(16, (3, 3), strides=2)(x))
            x = nn.relu(nn.Conv(32, (3, 3), strides=2)(x))
            return nn.Dense(10)(x.mean(axis=(1, 2)))

    n_workers, n_servers, steps = 4, 2, 40
    model = TinyCNN()
    held_out = SyntheticImages(seed=9999, noise=0.8)
    images, labels = zip(*[held_out.next_batch() for _ in range(4)])
    images, labels = jnp.asarray(np.concatenate(images)), np.concatenate(labels)
    streams = [
        SyntheticImages(seed=100 + i, noise=0.8, batch_size=64)
        for i in range(n_workers)
    ]
    van = LoopbackVan()
    try:
        example = streams[0].next_batch()
        variables = model.init(
            jax.random.PRNGKey(0), jnp.asarray(example[0][:1]), train=False
        )
        total = PytreeCodec(variables["params"]).total
        workers = [
            DenseKVWorker(Postoffice(f"W{i}", van), {"model": total}, n_servers)
            for i in range(n_workers)
        ]
        learner = AsyncDenseLearner(
            model, workers, ConsistencyConfig(mode=ConsistencyMode.BSP),
            example, seed=0,
        )
        for s in range(n_servers):
            DenseKVServer(
                Postoffice(f"S{s}", van),
                {"model": (total, OptimizerConfig(kind="sgd", learning_rate=0.3))},
                s, n_servers,
                init_vectors={"model": learner.initial_vector()},
            )
        eval_kv = DenseKVWorker(Postoffice("WE", van), {"model": total}, n_servers)

        def heldout_accuracy():
            params = learner.codec.unflatten(eval_kv.pull_sync("model", 60))
            out = model.apply({"params": params}, images, train=False)
            return float(np.mean(np.argmax(np.asarray(out), -1) == labels))

        before = heldout_accuracy()
        losses = learner.run(
            [s.next_batch for s in streams], steps, timeout=120.0
        )
        assert len(losses) == n_workers * steps
        after = heldout_accuracy()
        assert after > 0.5 and after > before, (before, after)
    finally:
        van.close()
