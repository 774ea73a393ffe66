"""Device-side hashing + scan-block training: parity with the host path.

The host-link-bound optimization (``dense_scan_train_step``): raw uint32
keys ship to the device, murmur fmix32 hashing runs inside the jit program,
and K steps execute per dispatch.  These tests pin the invariant that makes
it safe: host ``mix32`` and device ``mix32_jax`` agree bit-for-bit, so a
block-trained table is exactly the table the sequential host path produces.
"""

import numpy as np

import jax.numpy as jnp

from parameter_server_tpu.config import OptimizerConfig, TableConfig
from parameter_server_tpu.learner.sgd import LocalLRTrainer
from parameter_server_tpu.models import linear
from parameter_server_tpu.utils.keys import HashLocalizer, mix32


def test_mix32_host_device_parity():
    rng = np.random.default_rng(0)
    keys = rng.integers(0, 1 << 32, size=5000, dtype=np.uint64)
    host = mix32(keys.astype(np.uint32), np.uint32(7))
    dev = np.asarray(linear.mix32_jax(jnp.asarray(keys.astype(np.uint32)), 7))
    np.testing.assert_array_equal(host, dev.astype(np.uint32))


def test_hash_localizer_32bit_mode():
    loc = HashLocalizer(1000, seed=3, hash_bits=32)
    keys = np.arange(100, dtype=np.uint64) * 2654435761
    slots = loc.assign(keys)
    assert slots.min() >= 0 and slots.max() < 1000
    want = (mix32(keys.astype(np.uint32), np.uint32(3)) % np.uint32(1000)).astype(
        np.int32
    )
    np.testing.assert_array_equal(slots, want)


def test_step_block_matches_sequential_steps():
    cfg = TableConfig(
        name="w",
        rows=2048,
        dim=1,
        optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
    )
    rng = np.random.default_rng(1)
    K, B, nnz = 4, 64, 8
    keys = rng.integers(0, 1 << 20, size=(K, B, nnz), dtype=np.uint64)
    labels = rng.integers(0, 2, size=(K, B)).astype(np.float32)

    block_tr = LocalLRTrainer(cfg, mode="dense", device_hash=True)
    losses_block = np.asarray(block_tr.step_block(keys, labels))

    seq_tr = LocalLRTrainer(cfg, mode="dense", device_hash=True)
    losses_seq = [seq_tr.step(keys[k], labels[k]) for k in range(K)]

    np.testing.assert_allclose(losses_block, losses_seq, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(block_tr.table.value),
        np.asarray(seq_tr.table.value),
        rtol=1e-5,
        atol=1e-7,
    )
    assert block_tr.step_count == K


def test_step_block_learns():
    from parameter_server_tpu.data.synthetic import SyntheticCTR

    cfg = TableConfig(
        name="w",
        rows=1 << 14,
        dim=1,
        optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
    )
    tr = LocalLRTrainer(cfg, mode="dense", device_hash=True)
    data = SyntheticCTR(
        key_space=1 << 18, nnz=8, batch_size=256, seed=5, informative=0.2
    )
    K = 8
    losses = []
    for _ in range(12):
        batches = [data.next_batch() for _ in range(K)]
        keys = np.stack([b[0] for b in batches])
        labels = np.stack([b[1] for b in batches])
        losses.extend(np.asarray(tr.step_block(keys, labels)).tolist())
    assert np.mean(losses[-8:]) < np.mean(losses[:8]) - 0.01


def test_device_hash_requires_dense():
    cfg = TableConfig(name="w", rows=64, dim=1)
    import pytest

    with pytest.raises(ValueError, match="device_hash requires"):
        LocalLRTrainer(cfg, mode="rows", device_hash=True)


def test_step_block_pad_keys_route_to_trash():
    """PAD positions must hit the trash row on device, exactly as the host
    path does — padded batches train identical tables on both paths."""
    from parameter_server_tpu.utils.keys import PAD_KEY

    cfg = TableConfig(
        name="w",
        rows=512,
        dim=1,
        optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
    )
    rng = np.random.default_rng(2)
    K, B, nnz = 2, 32, 6
    keys = rng.integers(0, 1 << 20, size=(K, B, nnz), dtype=np.uint64)
    keys[:, :, -2:] = PAD_KEY  # variable-nnz padding
    labels = rng.integers(0, 2, size=(K, B)).astype(np.float32)

    block_tr = LocalLRTrainer(cfg, mode="dense", device_hash=True)
    block_tr.step_block(keys, labels)

    seq_tr = LocalLRTrainer(cfg, mode="dense", device_hash=True)
    for k in range(K):
        seq_tr.step(keys[k], labels[k])

    np.testing.assert_allclose(
        np.asarray(block_tr.table.value),
        np.asarray(seq_tr.table.value),
        rtol=1e-5,
        atol=1e-7,
    )
    # the trash row itself stays zero
    assert float(np.abs(np.asarray(block_tr.table.value)[-1]).max()) == 0.0
