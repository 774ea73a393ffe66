"""The push half of "rows stay on the worker's chip" (``kv/worker.py``), and
the counters that say how often either half engages.

``KVWorker._prepare_push`` reads where its gradient is off the gradient: a
``jax.Array`` is combined on the worker's device as it stands and only the
combined plane crosses to the host; NumPy values are uploaded as before.
Same program, same operand shapes: the combined plane and the rows the
servers end up with are the same bit for bit.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from parameter_server_tpu.config import OptimizerConfig, TableConfig
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.kv.worker import KVWorker, _segment_combine
from parameter_server_tpu.utils.trace import Tracer

ROWS, DIM, SERVERS = 1 << 14, 32, 2


def _cluster(dim=DIM):
    van = LoopbackVan()
    cfgs = {
        "t": TableConfig(
            name="t", rows=ROWS, dim=dim, init_scale=0.1,
            optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
        )
    }
    for i in range(SERVERS):
        KVServer(Postoffice(f"S{i}", van), cfgs, i, SERVERS)
    worker = KVWorker(
        Postoffice("W1", van), cfgs, SERVERS, min_bucket=16, tracer=Tracer()
    )
    return van, worker


def _batch(dim=DIM):
    rng = np.random.default_rng(11)
    pool = rng.choice(1 << 40, size=200, replace=False)
    keys = rng.choice(pool, size=(64, 9)).astype(np.uint64)
    grads = rng.standard_normal((keys.size, dim)).astype(np.float32)
    return keys, grads


#: how the step's gradient reaches ``push_sync`` as a device array
AS_DEVICE = {
    "on_the_workers_device": lambda g, w: jax.device_put(g, w.device),
    "on_another_device": lambda g, w: jax.device_put(g, jax.devices()[5]),
    "in_the_batch_s_shape": lambda g, w: jax.device_put(
        g.reshape(64, 9, -1), w.device
    ),
    "in_another_dtype": lambda g, w: jax.device_put(
        g.astype(np.float64), w.device  # x64 is off: arrives as float32
    ),
}


@pytest.mark.parametrize("how", list(AS_DEVICE))
def test_device_gradient_and_numpy_gradient_combine_and_apply_alike(how):
    keys, grads = _batch()
    planes, rows, spans = {}, {}, {}
    for kind in ("numpy", "device"):
        van, worker = _cluster()
        try:
            values = grads if kind == "numpy" else AS_DEVICE[how](grads, worker)
            assert isinstance(values, jax.Array) == (kind == "device")
            slots, combined = worker._prepare_push("t", keys, values)
            assert isinstance(combined, np.ndarray)
            planes[kind] = (slots, combined)
            worker.push_sync("t", keys, values, timeout=30)
            rows[kind] = np.asarray(worker.pull_sync("t", keys, timeout=30))
            spans[kind] = [s[4] for s in worker.tracer.spans("ps.worker.combine")]
            assert worker.counters()["push_combined_from_device"] == (
                2 if kind == "device" else 0
            )
        finally:
            van.close()
    assert np.array_equal(planes["numpy"][0], planes["device"][0])
    assert planes["numpy"][1].tobytes() == planes["device"][1].tobytes()
    assert rows["numpy"].tobytes() == rows["device"].tobytes()

    slots, combined = planes["device"]
    inverse_bytes = keys.size * 4
    for attrs in spans["device"]:
        assert attrs["where"] == "device"
        # the combined plane is all that comes down, the inverse all that
        # goes up: the [keys, dim] gradient stays where it was computed
        assert attrs["d2h_bytes"] == combined.nbytes < grads.nbytes
        assert attrs["h2d_bytes"] == inverse_bytes
    for attrs in spans["numpy"]:
        assert attrs["where"] == "host"
        assert attrs["d2h_bytes"] == combined.nbytes
        assert attrs["h2d_bytes"] == inverse_bytes + grads.nbytes


def test_a_device_gradient_compiles_no_second_combine():
    keys, grads = _batch()
    van, worker = _cluster()
    try:
        worker.push_sync("t", keys, grads, timeout=30)
        programs = _segment_combine._cache_size()
        worker.push_sync(
            "t", keys, jax.device_put(grads, worker.device), timeout=30
        )
        worker.wait(
            worker.push("t", keys, jnp.asarray(grads) * 2.0), timeout=30
        )
        assert _segment_combine._cache_size() == programs
        assert worker.counters()["push_combined_from_device"] == 2
    finally:
        van.close()


def test_counters_of_a_dim1_table_with_numpy_pushes_read_0_n_0():
    """``criteo_lr``'s shape: scalar rows assemble on the host and the
    trainer pushes NumPy, so neither half engages."""
    keys, grads = _batch(dim=1)
    van, worker = _cluster(dim=1)
    try:
        n = 3
        for _ in range(n):
            w = worker.pull_sync("t", keys, timeout=30)
            assert isinstance(w, np.ndarray) and w.shape == keys.shape
            worker.push_sync("t", keys, grads[:, 0], timeout=30)
        c = worker.counters()
        assert (
            c["pull_assembled_device"], c["pull_assembled_host"],
            c["push_combined_from_device"],
        ) == (0, n, 0)
    finally:
        van.close()


def test_counters_of_a_wide_table_with_device_pushes_read_n_0_n():
    """``dlrm_emb``'s shape: both halves engage at every step, and the
    pulled rows feed a jitted step without leaving the worker's device."""
    keys, _ = _batch()
    van, worker = _cluster()
    try:
        step = jax.jit(lambda rows: rows * 0.5)
        n = 3
        for _ in range(n):
            rows = worker.pull_sync("t", keys, timeout=30)
            # what the drivers do next moves nothing: the same buffer
            placed = jax.device_put(rows, worker.device)
            assert (
                placed.unsafe_buffer_pointer() == rows.unsafe_buffer_pointer()
            )
            g = step(rows)
            assert g.devices() == {worker.device}
            worker.push_sync("t", keys, g, timeout=30)
        c = worker.counters()
        assert (
            c["pull_assembled_device"], c["pull_assembled_host"],
            c["push_combined_from_device"],
        ) == (n, 0, n)
    finally:
        van.close()
