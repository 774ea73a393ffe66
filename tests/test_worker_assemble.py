"""The pull half of "rows stay on the worker's chip" (``kv/worker.py``).

``KVWorker.pull_result`` lays a pull's host replies into one bucketed plane
of unique rows, uploads it once and gathers it by ``inverse`` on the
worker's device; scalar rows (dim 1) are assembled in NumPy as before.
Every case pulls two batches of one bucket and holds, against
``uniq[inverse]`` in NumPy over the replies the worker was handed:

- the rows are equal bit for bit;
- a dim>1 result is a ``jax.Array`` on ``kv.device``, a dim-1 result NumPy;
- the jitted gather compiles nothing for the second batch (the uploaded
  plane is the bucket's shape, never a leg's row count);
- the array pull A returned is unchanged after pull B (the staging plane is
  reused across pulls).
"""

import sys
import threading

import numpy as np
import pytest

import jax

from parameter_server_tpu.config import OptimizerConfig, TableConfig
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.kv.worker import KVWorker, _gather_rows
from parameter_server_tpu.utils.trace import Tracer

ROWS = 1 << 14
BUCKET = 512


def _split_one_leg(pairs):
    """The fence-retry shape: one server's positions answered in two
    interleaved subsets (neither one ascending run), the rest as they came."""
    out, split = [], False
    for pos, rows, *meta in pairs:
        if not split and len(pos) >= 4:
            rows = np.asarray(rows).reshape(len(pos), -1)
            for part in (slice(0, None, 2), slice(1, None, 2)):
                out.append((pos[part], rows[part], *meta))
            split = True
        else:
            out.append((pos, rows, *meta))
    assert split
    return out


def _drop_one_leg(pairs):
    """A reply set that leaves positions uncovered: they read zero."""
    keep = [p for p in pairs if len(p[0])]
    return keep[1:]


#: case -> (row width, servers, what is done to the replies before assembly)
CASES = {
    "four_contiguous_legs": (128, 4, None),
    "noncontiguous_fence_retry": (128, 4, _split_one_leg),
    "sole_full_pair": (128, 1, None),
    "dim1_table": (1, 4, None),
    "two_counts_one_bucket": (128, 4, None),
    "uncovered_positions_read_zero": (128, 4, _drop_one_leg),
}


@pytest.fixture
def cluster(request):
    dim, n_servers, _ = CASES[request.param]
    van = LoopbackVan()
    cfgs = {
        "t": TableConfig(
            name="t", rows=ROWS, dim=dim, init_scale=0.5,
            optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
        )
    }
    for i in range(n_servers):
        KVServer(Postoffice(f"S{i}", van), cfgs, i, n_servers)
    # W1: not the process's default device, so placement is the worker's
    worker = KVWorker(
        Postoffice("W1", van), cfgs, n_servers, min_bucket=16, tracer=Tracer()
    )
    yield request.param, worker
    van.close()


def _batches(rng):
    """Two key batches with duplicates whose unique slots fall in one bucket
    at different true counts, so every leg's row count differs too."""
    out = []
    for n_unique in (300, 470):
        pool = rng.choice(1 << 40, size=n_unique, replace=False)
        keys = rng.choice(pool, size=(96, 13)).astype(np.uint64)
        keys.flat[:n_unique] = pool  # every pooled key appears
        out.append(keys)
    return out


@pytest.mark.parametrize("cluster", list(CASES), indirect=True)
def test_pull_equals_numpy_uniq_inverse(cluster):
    case, worker = cluster
    dim, _servers, reshape_replies = CASES[case]
    on_device = dim > 1
    handed = []
    pull_pairs = worker._pull_pairs

    def recording(ts, timeout):
        plan, pairs = pull_pairs(ts, timeout)
        if reshape_replies is not None:
            pairs = reshape_replies(pairs)
        handed.append((plan, pairs))
        return plan, pairs

    worker._pull_pairs = recording
    keys_a, keys_b = _batches(np.random.default_rng(7))

    got_a = worker.pull_sync("t", keys_a, timeout=30)
    held_a = np.array(got_a, copy=True)
    programs = _gather_rows._cache_size()
    got_b = worker.pull_sync("t", keys_b, timeout=30)
    if on_device:
        assert _gather_rows._cache_size() == programs

    (plan_a, _), (plan_b, _) = handed
    assert plan_a["n_slots"] == plan_b["n_slots"] == BUCKET
    real = [int((p["slots"] < ROWS).sum()) for p in (plan_a, plan_b)]
    assert real[0] != real[1]  # one bucket, two true counts
    for keys, got, (plan, pairs) in zip(
        (keys_a, keys_b), (got_a, got_b), handed
    ):
        uniq = np.zeros((plan["n_slots"], dim), np.float32)
        for pos, rows, *_meta in pairs:
            uniq[pos] = np.asarray(rows).reshape(len(pos), dim)
        want = uniq[plan["inverse"]].reshape(
            keys.shape + ((dim,) if on_device else ())
        )
        assert np.abs(want).max() > 0  # rows were drawn, not zeros
        assert got.shape == want.shape and got.dtype == want.dtype
        assert np.asarray(got).tobytes() == want.tobytes()
        if on_device:
            assert isinstance(got, jax.Array)
            assert got.devices() == {worker.device}
        else:
            assert isinstance(got, np.ndarray)
    # the staging plane was rewritten for B; what A returned did not move
    assert np.asarray(got_a).tobytes() == held_a.tobytes()

    c = worker.counters()
    assert c["pull_assembled_device"] == (2 if on_device else 0)
    assert c["pull_assembled_host"] == (0 if on_device else 2)
    spans = worker.tracer.spans("ps.worker.assemble")
    assert [s[4]["where"] for s in spans] == (
        ["device", "device"] if on_device else ["host", "host"]
    )
    plane = BUCKET * dim * 4
    for s in spans:
        attrs = s[4]
        assert attrs["d2h_bytes"] == 0
        # one upload of the bucketed plane and the inverse, however many
        # rows the legs truly held
        assert attrs["h2d_bytes"] == (
            plane + keys_a.size * 4 if on_device else 0
        )


def test_pull_result_device_and_pull_result_agree_on_host_replies():
    """``pull_result_device`` shares the staged upload for host replies; a
    dim-1 table goes through it on the device (a flat plane)."""
    van = LoopbackVan()
    try:
        cfgs = {
            "w": TableConfig(name="w", rows=ROWS, dim=1, init_scale=0.5),
            "e": TableConfig(name="e", rows=ROWS, dim=16, init_scale=0.5),
        }
        for i in range(2):
            KVServer(Postoffice(f"S{i}", van), cfgs, i, 2)
        worker = KVWorker(Postoffice("W0", van), cfgs, 2, min_bucket=16)
        keys = np.random.default_rng(3).integers(
            0, 1 << 30, size=(40, 5)
        ).astype(np.uint64)
        for table in cfgs:
            host = worker.pull_sync(table, keys, timeout=30)
            dev = worker.pull_result_device(
                worker.pull(table, keys), timeout=30
            )
            assert isinstance(dev, jax.Array) and dev.shape == host.shape
            assert np.asarray(dev).tobytes() == np.asarray(host).tobytes()
        assert isinstance(worker.pull_sync("w", keys, timeout=30), np.ndarray)
    finally:
        van.close()


def test_concurrent_pulls_of_one_bucket_do_not_share_a_staged_plane():
    """Pulls on several threads of one worker take turns at the bucket's
    staging plane: every thread reads its own keys' rows, every time."""
    van = LoopbackVan()
    old = sys.getswitchinterval()
    try:
        cfgs = {"t": TableConfig(name="t", rows=ROWS, dim=64, init_scale=0.5)}
        for i in range(4):
            KVServer(Postoffice(f"S{i}", van), cfgs, i, 4)
        worker = KVWorker(Postoffice("W0", van), cfgs, 4, min_bucket=16)
        rng = np.random.default_rng(5)
        n_threads, pulls = 12, 6  # more threads than this box has cores
        keys = [
            rng.integers(0, 1 << 40, size=(50, 8)).astype(np.uint64)
            for _ in range(n_threads)
        ]
        want = [np.asarray(worker.pull_sync("t", k, timeout=30)) for k in keys]
        assert len({w.tobytes() for w in want}) == n_threads
        wrong, errors = [], []

        def pull(i):
            try:
                for _ in range(pulls):
                    got = np.asarray(worker.pull_sync("t", keys[i], timeout=60))
                    if got.tobytes() != want[i].tobytes():
                        wrong.append(i)
            except Exception as e:  # reported below, on the test's thread
                errors.append(e)

        sys.setswitchinterval(1e-5)
        threads = [threading.Thread(target=pull, args=(i,)) for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors
        assert not wrong
        assert len(worker._stage) == 1  # one bucket, one plane
    finally:
        sys.setswitchinterval(old)
        van.close()
