"""The key-to-row map is the configuration's (``table.localizer``), and
``harness/keys.py`` is the one place that knows what ``"hash"`` and
``"identity"`` mean.  Under ``"hash"`` the reference check draws what it
drew before the map was a parameter (golden digests taken on the parent of
PR 27), so both cells run the checks they ran; under ``"identity"`` its keys
are rows of the table, reach every shard, and a key without a row is an
error in words."""

import hashlib

import numpy as np
import pytest

from benchmarks.harness import keys as keys_lib
from benchmarks.harness.correctness import check_draws, push_pull_check


def draws_digest(seed, rows, dim, pushes, localizer):
    draws = check_draws(seed, n_keys=3000, rows=rows, dim=dim, pushes=pushes,
                        localizer=localizer)
    distinct = next(draws)
    h = hashlib.sha256(distinct.tobytes())
    h.update(keys_lib.rows_of(distinct, rows, localizer).tobytes())
    n = 0
    for idx, grads in draws:
        assert idx.dtype == np.int64 and grads.dtype == np.float32
        assert grads.shape == (idx.size, dim)
        h.update(idx.tobytes())
        h.update(grads.tobytes())
        n += 1
    assert n == pushes
    return h.hexdigest()


# sha256 over keys, their rows, and every push's duplicate indices and
# gradient bytes, computed by the parent's push_pull_check lines (PR 26's
# tree) for these arguments: the dry run's criteo_lr and x4's dlrm_emb
GOLDEN = [
    (2147483999, 8192, 1, 4,
     "15f75e1bc29ffea2c8b3bb78c2030f68ed5bb0efb3a84539dfae3801c7fb074b"),
    (3000002701, 4 * 11735464, 128, 8,
     "c2afb9372ad80dd402724076d06d18ab8f5a67c4f077ccc34d6bb06bd2ede522"),
]


@pytest.mark.parametrize("seed,rows,dim,pushes,want", GOLDEN)
def test_hashed_draws_are_the_parent_s_value_for_value(
    seed, rows, dim, pushes, want
):
    assert draws_digest(seed, rows, dim, pushes, "hash") == want


@pytest.mark.parametrize("rows", [8192, 20480])
@pytest.mark.parametrize("servers", [1, 2, 4])
def test_identity_draws_are_rows_and_reach_every_shard(rows, servers):
    draws = check_draws(7, n_keys=3000, rows=rows, dim=4, pushes=1,
                        localizer="identity")
    distinct = next(draws)
    assert distinct.dtype == np.uint64 and distinct.size == 3000
    assert np.unique(distinct).size == 3000 and int(distinct.max()) < rows
    slots = keys_lib.rows_of(distinct, rows, "identity")
    assert np.array_equal(slots, distinct.astype(np.int64))
    assert np.unique(slots * servers // rows).size == servers
    idx, _grads = next(draws)
    # duplicates, and a count that is no power of two (the bucket pads)
    assert np.unique(idx).size < idx.size == 4150


def test_identity_draws_of_a_table_smaller_than_the_check_take_every_row():
    distinct = keys_lib.draw_check_keys(
        np.random.default_rng(0), 3000, 1000, "identity"
    )
    assert np.array_equal(np.sort(distinct), np.arange(1000, dtype=np.uint64))


@pytest.mark.parametrize("key", [8192, 8193, 1 << 40])
def test_a_key_without_a_row_fails_in_words_under_identity(key):
    keys = np.array([3, key, 5], dtype=np.uint64)
    with pytest.raises(ValueError, match=rf"key {key} has no row .* 8192 rows"):
        keys_lib.rows_of(keys, 8192, "identity")
    # the hashing trick takes any key
    assert keys_lib.rows_of(keys, 8192, "hash").max() < 8192


def test_the_configuration_states_the_localizer_and_absent_means_hash():
    assert keys_lib.localizer_of({"name": "w"}, "f.json") == "hash"
    assert keys_lib.localizer_of({"localizer": "identity"}, "f.json") == "identity"
    with pytest.raises(ValueError, match=r"configs/x\.json.*'modulo'"):
        keys_lib.localizer_of({"localizer": "modulo"}, "benchmarks/configs/x.json")
    # ``localizer_of`` is the one validator: past it a wrong name is a
    # caller's fault and no file's
    with pytest.raises(KeyError, match="modulo"):
        keys_lib.rows_of(np.arange(3), 8, "modulo")


@pytest.mark.parametrize("servers", [1, 2, 4])
def test_the_reference_check_passes_on_an_identity_cluster_wired_as_the_hybrid_path_is(
    servers,
):
    """``build_cluster`` with the localizer and the ``KVServer`` argument a
    driver on the hybrid path hands it (``device_replies``): the workers
    localise by identity, the servers got the argument, and the check
    reaches every shard and agrees with NumPy AdaGrad at the drawn ids."""
    from parameter_server_tpu.utils.keys import IdentityLocalizer

    from benchmarks.harness import cluster as cluster_lib

    table = cluster_lib.table_config(
        {"name": "emb", "dim": 16, "init_scale": 0.02,
         "optimizer": {"kind": "adagrad", "learning_rate": 0.05}},
        rows=20480,
    )
    cluster = cluster_lib.build_cluster(
        table, workers=2, servers=servers, localizer="identity",
        device_replies=True,
    )
    try:
        assert cluster.localizer == "identity"
        assert all(s.device_replies for s in cluster.servers.values())
        for w in cluster.workers.values():
            assert isinstance(w.localizers["emb"], IdentityLocalizer)
        fails, info = push_pull_check(cluster, 11)
        assert fails == []
        assert info["rows"] == 3000 and info["localizer"] == "identity"
        assert info["pushes"] == 4 and info["max_abs_err"] < 1e-5
        # a cluster that says "hash" over identity workers is caught, in the
        # program's words: the record is what the check goes by
        cluster.localizer = "hash"
        with pytest.raises(ValueError, match="IdentityLocalizer"):
            push_pull_check(cluster, 11)
    finally:
        cluster.close()


def test_cluster_and_batches_reads_the_localizer_once_and_forwards_server_arguments(
    monkeypatch,
):
    """Every driver's set-up starts with ``cluster_and_batches``: it reads
    ``table.localizer`` from the configuration and hands ``build_cluster``
    what a driver gives it for the servers (``device_replies``)."""
    import json
    import os

    from benchmarks.harness import cell as cell_lib
    from benchmarks.harness import cluster as cluster_lib

    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    seen = {}

    class Built(Exception):
        pass

    def fake(table, **kwargs):
        seen.update(kwargs, rows=table.rows)
        raise Built

    monkeypatch.setattr(cluster_lib, "build_cluster", fake)
    run = cell_lib.resolve(
        json.load(open(os.path.join(root, "BENCHMARK.json"))),
        "criteo_lr.skew", seed=5, seconds=0.5, trace=0, dry_run=True,
        bench_dir=os.path.join(root, "benchmarks"),
    )
    with pytest.raises(Built):
        cluster_lib.cluster_and_batches(run, device_replies=True)
    assert seen == {"workers": run.sizes["workers"],
                    "servers": run.sizes["servers"], "localizer": "hash",
                    "device_replies": True, "rows": run.sizes["rows"]}
