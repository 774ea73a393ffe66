import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from parameter_server_tpu.config import OptimizerConfig, TableConfig
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.kv.optim import make_optimizer
from parameter_server_tpu.kv.partition import RangePartition
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.kv.table import KVTable
from parameter_server_tpu.kv.worker import KVWorker
from parameter_server_tpu.utils.keys import HashLocalizer


def test_unknown_optimizer():
    with pytest.raises(ValueError, match="unknown optimizer"):
        make_optimizer(OptimizerConfig(kind="lbfgs"))


def test_sgd_apply():
    opt = make_optimizer(OptimizerConfig(kind="sgd", learning_rate=0.5, l2=0.1))
    v = jnp.ones((2, 3))
    g = jnp.full((2, 3), 2.0)
    new, _ = opt.apply(v, {}, g)
    np.testing.assert_allclose(np.asarray(new), 1 - 0.5 * (2 + 0.1), rtol=1e-6)


def test_adagrad_apply_matches_numpy():
    opt = make_optimizer(OptimizerConfig(kind="adagrad", learning_rate=0.1, eps=1e-8))
    v = jnp.zeros((4, 1))
    state = {"sum_sq": jnp.zeros((4, 1))}
    g = jnp.array([[1.0], [2.0], [0.0], [-1.0]])
    new, ns = opt.apply(v, state, g)
    gn = np.asarray(g)
    expect = -0.1 * gn / (np.abs(gn) + 1e-8)
    expect[2] = 0.0
    np.testing.assert_allclose(np.asarray(new), expect, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ns["sum_sq"]), gn * gn)


def test_adam_per_row_step():
    opt = make_optimizer(OptimizerConfig(kind="adam", learning_rate=0.01))
    v = jnp.zeros((2, 1))
    state = {k: jnp.zeros((2, 1)) for k in ("m", "v", "t")}
    g = jnp.array([[1.0], [0.0]])
    new, ns = opt.apply(v, state, g)
    # row 0 took a step; first adam step size ~= lr
    assert abs(float(new[0, 0]) + 0.01) < 1e-3
    assert float(ns["t"][0, 0]) == 1.0 and float(ns["t"][1, 0]) == 1.0


def test_ftrl_lazy_weights_and_sparsity():
    cfg = OptimizerConfig(kind="ftrl", l1=1.0, ftrl_alpha=0.1)
    opt = make_optimizer(cfg)
    z = jnp.array([[0.5], [-5.0]])
    state = {"n": jnp.array([[1.0], [4.0]])}
    w = opt.pull_weights(z, state)
    assert float(w[0, 0]) == 0.0  # |z| <= l1 -> exactly zero (L1 sparsity)
    expect = -(-5.0 + 1.0) / ((1.0 + 2.0) / 0.1)
    np.testing.assert_allclose(float(w[1, 0]), expect, rtol=1e-5)


def test_ftrl_learns_sign():
    """Pushing constant positive gradients drives the weight negative."""
    cfg = OptimizerConfig(kind="ftrl", l1=0.01, ftrl_alpha=0.5)
    t = KVTable(TableConfig(name="w", rows=8, dim=1, optimizer=cfg))
    ids = jnp.arange(8, dtype=jnp.int32)
    for _ in range(20):
        t.push(ids, jnp.ones((8, 1)))
    w = np.asarray(t.pull(ids))
    assert np.all(w < 0)


def test_table_push_pull_shadow():
    cfg = TableConfig(
        name="emb",
        rows=64,
        dim=8,
        optimizer=OptimizerConfig(kind="sgd", learning_rate=1.0),
    )
    t = KVTable(cfg)
    rng = np.random.default_rng(0)
    shadow = np.zeros((65, 8), dtype=np.float64)
    for _ in range(5):
        ids = np.sort(rng.permutation(64)[:16]).astype(np.int32)
        grads = rng.normal(size=(16, 8)).astype(np.float32)
        t.push(jnp.asarray(ids), jnp.asarray(grads))
        shadow[ids] -= grads
    np.testing.assert_allclose(
        np.asarray(t.pull(jnp.arange(64, dtype=jnp.int32))),
        shadow[:64],
        rtol=1e-5,
        atol=1e-6,
    )


def test_table_init_scale():
    cfg = TableConfig(name="emb", rows=100, dim=16, init_scale=0.1)
    t = KVTable(cfg)
    vals = np.asarray(t.value)
    assert 0.01 < vals[:100].std() < 0.3
    np.testing.assert_allclose(vals[100], 0.0)  # trash row zeroed


def test_trash_row_stays_zero_under_pad_gradients():
    """PAD_KEY positions in variable-nnz batches must not poison the trash row."""
    from parameter_server_tpu.utils.keys import PAD_KEY, HashLocalizer, localize_to_slots

    cfg = TableConfig(
        name="w", rows=64, dim=4,
        optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.5),
    )
    t = KVTable(cfg)
    loc = HashLocalizer(64)
    keys = np.array([5, 9, PAD_KEY, PAD_KEY], dtype=np.uint64)
    slots, inverse, n = localize_to_slots(keys, loc, min_bucket=8)
    grads = np.ones((4, 4), dtype=np.float32)  # pads carry REAL grads
    combined = t.combine(jnp.asarray(inverse), jnp.asarray(grads), slots.shape[0])
    t.push(jnp.asarray(slots), combined)
    np.testing.assert_allclose(np.asarray(t.value)[64], 0.0)  # trash reset
    np.testing.assert_allclose(np.asarray(t.state["sum_sq"])[64], 0.0)
    # pulls of pad positions are exactly zero
    pulled = np.asarray(t.pull(jnp.asarray(slots)))
    trash_positions = slots == 64
    np.testing.assert_allclose(pulled[trash_positions], 0.0)


def test_hash_localizer_rejects_giant_capacity():
    from parameter_server_tpu.utils.keys import HashLocalizer

    with pytest.raises(ValueError, match="int32"):
        HashLocalizer(3_000_000_000)


def test_range_partition():
    p = RangePartition(rows=10, num_servers=3)
    np.testing.assert_array_equal(p.offsets, [0, 4, 7, 10])
    ids = np.array([0, 3, 4, 9, 10], dtype=np.int32)  # 10 == trash
    parts = list(p.slice_ids(ids))
    assert [seg for _, seg, _ in parts] == [slice(0, 2), slice(2, 3), slice(3, 5)]
    np.testing.assert_array_equal(parts[0][2], [0, 3])
    np.testing.assert_array_equal(parts[1][2], [0])
    np.testing.assert_array_equal(parts[2][2], [2, 3])  # local trash == 3


def test_range_partition_empty_segments():
    p = RangePartition(rows=100, num_servers=4)
    parts = list(p.slice_ids(np.array([0, 1], dtype=np.int32)))
    assert len(parts) == 4
    assert parts[1][2].size == 0 and parts[3][2].size == 0


@pytest.fixture
def cluster():
    van = LoopbackVan()
    cfgs = {
        "w": TableConfig(
            name="w",
            rows=1000,
            dim=4,
            optimizer=OptimizerConfig(kind="sgd", learning_rate=1.0),
        )
    }
    servers = [
        KVServer(Postoffice(f"S{i}", van), cfgs, i, 2) for i in range(2)
    ]
    worker = KVWorker(Postoffice("W0", van), cfgs, 2, min_bucket=16)
    yield van, servers, worker, cfgs
    van.close()


def test_worker_server_roundtrip(cluster):
    van, servers, worker, cfgs = cluster
    keys = np.array([17, 999999, 17, 42], dtype=np.uint64)
    # initial pull: zeros
    w0 = worker.pull_sync("w", keys, timeout=10)
    assert w0.shape == (4, 4)
    np.testing.assert_allclose(w0, 0.0)
    # push gradient 1.0 everywhere; key 17 appears twice -> combined grad 2
    ts = worker.push("w", keys, np.ones((4, 4), dtype=np.float32))
    assert worker.wait(ts, timeout=10)
    w1 = worker.pull_sync("w", keys, timeout=10)
    np.testing.assert_allclose(w1[0], -2.0, rtol=1e-6)  # sgd lr=1: w -= g
    np.testing.assert_allclose(w1[2], -2.0, rtol=1e-6)
    np.testing.assert_allclose(w1[1], -1.0, rtol=1e-6)
    np.testing.assert_allclose(w1[3], -1.0, rtol=1e-6)
    assert servers[0].pushes + servers[1].pushes == 2


def test_worker_multi_worker_consistency(cluster):
    """Two workers sharing HashLocalizers see each other's pushes."""
    van, servers, worker, cfgs = cluster
    worker2 = KVWorker(Postoffice("W1", van), cfgs, 2, min_bucket=16)
    keys = np.array([123456789], dtype=np.uint64)
    ts = worker.push("w", keys, np.full((1, 4), 3.0, dtype=np.float32))
    worker.wait(ts, timeout=10)
    w = worker2.pull_sync("w", keys, timeout=10)
    np.testing.assert_allclose(w[0], -3.0, rtol=1e-6)


# ---------------------------------------------------------------------------
# The layout rule (PR 26): a table of dim 1 holds flat [rows + 1] planes, every
# other table [rows + 1, dim]; rows cross every method as [n, dim]; host forms
# are [rows(+1), dim] NumPy whatever the dim.
# ---------------------------------------------------------------------------

_KINDS = ["sgd", "adagrad", "adam", "ftrl"]
_D1_ROWS, _D1_N, _D1_REAL = 50, 16, 11


def _dim1_table(kind, fused=True, **kw):
    return KVTable(
        TableConfig(
            name="w", rows=_D1_ROWS, dim=1, fused_apply=fused,
            optimizer=OptimizerConfig(kind=kind, learning_rate=0.1), **kw,
        ),
        seed=5,
    )


def _padded_ids(rng):
    """Unique row ids, the bucket's tail padded with the trash row."""
    ids = np.full(_D1_N, _D1_ROWS, np.int32)
    ids[:_D1_REAL] = rng.permutation(_D1_ROWS)[:_D1_REAL]
    return ids


def _column_apply(opt):
    """``(apply, pull)`` on [rows + 1, 1] arrays through the XLA entry points,
    the apply with its trash reset: what a dim-1 table ran before its planes
    were flat."""
    from parameter_server_tpu.ops import scatter

    def step(value, state, ids, grads):
        value, state = scatter._apply_rows_xla(
            value, state, ids, grads, opt.apply
        )
        fills = opt.state_shapes()
        return value.at[-1].set(0.0), {
            k: state[k].at[-1].set(fills[k]) for k in state
        }

    def pull(value, state, ids):
        return opt.pull_weights(
            scatter.gather_rows_xla(value, ids),
            {k: scatter.gather_rows_xla(v, ids) for k, v in state.items()},
        )

    return jax.jit(step), jax.jit(pull)


@pytest.mark.parametrize("op", ["push", "push_batch", "push_combined"])
@pytest.mark.parametrize("fused", [True, False], ids=["fused", "threepass"])
@pytest.mark.parametrize("kind", _KINDS)
def test_dim1_table_matches_column_planes_bitwise(kind, fused, op):
    from parameter_server_tpu.ops import scatter

    t = _dim1_table(kind, fused, init_scale=0.1)
    opt, fills = t.optimizer, t.optimizer.state_shapes()
    assert t.value.shape == (_D1_ROWS + 1,)
    assert all(s.shape == (_D1_ROWS + 1,) for s in t.state.values())
    ref_v = jnp.asarray(np.asarray(t.value)[:, None])
    ref_s = {k: jnp.asarray(np.asarray(v)[:, None]) for k, v in t.state.items()}
    column_apply, column_pull = _column_apply(opt)
    rng = np.random.default_rng(11)
    for _ in range(2):
        ids = _padded_ids(rng)
        if op == "push":
            # pads carry REAL gradients: the trash reset has work to do
            grads = rng.normal(size=(_D1_N, 1)).astype(np.float32)
            t.push(jnp.asarray(ids), jnp.asarray(grads))
        else:
            vals = rng.normal(size=(2, _D1_N // 2, 1)).astype(np.float32)
            flat = vals.reshape(-1, 1)
            if op == "push_batch":
                positions = np.full(_D1_N, _D1_N, np.int32)  # the zero row
                positions[:_D1_REAL] = rng.permutation(_D1_N)[:_D1_REAL]
                grads = np.concatenate([flat, np.zeros((1, 1), np.float32)])[
                    positions
                ]
                t.push_batch(
                    jnp.asarray(ids), jnp.asarray(positions), jnp.asarray(vals)
                )
            else:
                inverse = rng.integers(0, _D1_REAL, _D1_N).astype(np.int32)
                grads = np.asarray(
                    scatter.segment_combine(jnp.asarray(flat), inverse, _D1_N)
                )
                t.push_combined(
                    jnp.asarray(ids), jnp.asarray(inverse), jnp.asarray(vals)
                )
        ref_v, ref_s = column_apply(ref_v, ref_s, ids, jnp.asarray(grads))
        assert t.value.shape == (_D1_ROWS + 1,)
        np.testing.assert_array_equal(np.asarray(t.value), np.asarray(ref_v)[:, 0])
        for k in fills:
            assert t.state[k].shape == (_D1_ROWS + 1,)
            np.testing.assert_array_equal(
                np.asarray(t.state[k]), np.asarray(ref_s[k])[:, 0]
            )
            assert float(t.state[k][-1]) == fills[k]  # trash row reset
        assert float(t.value[-1]) == 0.0
        pulled = t.pull(jnp.asarray(ids))
        assert pulled.shape == (_D1_N, 1)
        want = column_pull(ref_v, ref_s, ids)
        np.testing.assert_array_equal(np.asarray(pulled), np.asarray(want))
        np.testing.assert_array_equal(np.asarray(pulled)[_D1_REAL:], 0.0)
    assert not np.array_equal(np.asarray(t.value)[:-1], 0.0)


@pytest.mark.parametrize("dim", [1, 3, 16, 128])
def test_plane_rank_follows_dim(dim):
    t = KVTable(
        TableConfig(
            name="w", rows=24, dim=dim,
            optimizer=OptimizerConfig(kind="adam"),
        )
    )
    want = (25,) if dim == 1 else (25, dim)
    assert t.value.shape == want
    assert {k: v.shape for k, v in t.state.items()} == dict.fromkeys("mvt", want)
    assert t.nominal_bytes == 25 * dim * 4 * 4
    ids = jnp.asarray([3, 7, 24, 24], dtype=jnp.int32)
    t.push(ids, jnp.ones((4, dim), jnp.float32))
    assert t.value.shape == want  # the apply keeps the form it was given
    assert t.pull(ids).shape == (4, dim)
    value, state = t.host_planes()
    assert value.shape == (25, dim) and isinstance(value, np.ndarray)
    assert all(v.shape == (25, dim) for v in state.values())
    assert t.weights().shape == (24, dim)


@pytest.mark.parametrize("dim", [1, 3])
def test_init_scale_draws_the_rows_it_drew(dim):
    """The flat plane of a dim-1 table holds the numbers its [rows + 1, 1]
    form held: restarts and reference runs see the same initial rows."""
    t = KVTable(TableConfig(name="w", rows=100, dim=dim, init_scale=0.1), seed=7)
    want = np.array(
        jax.random.normal(jax.random.PRNGKey(7), (101, dim), jnp.float32) * 0.1
    )
    want[100] = 0.0
    value, _ = t.host_planes()
    np.testing.assert_array_equal(value, want)
    assert np.abs(value[:100]).min() > 0.0


def _plane_sized_relayouts(text, n):
    """Lines of a lowered program that reshape, reduce, broadcast or
    transpose something of ``n`` elements."""
    bad = []
    for line in text.splitlines():
        if not re.search(
            r"stablehlo\.(reshape|reduce|broadcast|broadcast_in_dim|"
            r"transpose|dynamic_reshape)\b",
            line,
        ):
            continue
        for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]\w*>", line):
            if np.prod([int(d) for d in dims.split("x") if d]) == n:
                bad.append(line.strip())
                break
    return bad


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "threepass"])
@pytest.mark.parametrize("kind", ["adagrad", "ftrl"])
@pytest.mark.parametrize("prog", ["push", "push_batch", "push_combined", "pull"])
def test_dim1_programs_hold_no_pass_over_a_plane(prog, kind, fused):
    """What keeps a later edit from bringing the relayout back unseen on the
    CPU: the programs of a dim-1 table take rank-1 planes, donate them to
    the scatter, and no reshape, reduce, broadcast or transpose in them has
    an operand or a result of ``rows + 1`` elements (``PERF.md`` section 6,
    PR 26: on the TPU an ``[N, 1]`` plane cost two to five passes over 2 GiB
    in every program)."""
    rows, n = 1000, 64  # 1001 = 7 x 11 x 13: no n-row operand has that size
    t = KVTable(
        TableConfig(
            name="w", rows=rows, dim=1, fused_apply=fused,
            optimizer=OptimizerConfig(kind=kind),
        )
    )
    ids = jnp.zeros((n,), jnp.int32)
    vals = jnp.zeros((2, n // 2, 1), jnp.float32)
    fn, args = {
        "push": (t._push_fn, (ids, jnp.zeros((n, 1), jnp.float32))),
        "push_batch": (t._push_batch_fn, (ids, ids, vals)),
        "push_combined": (t._push_combined_fn, (ids, ids, vals)),
        "pull": (t._pull_fn, (ids,)),
    }[prog]
    text = fn.lower(t.value, t.state, *args).as_text()
    assert _plane_sized_relayouts(text, rows + 1) == []
    main = re.search(r"func\.func public @main\((.*?)\) ->", text).group(1)
    planes = re.findall(r"(%arg\d+): tensor<([\dx]+)xf32>( \{[^}]*\})?", main)
    flat = [p for p in planes if p[1] == str(rows + 1)]
    # the value plane and, where the program reads it, the state plane
    assert len(flat) >= 1 and not re.search(rf"tensor<{rows + 1}x1xf32>", text)
    if prog != "pull":
        assert len(flat) == 1 + len(t.state)
        assert all("tf.aliasing_output" in p[2] for p in flat), main
    # the checker sees a plane-sized reshape where there is one
    col = jnp.zeros((rows + 1, 1), jnp.float32)
    bad = jax.jit(lambda v: v.reshape(-1)).lower(col).as_text()
    assert _plane_sized_relayouts(bad, rows + 1)


def test_dim1_host_forms_round_trip():
    """``set_value``, ``install_rows``, ``resize``, ``host_planes`` and
    ``weights`` give and take ``[rows(+1), 1]`` NumPy arrays; the planes on
    the device stay flat through all of them."""
    t = _dim1_table("adagrad")
    rng = np.random.default_rng(3)
    buf = rng.normal(size=(_D1_ROWS + 1, 1)).astype(np.float32)
    t.set_value(buf)
    assert t.value.shape == (_D1_ROWS + 1,)
    np.testing.assert_array_equal(t.host_planes()[0], buf)
    with pytest.raises(ValueError, match="expected"):
        t.set_value(buf[:, 0])  # the host form is [rows + 1, dim], not flat
    rows = rng.normal(size=(30, 1)).astype(np.float32)
    acc = rng.uniform(size=(30, 1)).astype(np.float32)
    t.install_rows(rows, {"sum_sq": acc})  # another row count, no trash row
    assert t.rows == 30 and t.value.shape == (31,)
    assert t.state["sum_sq"].shape == (31,)
    value, state = t.host_planes()
    np.testing.assert_array_equal(value[:30], rows)
    np.testing.assert_array_equal(state["sum_sq"][:30], acc)
    assert value[30, 0] == 0.0 and state["sum_sq"][30, 0] == 0.0
    np.testing.assert_array_equal(t.weights(), rows)
    grown = np.concatenate([value, value])  # [62, 1], trash row included
    t.resize(grown, {"sum_sq": np.concatenate([state["sum_sq"]] * 2)})
    assert t.rows == 61 and t.value.shape == (62,)
    np.testing.assert_array_equal(t.host_planes()[0], grown)
    with pytest.raises(ValueError, match="bad resize value shape"):
        t.resize(grown[:, 0], {"sum_sq": grown[:, 0]})
    # the resized shard still applies and serves
    ids = jnp.asarray([0, 60, 61, 61], dtype=jnp.int32)
    t.push(ids, jnp.ones((4, 1), jnp.float32))
    assert t.pull(ids).shape == (4, 1) and t.value.shape == (62,)


# ---------------------------------------------------------------------------
# A flat plane's apply (PR 36): a push that says how many of its ids are real
# ends with the rows of one that does not, the trash row reset; a server
# counts a leg's ids up to the last that is a row of its shard.
# ---------------------------------------------------------------------------

_F_ROWS, _F_BUCKET = 20_000, 4096
_F_REAL = _F_BUCKET // 2 + 1  # pads present, a chunk's edge + 1


@pytest.mark.parametrize("op", ["push", "push_batch", "push_combined"])
@pytest.mark.parametrize("fused", [True, False], ids=["fused", "threepass"])
@pytest.mark.parametrize("kind", _KINDS)
def test_dim1_push_of_counted_ids_matches_the_plain_push(kind, fused, op):
    def table():
        return KVTable(
            TableConfig(
                name="w", rows=_F_ROWS, dim=1, fused_apply=fused,
                init_scale=0.1,
                optimizer=OptimizerConfig(kind=kind, learning_rate=0.1),
            ),
            seed=5,
        )

    plain, told = table(), table()
    rng = np.random.default_rng(17)
    n = np.int32(_F_REAL)
    for _ in range(2):
        ids = np.full(_F_BUCKET, _F_ROWS, np.int32)
        ids[:_F_REAL] = np.sort(rng.choice(_F_ROWS, _F_REAL, replace=False))
        ids = jnp.asarray(ids)
        if op == "push":
            # the pads carry REAL gradients, as a worker's PAD_KEY
            # positions may: the trash reset leaves nothing of them
            args = (jnp.asarray(
                rng.normal(size=(_F_BUCKET, 1)).astype(np.float32)
            ),)
        else:
            vals = rng.normal(size=(2, _F_BUCKET // 2, 1)).astype(np.float32)
            if op == "push_batch":
                index = np.full(_F_BUCKET, _F_BUCKET, np.int32)  # zero row
                index[:_F_REAL] = rng.permutation(_F_BUCKET)[:_F_REAL]
            else:  # every position sums into a real slot
                index = rng.integers(0, _F_REAL, _F_BUCKET).astype(np.int32)
            args = (jnp.asarray(index), jnp.asarray(vals))
        getattr(plain, op)(ids, *args)
        getattr(told, op)(ids, *args, n)
    want_v, want_s = plain.host_planes()
    got_v, got_s = told.host_planes()
    np.testing.assert_array_equal(got_v, want_v)
    assert got_v[-1, 0] == 0.0 and np.abs(got_v[:-1]).max() > 0.0
    for k, fill in told.optimizer.state_shapes().items():
        np.testing.assert_array_equal(got_s[k], want_s[k])
        assert got_s[k][-1, 0] == fill  # trash row reset
    text = getattr(told, f"_{op}_fn").lower(
        told.value, told.state, ids, *args, n
    ).as_text()
    assert ("while" in text) == fused  # three passes walk the whole bucket


def test_rank2_table_compiles_one_program_whatever_a_push_says():
    t = KVTable(
        TableConfig(
            name="e", rows=64, dim=128,
            optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
        )
    )
    ids = jnp.asarray(np.r_[np.arange(5), [64] * 3].astype(np.int32))
    grads = jnp.ones((8, 128), jnp.float32)
    t.push(ids, grads)
    t.push(ids, grads, np.int32(5))
    assert t._push_fn._cache_size() == 1
    assert "while" not in t._push_fn.lower(
        t.value, t.state, ids, grads
    ).as_text()


@pytest.mark.parametrize("dup_policy", [None, "rounds", "combine"])
def test_server_counts_a_leg_up_to_its_last_row_of_the_shard(dup_policy):
    """A worker's leg ends in its own bucket pads (keys past the table, at
    the trash row, here with gradients as PAD_KEY positions may carry) and
    then the server's: neither is visited.  A client whose keys descend,
    pads first (nothing in the wire contract forbids it), is walked up to
    its last real id.  Both end with the rows a plain table ends with."""
    from parameter_server_tpu.config import ApplyEngineConfig
    from parameter_server_tpu.core.messages import Message, Task, TaskKind

    rows = 6000
    cfg = TableConfig(
        name="w", rows=rows, dim=1, init_scale=0.1,
        optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
    )
    apply = (
        None if dup_policy is None
        else ApplyEngineConfig(dup_policy=dup_policy)
    )

    def push(ids, grads):
        return Message(
            task=Task(TaskKind.PUSH, "kv", payload={"table": "w"}),
            sender="W0", recver="S0", keys=ids.astype(np.int32),
            values=[grads.reshape(-1, 1)],
        )

    rng = np.random.default_rng(23)
    perm = rng.permutation(rows)  # disjoint: any dup_policy is sequential
    # 1500 rows in order and 700 of the worker's pads: a bucket of 4096
    up = np.r_[np.sort(perm[:1500]), [rows] * 700]
    down = np.r_[[rows] * 3, np.sort(perm[1500:1521])[::-1]]
    g_up = rng.normal(size=up.size).astype(np.float32)
    g_down = rng.normal(size=down.size).astype(np.float32)
    van = LoopbackVan()
    try:
        server = KVServer(Postoffice("S0", van), {"w": cfg}, 0, 1, apply=apply)
        plain = KVTable(cfg)
        plain.resize(*server.tables["w"].host_planes())
        msgs = [push(up, g_up), push(down, g_down)]
        if dup_policy is None:
            replies = [server.handle_request(m) for m in msgs]
            want = {"apply_ids_real": 1500 + 24, "apply_ids_bucket": 4096 + 32}
        else:  # a bundle: the trash ids are dropped, the rest sorted
            replies = server.handle_request_batch(msgs)
            want = {"apply_ids_real": 1521, "apply_ids_bucket": 2048}
        assert all("__error__" not in r.task.payload for r in replies)
        got = server.counters()
        assert {k: got[k] for k in want} == want
        for ids, g in ((up, g_up), (down, g_down)):
            pad = -len(ids) % 8
            plain.push(
                jnp.asarray(np.r_[ids, [rows] * pad].astype(np.int32)),
                jnp.asarray(np.r_[g, [0.0] * pad].astype(np.float32)[:, None]),
            )
        want_v, want_s = plain.host_planes()
        got_v, got_s = server.tables["w"].host_planes()
        np.testing.assert_array_equal(got_v, want_v)
        np.testing.assert_array_equal(got_s["sum_sq"], want_s["sum_sq"])
        assert got_v[-1, 0] == 0.0 and got_s["sum_sq"][-1, 0] == 0.0
    finally:
        van.close()
