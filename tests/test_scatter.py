import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from parameter_server_tpu.ops import scatter
from parameter_server_tpu.utils.keys import localize_batch


def _table(rows=64, dim=128, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(rows, dim)).astype(np.float32))


def test_gather_xla_matches_numpy():
    t = _table()
    ids = jnp.array([3, 0, 3, 63], dtype=jnp.int32)
    out = scatter.gather_rows(t, ids, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(t)[[3, 0, 3, 63]])


def test_scatter_add_xla_duplicates():
    t = _table(rows=8, dim=128)
    ids = jnp.array([1, 1, 2], dtype=jnp.int32)
    rows = jnp.ones((3, 128), dtype=jnp.float32)
    out = scatter.scatter_add_rows(t, ids, rows, impl="xla")
    expect = np.asarray(t).copy()
    expect[1] += 2.0
    expect[2] += 1.0
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)


def test_segment_combine_pads_zero():
    vals = jnp.ones((5, 4), dtype=jnp.float32)
    inverse = jnp.array([0, 0, 1, 2, 1], dtype=jnp.int32)
    out = scatter.segment_combine(vals, inverse, num_rows=8)
    np.testing.assert_allclose(np.asarray(out)[0], 2.0 * np.ones(4))
    np.testing.assert_allclose(np.asarray(out)[1], 2.0 * np.ones(4))
    np.testing.assert_allclose(np.asarray(out)[2], 1.0 * np.ones(4))
    np.testing.assert_allclose(np.asarray(out)[3:], 0.0)


def test_combine_and_scatter_add_end_to_end():
    """Full push apply: raw batch keys -> localize -> combine -> scatter."""
    capacity, dim = 32, 128
    table = jnp.zeros((capacity + 1, dim), dtype=jnp.float32)
    keys = np.array([100, 7, 100, 9, 7, 100], dtype=np.uint64)
    uniq, inverse, n = localize_batch(keys, min_bucket=8)
    # dense local ids: pretend localizer assigned slots 0..n-1, pads -> trash
    slots = np.full(uniq.shape[0], capacity, dtype=np.int32)
    slots[:n] = np.arange(n)
    grads = jnp.ones((keys.shape[0], dim), dtype=jnp.float32)
    out = scatter.combine_and_scatter_add(
        table, jnp.asarray(slots), jnp.asarray(inverse), grads, uniq.shape[0]
    )
    out = np.asarray(out)
    # uniq sorted: [7, 9, 100]; counts [2, 1, 3]
    np.testing.assert_allclose(out[0], 2.0)
    np.testing.assert_allclose(out[1], 1.0)
    np.testing.assert_allclose(out[2], 3.0)
    np.testing.assert_allclose(out[3:capacity], 0.0)
    np.testing.assert_allclose(out[capacity], 0.0)  # trash row got only zeros


def test_gather_grad_is_scatter():
    """XLA gather must be differentiable (backward = scatter-add)."""
    t = _table(rows=8, dim=128)
    ids = jnp.array([1, 1, 3], dtype=jnp.int32)

    def loss(tbl):
        return jnp.sum(scatter.gather_rows(tbl, ids, impl="xla") ** 2)

    g = jax.grad(loss)(t)
    expect = np.zeros_like(np.asarray(t))
    tn = np.asarray(t)
    expect[1] = 2 * 2 * tn[1]  # row 1 gathered twice
    expect[3] = 2 * tn[3]
    np.testing.assert_allclose(np.asarray(g), expect, rtol=1e-6)


def test_pallas_rejects_unaligned_ids():
    t = _table(rows=16, dim=128)
    ids = jnp.array([1, 2, 3], dtype=jnp.int32)  # not a multiple of 8
    with pytest.raises(ValueError, match="bucket-pad"):
        scatter._pallas_gather(t, ids, interpret=True)
    with pytest.raises(ValueError, match="bucket-pad"):
        scatter._pallas_scatter_add(t, ids, jnp.ones((3, 128)), interpret=True)


def test_pallas_rejects_unaligned_dim():
    t = jnp.zeros((16, 100), dtype=jnp.float32)
    ids = jnp.arange(8, dtype=jnp.int32)
    with pytest.raises(ValueError, match="dim == 128 or dim % 1024"):
        scatter._pallas_gather(t, ids, interpret=True)


def test_combine_and_scatter_add_duplicate_slots():
    """Overflowed-localizer case: two unique keys sharing a slot must both land."""
    table = jnp.zeros((4, 128), dtype=jnp.float32)
    # unique keys 0,1 both hashed to slot 2
    ids = jnp.array([2, 2], dtype=jnp.int32)
    inverse = jnp.array([0, 1], dtype=jnp.int32)
    vals = jnp.ones((2, 128), dtype=jnp.float32)
    out = scatter.combine_and_scatter_add(table, ids, inverse, vals, num_rows=2)
    np.testing.assert_allclose(np.asarray(out)[2], 2.0)


@pytest.mark.parametrize("op", ["gather", "scatter_add"])
def test_pallas_interpret_matches_xla(op):
    """Pallas kernels in interpret mode on CPU must match the XLA path."""
    t = _table(rows=64, dim=128)
    ids = jnp.asarray(np.random.default_rng(1).permutation(64)[:16].astype(np.int32))
    if op == "gather":
        got = scatter._pallas_gather(t, ids, interpret=True)
        want = scatter.gather_rows_xla(t, ids)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want))
    else:
        rows = jnp.asarray(
            np.random.default_rng(2).normal(size=(16, 128)).astype(np.float32)
        )
        got = scatter._pallas_scatter_add(t, ids, rows, interpret=True)
        want = scatter.scatter_add_rows_xla(t, ids, rows)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_pallas_scatter_set_matches_xla():
    t = _table(rows=64, dim=128)
    ids = jnp.asarray(
        np.random.default_rng(3).choice(63, size=16, replace=False), jnp.int32
    )
    rows = jnp.asarray(
        np.random.default_rng(4).normal(size=(16, 128)), jnp.float32
    )
    want = scatter.scatter_update_rows_xla(t, ids, rows)
    got = scatter._pallas_scatter_set(t, ids, rows, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    # dispatcher form
    got2 = scatter.scatter_update_rows(
        t, ids, rows, impl="pallas", interpret=True
    )
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want), atol=1e-6)


@pytest.mark.parametrize("n,block", [(64, None), (64, 8), (64, 32), (24, None), (96, 16)])
def test_pallas_double_buffered_scatter_add_blocks(n, block):
    """The double-buffered RMW kernel is exact for every block geometry.

    n=24 exercises the auto-pick fallback to 8; explicit blocks exercise the
    slot-reuse wait logic at different pipeline depths.
    """
    t = _table(rows=128, dim=128, seed=5)
    rng = np.random.default_rng(6)
    ids = jnp.asarray(rng.choice(127, size=n, replace=False), jnp.int32)
    rows = jnp.asarray(rng.normal(size=(n, 128)), jnp.float32)
    want = scatter.scatter_add_rows_xla(t, ids, rows)
    got = scatter._pallas_scatter_add(
        t, ids, rows, interpret=True, block_rows=block
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5
    )


def test_pallas_block_rows_validation():
    t = _table(rows=16, dim=128)
    ids = jnp.arange(12, dtype=jnp.int32)  # not divisible by 8
    with pytest.raises(ValueError, match="divisible by 8"):
        scatter._pallas_gather(t, ids, interpret=True)
    with pytest.raises(ValueError, match="block_rows"):
        scatter._pallas_gather(t, jnp.arange(16, dtype=jnp.int32),
                               interpret=True, block_rows=32)


def test_kvserver_full_path_pallas_parity():
    """FULL production push/pull path under scatter_impl='pallas' (VERDICT
    r2 #4): two identical KVServer clusters, one per kernel impl, must stay
    bitwise-close through repeated pushes with duplicates + pads."""
    from parameter_server_tpu.config import OptimizerConfig, TableConfig
    from parameter_server_tpu.core.postoffice import Postoffice
    from parameter_server_tpu.core.van import LoopbackVan
    from parameter_server_tpu.kv.server import KVServer
    from parameter_server_tpu.kv.worker import KVWorker
    from parameter_server_tpu.utils.keys import HashLocalizer

    rows, dim = 512, 128
    keys = (np.arange(96, dtype=np.uint64) * 7919) % 3000
    keys = np.concatenate([keys, keys[:32]])  # duplicates pre-combine
    rng = np.random.RandomState(0)
    grads = rng.randn(keys.size, dim).astype(np.float32)

    pulled = {}
    for impl in ("xla", "pallas"):
        cfgs = {
            "e": TableConfig(
                name="e", rows=rows, dim=dim,
                optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
                scatter_impl=impl,
            )
        }
        van = LoopbackVan()
        try:
            servers = [
                KVServer(
                    Postoffice(f"S{i}", van), cfgs, i, 2,
                    pallas_interpret=True,  # asked for, never inferred
                )
                for i in range(2)
            ]
            assert all(
                s.tables["e"]._interpret and s.tables["e"].device == d
                for s, d in zip(servers, jax.local_devices())
            )
            worker = KVWorker(
                Postoffice("W0", van), cfgs, 2, min_bucket=16,
                localizers={"e": HashLocalizer(rows)},
            )
            for _ in range(3):
                worker.wait(worker.push("e", keys, grads), timeout=30)
            pulled[impl] = worker.pull_sync("e", keys, timeout=30)
        finally:
            van.close()
    np.testing.assert_allclose(
        pulled["pallas"], pulled["xla"], atol=1e-5, rtol=1e-5
    )


@pytest.mark.parametrize("dim", [1024, 2048])
def test_pallas_chunked_wide_rows(dim):
    """Wide rows (transformer d_model) DMA as (dim//128, 128) chunks of the
    (rows*c, 128) view — the layout Mosaic accepts for dim % 1024 == 0."""
    rng = np.random.default_rng(8)
    t = jnp.asarray(rng.normal(size=(64, dim)), jnp.float32)
    ids = jnp.asarray(rng.choice(63, size=16, replace=False), jnp.int32)
    rows = jnp.asarray(rng.normal(size=(16, dim)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(scatter._pallas_gather(t, ids, interpret=True)),
        np.asarray(jnp.take(t, ids, axis=0)), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(scatter._pallas_scatter_add(t, ids, rows, interpret=True)),
        np.asarray(scatter.scatter_add_rows_xla(t, ids, rows)),
        atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(scatter._pallas_scatter_set(t, ids, rows, interpret=True)),
        np.asarray(scatter.scatter_update_rows_xla(t, ids, rows)), atol=1e-6)


def test_pallas_rejects_unsupported_dim():
    t = jnp.zeros((16, 256), jnp.float32)  # 256: single-row slice unaligned
    with pytest.raises(ValueError, match="dim == 128 or dim % 1024"):
        scatter._pallas_gather(t, jnp.arange(8, dtype=jnp.int32), interpret=True)
    # and auto mode silently falls back to XLA
    out = scatter.gather_rows(t, jnp.arange(8, dtype=jnp.int32), impl="auto")
    assert out.shape == (8, 256)


# ---------------------------------------------------------------------------
# A rank-1 plane is a dim-1 table (PR 26): the XLA entry points take a flat
# ``[rows + 1]`` plane and give and take ``[n, 1]`` rows, bit for bit what
# they do on the ``[rows + 1, 1]`` column form.
# ---------------------------------------------------------------------------

_FLAT_ROWS = 40


def _flat_case(seed=0):
    """(flat plane, its column form, unique ids with pads at the trash row,
    [n, 1] rows whose pad rows are zero)."""
    rng = np.random.default_rng(seed)
    plane = rng.normal(size=_FLAT_ROWS + 1).astype(np.float32)
    plane[-1] = 0.0
    ids = np.full(16, _FLAT_ROWS, np.int32)
    ids[:11] = rng.permutation(_FLAT_ROWS)[:11]
    rows = rng.normal(size=(16, 1)).astype(np.float32)
    rows[11:] = 0.0
    return (
        jnp.asarray(plane), jnp.asarray(plane[:, None]), jnp.asarray(ids),
        jnp.asarray(rows),
    )


@pytest.mark.parametrize("op", ["gather", "scatter_update", "scatter_add"])
@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
def test_flat_plane_matches_column_plane_bitwise(op, jit):
    flat, col, ids, rows = _flat_case()
    if op == "gather":
        fn, args = scatter.gather_rows, ()
    else:
        fn = {
            "scatter_update": scatter.scatter_update_rows,
            "scatter_add": scatter.scatter_add_rows,
        }[op]
        args = (rows,)
    if jit:
        fn = jax.jit(fn)
    got, want = fn(flat, ids, *args), fn(col, ids, *args)
    if op == "gather":
        assert got.shape == (16, 1)  # rows cross the interface as [n, dim]
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    else:
        assert got.shape == (_FLAT_ROWS + 1,)  # the plane keeps its form
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want)[:, 0])


@pytest.mark.parametrize("kind", ["sgd", "adagrad", "adam", "ftrl"])
@pytest.mark.parametrize("jit,l2", [(False, 0.01), (True, 0.0)], ids=["eager-l2", "jit"])
def test_apply_rows_flat_plane_matches_column_plane_bitwise(kind, jit, l2):
    """Same gathers, same row function on ``[n, 1]`` blocks, same
    write-backs.  Op by op (eager) that is bit for bit under every rule,
    penalties included; under ``jit`` the CPU compiler contracts
    ``grad + l2 * value`` into the next product differently in an ``[n]`` and
    an ``[n, 1]`` fusion (AdaGrad's ``sum_sq``, one ulp in one row), so the
    compiled case runs the rules without the penalty."""
    from parameter_server_tpu.config import OptimizerConfig
    from parameter_server_tpu.kv.optim import make_optimizer

    opt = make_optimizer(OptimizerConfig(kind=kind, learning_rate=0.1, l2=l2))
    flat, col, ids, grads = _flat_case(seed=1)
    fills = opt.state_shapes()
    apply = scatter.apply_rows
    if jit:
        apply = jax.jit(apply, static_argnames=("row_fn",))
    fv, fs = flat, {k: jnp.full_like(flat, f) for k, f in fills.items()}
    cv, cs = col, {k: jnp.full_like(col, f) for k, f in fills.items()}
    for _ in range(3):  # state planes move too
        fv, fs = apply(fv, fs, ids, grads, row_fn=opt.apply)
        cv, cs = apply(cv, cs, ids, grads, row_fn=opt.apply)
    assert fv.shape == (_FLAT_ROWS + 1,) and cv.shape == (_FLAT_ROWS + 1, 1)
    np.testing.assert_array_equal(np.asarray(fv), np.asarray(cv)[:, 0])
    for k in fills:
        assert fs[k].shape == (_FLAT_ROWS + 1,)
        np.testing.assert_array_equal(np.asarray(fs[k]), np.asarray(cs[k])[:, 0])
    assert not np.array_equal(np.asarray(fv), np.asarray(flat))


def test_flat_plane_refuses_rows_wider_than_one():
    flat, _col, ids, _rows = _flat_case()
    with pytest.raises(TypeError, match="reshape"):
        scatter.scatter_update_rows(flat, ids, jnp.ones((16, 2)))


@pytest.mark.parametrize(
    "op", ["gather", "scatter_update", "scatter_add", "apply"]
)
def test_pallas_refuses_flat_plane_by_its_dim(op):
    """``scatter_impl="pallas"`` refused dim 1 as ``[N, 1]``; the flat plane
    is refused for the same reason, in the same words."""
    flat, _col, ids, rows = _flat_case()
    with pytest.raises(ValueError, match=r"dim == 128 or dim % 1024 == 0, got 1"):
        if op == "gather":
            scatter.gather_rows(flat, ids, impl="pallas", interpret=True)
        elif op == "apply":
            scatter.apply_rows(
                flat, {}, ids, rows, lambda v, s, g: (v - g, s),
                impl="pallas", interpret=True,
            )
        else:
            getattr(scatter, op + "_rows")(
                flat, ids, rows, impl="pallas", interpret=True
            )


# ---------------------------------------------------------------------------
# A flat plane's apply (PR 36): given the count ``n`` of its real ids it walks
# the bucket a chunk a turn and stops after the turn that holds the last real
# id.  Every real row is the whole-bucket apply's, bit for bit; a rank-2
# plane's program is the same with and without the count.
# ---------------------------------------------------------------------------

_CHUNK = scatter._FLAT_CHUNK
_BUCKET = 8 * _CHUNK
_BIG_ROWS = 3 * _BUCKET


@functools.lru_cache(maxsize=None)
def _flat_applies(kind):
    """``(optimizer, whole, counted)``: the whole-bucket apply and the apply
    given ``n``, jitted.  ``n`` is traced: one program serves every count."""
    from parameter_server_tpu.config import OptimizerConfig
    from parameter_server_tpu.kv.optim import make_optimizer

    opt = make_optimizer(OptimizerConfig(kind=kind, learning_rate=0.1))

    def whole(value, state, ids, grads):
        return scatter.apply_rows(value, state, ids, grads, opt.apply)

    def counted(value, state, ids, grads, n):
        return scatter.apply_rows(value, state, ids, grads, opt.apply, n=n)

    return opt, jax.jit(whole), jax.jit(counted)


def _leg(n, seed):
    """A leg as a server hands it over: ``n`` unique ids in order, the
    bucket's tail padded with the trash row and zero gradients."""
    rng = np.random.default_rng(seed)
    ids = np.full(_BUCKET, _BIG_ROWS, np.int32)
    ids[:n] = np.sort(rng.choice(_BIG_ROWS, size=n, replace=False))
    grads = np.zeros((_BUCKET, 1), np.float32)
    grads[:n] = rng.normal(size=(n, 1))
    return jnp.asarray(ids), jnp.asarray(grads)


@pytest.mark.parametrize("kind", ["sgd", "adagrad", "adam", "ftrl"])
@pytest.mark.parametrize(
    "n",
    [0, 1, _CHUNK - 1, _CHUNK, _CHUNK + 1, _BUCKET // 2 + 1, _BUCKET],
    ids=["none", "one", "edge-1", "edge", "edge+1", "half+1", "bucket"],
)
def test_flat_apply_of_counted_ids_matches_whole_bucket_bitwise(kind, n):
    opt, whole, counted = _flat_applies(kind)
    rng = np.random.default_rng(2)
    plane = rng.normal(size=_BIG_ROWS + 1).astype(np.float32)
    plane[-1] = 0.0
    fills = opt.state_shapes()
    wv, ws = jnp.asarray(plane), {
        k: jnp.full((_BIG_ROWS + 1,), f, jnp.float32) for k, f in fills.items()
    }
    cv, cs = wv, dict(ws)
    for turn in range(2):  # the state planes move too
        ids, grads = _leg(n, seed=10 * n + turn)
        wv, ws = whole(wv, ws, ids, grads)
        cv, cs = counted(cv, cs, ids, grads, np.int32(n))
        # the trash row is the table's to reset: a pad visited or not
        # leaves it as it likes
        np.testing.assert_array_equal(np.asarray(cv)[:-1], np.asarray(wv)[:-1])
        for k in fills:
            np.testing.assert_array_equal(
                np.asarray(cs[k])[:-1], np.asarray(ws[k])[:-1]
            )
    assert np.array_equal(np.asarray(cv)[:-1], plane[:-1]) == (n == 0)
    assert counted._cache_size() == 1  # one program a bucket whatever n


def _lowered(value, b, with_n):
    """Lowered text of ``apply_rows`` on a bucket of ``b`` ids of a plane
    like ``value``, told the count or not."""
    def f(value, state, ids, rows, n):
        return scatter.apply_rows(
            value, state, ids, rows, lambda v, s, g: (v - g, s),
            n=n if with_n else None,
        )

    dim = 1 if value.ndim == 1 else value.shape[1]
    return jax.jit(f).lower(
        value, {"s": value}, jnp.zeros(b, jnp.int32), jnp.zeros((b, dim)),
        np.int32(3),
    ).as_text()


def test_flat_apply_walks_the_whole_bucket_without_a_count():
    """No ``n``: the parent's program, no loop; a bucket no chunk divides
    (a caller of the module with its own sizes) likewise; nothing tells the
    compiler that the ids are in order (module docstring)."""
    flat = jnp.zeros(41)
    assert "while" not in _lowered(flat, 16, False)
    assert "while" in _lowered(flat, 16, True)
    assert "while" not in _lowered(flat, 3 * _CHUNK // 2, True)
    assert "indices_are_sorted = true" not in _lowered(flat, 16, True)


def test_rank2_plane_lowers_to_the_same_text_with_and_without_the_count():
    """``dlrm_emb``'s and the ``pretrain8k`` cells' planes are rank 2: the
    count selects nothing there."""
    wide = jnp.zeros((41, 128))
    want = _lowered(wide, 16, False)
    assert _lowered(wide, 16, True) == want and "while" not in want
