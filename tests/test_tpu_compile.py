"""The table programs of a dim-1 table, compiled for a described v5e chip.

No chip is attached and nothing runs: the TPU's compiler is installed here
and compiles for a topology that is described (``on-chip-measurement`` guide,
section 2).  What it shows is what a CPU run cannot: whether the compiled
program passes over a whole plane.  With ``[N, 1]`` planes every pull held one
such pass and every apply eight, 85 % of ``criteo_lr.skew``'s device time
(``PERF.md`` section 6, PR 26); with flat planes the only operations over a
plane are the gather and scatter fusions and the in-place trash reset.

Blocked attention under a window (``ops/blocked_attention.py``, PR 35) is
compiled here at a window layer's published shapes: what the step's own
compile showed (stacked masks of 1.9 GB a layer under a checkpoint,
``blocked_attention._tied``) shows only for the described chip's memory.

All such compiles live in this one file, and the topology is described
inside a fixture: only one process may load the TPU's library.
"""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from parameter_server_tpu.config import OptimizerConfig, TableConfig
from parameter_server_tpu.kv.table import KVTable
from parameter_server_tpu.ops import scatter
from parameter_server_tpu.ops.blocked_attention import blocked_causal_attention

#: one server's shard of ``criteo_lr.skew`` and its largest id bucket.  The
#: real size, because a small plane compiles differently (the compiler
#: stages a 16 MiB plane through fast memory); only shapes are handed over,
#: no plane of this size is allocated here.
ROWS = 1 << 29
N = 65536


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    """``fn`` compiled for the described chip (``conftest.py`` keeps the
    persistent compile cache off: an entry written without a chip cannot be
    read back)."""
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes,
    )
    return fn.lower(*args).compile()


def _plane_ops(compiled, elements):
    """``{opcode: count}`` of the operations of the ENTRY computation and of
    every ``while``'s body and condition whose result is a float32 array of
    ``elements`` elements; parameters, bitcasts and a tuple's elements aside
    (they move nothing)."""
    text = compiled.as_text()
    blocks = {
        m.group(2): m.group(3)
        for m in re.finditer(
            r"^(ENTRY )?%(\S+) \(.*?\{\n(.*?)^\}", text, re.M | re.S
        )
    }
    walked = [body for name, body in blocks.items() if name.startswith("main")]
    walked += [
        blocks[name]
        for name in re.findall(r"(?:body|condition)=%([\w.\-]+)", text)
    ]
    ops = {}
    for m in re.finditer(
        r"= f32\[([\d,]+)\]\S* ([\w\-]+)\(", "\n".join(walked)
    ):
        size = int(np.prod([int(d) for d in m.group(1).split(",")]))
        if size == elements and m.group(2) not in (
            "parameter", "bitcast", "get-tuple-element",
        ):
            ops[m.group(2)] = ops.get(m.group(2), 0) + 1
    return ops


def _table():
    """A dim-1 table of a few rows: its jitted programs retrace for the
    shapes they are lowered with."""
    return KVTable(
        TableConfig(
            name="w", rows=8, dim=1,
            optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
        )
    )


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_dim1_pull_compiles_to_one_gather_over_the_plane(one_chip):
    t = _table()
    c = _compile(
        t._pull_fn, one_chip, _sds((ROWS + 1,)),
        {k: _sds((ROWS + 1,)) for k in t.state}, _sds((N,), jnp.int32),
    )
    assert _plane_ops(c, ROWS + 1) == {}  # the gather's result is [N]
    assert c.memory_analysis().temp_size_in_bytes < 4 * ROWS // 8


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "threepass"])
def test_dim1_apply_compiles_to_scatters_and_the_trash_reset(one_chip, fused):
    t = _table()
    t.fused_apply = fused
    planes = 1 + len(t.state)
    c = _compile(
        t._push_fn, one_chip, _sds((ROWS + 1,)),
        {k: _sds((ROWS + 1,)) for k in t.state}, _sds((N,), jnp.int32),
        _sds((N, 1)),
    )
    # a scatter fusion and an in-place trash reset (a dynamic-update-slice
    # fusion) for each plane, and nothing else: no reduce, no while, no
    # broadcast, no copy of a plane
    assert _plane_ops(c, ROWS + 1) == {"fusion": 2 * planes}
    assert c.memory_analysis().temp_size_in_bytes < 4 * ROWS // 8


@pytest.mark.parametrize("n_ids", [N, N // 2])
def test_dim1_apply_of_counted_ids_loops_over_chunks_in_place(one_chip, n_ids):
    """What a server's push runs since PR 36: the count ``n`` an operand
    (one program a bucket whatever it holds), one ``while`` whose body holds
    a chunk's gathers, update and both write-backs; the planes aliased, and
    still nothing over a plane but the scatter fusions and the trash reset.
    No scatter is told that its ids are in order: that selects a pass over
    the plane (``ops/scatter.py``)."""
    t = _table()
    planes = 1 + len(t.state)
    c = _compile(
        t._push_fn, one_chip, _sds((ROWS + 1,)),
        {k: _sds((ROWS + 1,)) for k in t.state}, _sds((n_ids,), jnp.int32),
        _sds((n_ids, 1)), _sds((), jnp.int32),
    )
    text = c.as_text()
    assert _plane_ops(c, ROWS + 1) == {"fusion": 2 * planes}
    assert len(re.findall(r" while\(", text)) == 1
    assert "indices_are_sorted=true" not in text
    assert re.search(r"s32\[\]\S* parameter\(4\)", text)  # n, traced
    mem = c.memory_analysis()
    assert mem.temp_size_in_bytes < 1 << 20
    assert mem.alias_size_in_bytes >= planes * 4 * ROWS  # donated, in place


def test_rank2_apply_compiles_to_the_parent_s_program(one_chip):
    """``dlrm_emb.skew.x4``'s shard and leg: told the count or not, a rank-2
    plane's apply is one optimised program, operation for operation."""
    rows, dim, leg = 11735464, 128, 16384
    t = KVTable(
        TableConfig(
            name="e", rows=8, dim=dim,
            optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.1),
        )
    )
    shapes = (
        _sds((rows + 1, dim)), {k: _sds((rows + 1, dim)) for k in t.state},
        _sds((leg,), jnp.int32), _sds((leg, dim)),
    )
    plain = _program(_compile(t._push_fn, one_chip, *shapes))
    told = _program(_compile(t._push_fn, one_chip, *shapes, _sds((), jnp.int32)))
    assert told == plain and " while(" not in plain


def test_column_planes_compile_to_passes_over_the_plane(one_chip):
    """The control: the same gather on an ``[N, 1]`` plane relayouts it, and
    the checks above see it.  If this compiler ever stops doing so, the flat
    form is no longer needed for the TPU's sake."""
    col = jax.jit(scatter.gather_rows_xla)
    c = _compile(col, one_chip, _sds((ROWS + 1, 1)), _sds((N,), jnp.int32))
    assert _plane_ops(c, ROWS + 1) != {}
    assert c.memory_analysis().temp_size_in_bytes >= 4 * ROWS


# -- blocked attention -----------------------------------------------------------
def _attention(window, by_sequence=False):
    """Value and gradients of blocked attention, jitted; ``by_sequence``: as
    a body runs it, a ``lax.map`` over the sequences of a checkpointed
    layer (``models/moe.py::by_sequence``)."""
    def attn(q, k, v, qs=None, ks=None):
        return blocked_causal_attention(
            q, k, v, block=256, band=8, scale=0.125, q_shared=qs, k_shared=ks,
            window=window,
        )

    def f(*args):
        if not by_sequence:
            return jnp.sum(attn(*args))
        one = jax.checkpoint(lambda *row: attn(*(a[None] for a in row))[0])
        return jnp.sum(jax.lax.map(lambda row: one(*row), args))

    return lambda n: jax.jit(jax.value_and_grad(f, argnums=tuple(range(n))))


def _program(compiled):
    """The compiled program's operations, without where in the source each
    came from (the tables of files and stack frames, every ``metadata``)."""
    text = compiled.as_text()
    ops = text[text.index("\n\n%"):] if "\n\n%" in text else text
    return re.sub(r", metadata=\{[^}]*\}", "", ops)


@pytest.mark.parametrize("B,by_sequence,limit", [
    (1, False, 1 << 29), (2, True, 1 << 31),
], ids=["bare", "by_sequence"])
def test_windowed_attention_at_a_window_layer_s_published_shapes(
    one_chip, B, by_sequence, limit
):
    """``[B, 8192, 64, 128]`` queries over 8 key heads, window 512, forward
    and the written-out backward: the chip's compiler takes it, and what it
    keeps beside the arguments and the results is a few blocks of scores
    (``[8, 8, 256, 768]`` float32 is 48 MiB) and, by sequence, a sequence's
    residuals: 0.38 and 1.44 GiB.  Run as a body runs it, the loop without
    ``blocked_attention._tied`` keeps its masks and its empty shared part's
    scores stacked over the 32 blocks, 3.13 GiB, which the limit refuses."""
    S = 8192
    shapes = (_sds((B, S, 64, 128)), _sds((B, S, 8, 128)), _sds((B, S, 8, 128)))
    c = _compile(_attention(512, by_sequence)(3), one_chip, *shapes)
    assert c.memory_analysis().temp_size_in_bytes < limit
    # no operation's result is a [S, S] tensor of scores or a stack of blocks
    sizes = [
        int(np.prod([int(d) for d in dims.split(",")]))
        for dims in re.findall(r"= (?:f32|pred)\[([\d,]+)\]", c.as_text())
    ]
    assert max(sizes) <= B * 64 * S * 128


@pytest.mark.parametrize("cell,H,Hkv,D,Dv,Dr", [
    ("kimi_linear_a3b.pretrain8k", 32, 32, 128, 128, 64),
    ("lfm2_8b_a1b.pretrain8k", 32, 8, 64, 64, 0),
])
def test_a_window_of_every_key_compiles_to_the_causal_program(one_chip, cell, H, Hkv, D, Dv, Dr):
    """At the attention shapes of the two cells that had this kernel before
    windows existed: no window and a window of exactly the sequence compile
    to the same program for the described chip, operation for operation
    (``tests/test_blocked_window.py`` holds that program's operations to
    what they were before PR 35, and a wider window to the same jaxpr)."""
    S = 8192
    shapes = [_sds((1, S, H, D)), _sds((1, S, Hkv, D)), _sds((1, S, Hkv, Dv))]
    if Dr:
        shapes += [_sds((1, S, H, Dr)), _sds((1, S, Dr))]
    causal, whole = (
        _program(_compile(_attention(w)(len(shapes)), one_chip, *shapes))
        for w in (None, S)
    )
    assert causal == whole and "while" in causal
