"""The table programs of a dim-1 table, compiled for a described v5e chip.

No chip is attached and nothing runs: the TPU's compiler is installed here
and compiles for a topology that is described (``on-chip-measurement`` guide,
section 2).  What it shows is what a CPU run cannot: whether the compiled
program passes over a whole plane.  With ``[N, 1]`` planes every pull held one
such pass and every apply eight, 85 % of ``criteo_lr.skew``'s device time
(``PERF.md`` section 6, PR 26); with flat planes the only operations over a
plane are the gather and scatter fusions and the in-place trash reset.

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
    """``{opcode: count}`` of the ENTRY computation's operations whose result
    is a float32 array of ``elements`` elements, parameters and bitcasts
    aside (they move nothing)."""
    text = compiled.as_text()
    entry = text[text.index("ENTRY") :]
    entry = entry[: entry.index("\n}")]
    ops = {}
    for m in re.finditer(r"= f32\[([\d,]+)\]\S* ([\w\-]+)\(", entry):
        size = int(np.prod([int(d) for d in m.group(1).split(",")]))
        if size == elements and m.group(2) not in ("parameter", "bitcast"):
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


def test_column_planes_compile_to_passes_over_the_plane(one_chip):
    """The control: the same gather on an ``[N, 1]`` plane relayouts it, and
    the checks above see it.  If this compiler ever stops doing so, the flat
    form is no longer needed for the TPU's sake."""
    col = jax.jit(scatter.gather_rows_xla)
    c = _compile(col, one_chip, _sds((ROWS + 1, 1)), _sds((N,), jnp.int32))
    assert _plane_ops(c, ROWS + 1) != {}
    assert c.memory_analysis().temp_size_in_bytes >= 4 * ROWS
