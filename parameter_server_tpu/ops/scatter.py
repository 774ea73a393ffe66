"""Device-side sparse table primitives: row gather / scatter-add.

These are the TPU equivalents of the reference server's hot loops
(``src/parameter/kv_vector.h`` :: ``ParallelOrderedMatch`` merge + scatter-ADD
into the value array, and the Pull-side row gather [U — reference mount empty,
public layout]).  The host has already localized global keys to dense row ids
(:mod:`parameter_server_tpu.utils.keys`), so the device only sees fixed-shape
``int32`` row-id vectors.

**Plane shapes.**  A table plane (the value, or one optimizer-state array) is
``[rows + 1, dim]`` with the trash row last, EXCEPT that **a rank-1 plane is
a dim-1 table**: ``[rows + 1]``.  On the TPU an ``[N, 1]`` array lives in the
``T(1,128)`` layout while gather and scatter work on the flat ``T(1024)``
one, so every program over an ``[N, 1]`` plane began and ended with passes
over the whole plane (``PERF.md`` section 6, PR 26); a flat plane is in the
layout the gather and the scatter work in.  The XLA entry points below take
either and read the form from the plane's rank; rows cross every interface
as ``[n, dim]`` (``[n, 1]`` for a flat plane), so the only reshapes are of
``n``-row operands, never of a plane.  The Pallas kernels need ``dim == 128``
or ``dim % 1024 == 0`` and refuse a flat plane like any other dim-1 table.

**A flat plane's apply** (:func:`_apply_rows_xla`; ``PERF.md`` section 6,
PR 36).  On the TPU the write-back into a flat plane is a serial loop over
the ids, pads included: 100 ns an id a plane in 2 GiB, some ten times the
gather's cost.  A caller that says how many ids are real (``n``, a traced
scalar: ids ``[n:]`` point at the trash row, which the table resets) has the
bucket walked ``_FLAT_CHUNK`` ids a turn, both gathers, the row function and
both write-backs of a chunk in one turn of one ``while`` with the planes
carried in place, for ``ceil(n / chunk)`` turns: at most ``chunk - 1`` pads
are visited, and every real row's arithmetic is the whole bucket's, bit for
bit.  It is read from what the function sees (the plane's rank, the
argument): a rank-2 plane's program is the same with and without it.
**Nothing tells the compiler that the ids are in order**, though a server's
are: on a v5e ``indices_are_sorted=True`` selects a scatter that stages the
plane through fast memory window by window, 7 ms a 2 GiB plane whatever the
ids, against the loop's 2-3 ms for a leg of 20-33 k (section 6, PR 36).

Two implementations:

- **XLA** (default): ``jnp.take`` / ``.at[].add``.  Differentiable, handles
  duplicate ids, runs everywhere.  XLA lowers these to native gather/scatter
  which is adequate for small-dim tables (e.g. LR weights).
- **Pallas** (``impl="pallas"``): a double-buffer-free DMA kernel that copies
  ``block_rows`` table rows HBM→VMEM per grid step via scalar-prefetched ids,
  adds, and writes back.  The table never materializes in VMEM, so capacity is
  bounded by HBM only.  Requires: unique row ids (pre-combined duplicates —
  exactly what :func:`localize_batch` + :func:`segment_combine` produce),
  ``dim % 128 == 0``, float32.  Padding rows must carry zero values and may
  all point at the shared trash row (writes become idempotent ``+0``).

The duplicate-key pre-combine that the reference does inside
``ParallelOrderedMatch`` happens here as a device-side ``segment_sum``
(:func:`segment_combine`) keyed by the localizer's inverse indices.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, Literal, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Impl = Literal["auto", "xla", "pallas"]


#: the aliased-output DMA kernels write HBM behind XLA's back
_SIDE_EFFECTS = pltpu.CompilerParams(has_side_effects=True)

#: DMA semaphores one kernel may hold.  The chip's semaphore memory is 2 KiB
#: for the whole program (4 B each); measured on TPU v5 lite, jax 0.9.0 /
#: libtpu 0.0.34 (PR 21): the fused apply with Adam's four planes at 32 rows
#: a block asked for 512 and Mosaic refused ("Used 2.1K of 2.0K sflag").
#: Half the memory leaves room for the rest of the jitted step.
_MAX_DMA_SEMAPHORES = 256

#: ids a turn of a flat plane's chunked apply handles (:func:`_apply_rows_xla`).
#: On a v5e a turn costs 2 us beside 0.2 us an id visited (``PERF.md`` section
#: 6, PR 36): at 1,024 a leg's mean 512 pads and its 20-33 turns weigh the same
_FLAT_CHUNK = 1024

#: row-wise update rule: (value_rows, state_rows, grad_rows) ->
#: (new_value_rows, new_state_rows).  ServerOptimizer.apply satisfies this
#: contract directly — pure, elementwise over [n, dim] blocks — which is what
#: lets :func:`apply_rows` inline it into a single gather→apply→scatter pass.
RowFn = Callable[
    [jax.Array, Dict[str, jax.Array], jax.Array],
    Tuple[jax.Array, Dict[str, jax.Array]],
]


def segment_combine(values: jax.Array, inverse: jax.Array, num_rows: int) -> jax.Array:
    """Sum per-position values into their unique-key rows.

    ``inverse`` is the position->unique-row map from ``localize_batch``;
    ``num_rows`` the (bucket-padded) unique count.  Rows past the true unique
    count receive zero — exactly the padding contract the pallas scatter path
    requires.
    """
    return jax.ops.segment_sum(values, inverse, num_segments=num_rows)


# ---------------------------------------------------------------------------
# XLA implementations
# ---------------------------------------------------------------------------


def gather_rows_xla(table: jax.Array, ids: jax.Array) -> jax.Array:
    """``[n, dim]`` rows of ``table`` (``[n, 1]`` of a flat plane)."""
    rows = jnp.take(table, ids, axis=0)
    return rows[:, None] if table.ndim == 1 else rows


def _rows_for(table: jax.Array, ids: jax.Array, rows: jax.Array) -> jax.Array:
    """``[n, dim]`` rows in the form ``table``'s scatter takes: ``[n]`` for a
    flat plane (a reshape of ``n`` elements, refused unless ``dim == 1``)."""
    return rows.reshape(ids.shape) if table.ndim == 1 else rows


def scatter_add_rows_xla(table: jax.Array, ids: jax.Array, rows: jax.Array) -> jax.Array:
    return table.at[ids].add(_rows_for(table, ids, rows))


def scatter_update_rows_xla(table: jax.Array, ids: jax.Array, rows: jax.Array) -> jax.Array:
    return table.at[ids].set(_rows_for(table, ids, rows))


# ---------------------------------------------------------------------------
# Pallas implementations
# ---------------------------------------------------------------------------


def _pick_block_rows(
    n: int, block_rows: int | None, sems_per_row: int = 1
) -> int:
    """Largest supported block dividing ``n`` (or validate an explicit one)
    whose ``sems_per_row`` DMA semaphores a row fit the chip's budget."""
    if block_rows is not None:
        if n % block_rows != 0:
            raise ValueError(
                f"pallas path requires len(ids) % block_rows == 0, got "
                f"{n} % {block_rows}"
            )
        if block_rows * sems_per_row > _MAX_DMA_SEMAPHORES:
            raise ValueError(
                f"block_rows={block_rows} needs {block_rows * sems_per_row} "
                f"DMA semaphores; the budget is {_MAX_DMA_SEMAPHORES}"
            )
        return block_rows
    for b in (32, 16, 8):
        if n % b == 0 and b * sems_per_row <= _MAX_DMA_SEMAPHORES:
            return b
    raise ValueError(
        f"pallas path requires len(ids) divisible by 8, got {n}; "
        "bucket-pad ids (utils.keys.localize_batch) or use impl='xla'"
    )


def _chunks(dim: int) -> int:
    """Row chunking factor: logical rows are DMAed as ``c`` physical
    ``(., 128)`` rows of the ``(rows*c, 128)`` view.

    Mosaic (this toolchain) only slices HBM memrefs along dim 0 in
    tile-aligned units: a squeezed single-row slice works when the row is
    exactly one 128-lane tile (dim == 128 -> c == 1), and a ``(c, 128)``
    slice works when c is a multiple of the 8-sublane tiling (dim % 1024
    == 0).  Anything between is refused here.  On jax 0.9.0 / libtpu
    0.0.34 (PR 21 chip run) gather and scatter-set also compiled, and were
    exact, at c = 2, 3, 4 — in 4-5 s against 0.2 s at c = 8; scatter-add
    and the fused apply were not tried there, so the rule stands until
    ROADMAP S8 settles it.
    """
    if dim == 128:
        return 1
    c = dim // 128
    if dim % 128 == 0 and c % 8 == 0:
        return c
    raise ValueError(
        f"pallas path requires dim == 128 or dim % 1024 == 0, got {dim}; "
        "use impl='xla'"
    )


def _check_pallas_args(table: jax.Array, ids: jax.Array) -> None:
    if table.ndim not in (1, 2) or table.dtype != jnp.float32:
        raise ValueError(
            f"pallas path requires a 2-D float32 table, got "
            f"{table.shape} {table.dtype}; use impl='xla'"
        )
    # a flat plane is a dim-1 table: refused by its dim like any other
    _chunks(1 if table.ndim == 1 else table.shape[1])


def _copy_rows(src_ref, src_row, dst_ref, dst_row, sem, c):
    """Async copy of one logical row (c physical 128-lane rows)."""
    if c == 1:
        return pltpu.make_async_copy(
            src_ref.at[src_row], dst_ref.at[dst_row], sem
        )
    return pltpu.make_async_copy(
        src_ref.at[pl.ds(src_row * c, c)],
        dst_ref.at[pl.ds(dst_row * c, c)],
        sem,
    )


def _gather_kernel(ids_ref, table_ref, out_ref, sems, *, block, c):
    i = pl.program_id(0)
    for k in range(block):
        row = ids_ref[i * block + k]
        _copy_rows(table_ref, row, out_ref, k, sems.at[k], c).start()
    for k in range(block):
        row = ids_ref[i * block + k]
        _copy_rows(table_ref, row, out_ref, k, sems.at[k], c).wait()


def _pallas_gather(
    table: jax.Array,
    ids: jax.Array,
    *,
    interpret: bool,
    block_rows: int | None = None,
) -> jax.Array:
    _check_pallas_args(table, ids)
    n = ids.shape[0]
    block = _pick_block_rows(n, block_rows)
    dim = table.shape[1]
    c = _chunks(dim)
    tview = table.reshape(-1, 128) if c > 1 else table
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // block,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(
            (block * c, 128 if c > 1 else dim),
            lambda i, ids: (i, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[pltpu.SemaphoreType.DMA((block,))],
    )
    out = pl.pallas_call(
        functools.partial(_gather_kernel, block=block, c=c),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (n * c, 128) if c > 1 else (n, dim), table.dtype
        ),
        interpret=interpret,
    )(ids, tview)
    return out.reshape(n, dim) if c > 1 else out


def _scatter_add_kernel(ids_ref, vals_ref, table_ref, out_ref, scratch,
                        rsems, wsems, *, block, c):
    """Double-buffered read-modify-write scatter-add.

    out_ref aliases table_ref (donated input).  Two scratch slots pipeline
    the row round-trips: while block *i* adds and writes back from slot
    ``i%2``, block *i+1*'s rows are already streaming HBM->VMEM into the
    other slot, hiding the gather latency behind the add+write of the
    previous block (the "double-buffering" VERDICT r2 #4 asked for).

    Safety: row ids are unique (callers guarantee; duplicates are
    pre-combined), so block *i*'s write-backs and block *i+1*'s prefetches
    never touch the same row — except the shared trash row, which holds
    zeros and receives +0 writes (bytes unchanged), making the overlap
    benign there too.
    """
    i = pl.program_id(0)
    nb = pl.num_programs(0)
    slot = i % 2
    nxt = (i + 1) % 2

    @pl.when(i == 0)
    def _first_reads():
        for k in range(block):
            row = ids_ref[k]
            _copy_rows(out_ref, row, scratch.at[0], k, rsems.at[0, k], c).start()

    # Slot reuse: the write-backs issued at step i-1 came FROM scratch[nxt];
    # they must land before new rows stream INTO that slot.
    @pl.when(i > 0)
    def _drain_prev_writes():
        for k in range(block):
            row = ids_ref[(i - 1) * block + k]
            _copy_rows(
                scratch.at[nxt], k, out_ref, row, wsems.at[nxt, k], c
            ).wait()

    @pl.when(i + 1 < nb)
    def _prefetch_next():
        for k in range(block):
            row = ids_ref[(i + 1) * block + k]
            _copy_rows(
                out_ref, row, scratch.at[nxt], k, rsems.at[nxt, k], c
            ).start()

    for k in range(block):
        row = ids_ref[i * block + k]
        _copy_rows(out_ref, row, scratch.at[slot], k, rsems.at[slot, k], c).wait()
    scratch[slot] = scratch[slot] + vals_ref[...]
    for k in range(block):
        row = ids_ref[i * block + k]
        _copy_rows(scratch.at[slot], k, out_ref, row, wsems.at[slot, k], c).start()

    @pl.when(i + 1 == nb)
    def _drain_last_writes():
        for k in range(block):
            row = ids_ref[i * block + k]
            _copy_rows(
                scratch.at[slot], k, out_ref, row, wsems.at[slot, k], c
            ).wait()


def _pallas_scatter_add(
    table: jax.Array,
    ids: jax.Array,
    rows: jax.Array,
    *,
    interpret: bool,
    block_rows: int | None = None,
) -> jax.Array:
    _check_pallas_args(table, ids)
    n = ids.shape[0]
    block = _pick_block_rows(n, block_rows, sems_per_row=4)
    dim = table.shape[1]
    c = _chunks(dim)
    tview = table.reshape(-1, 128) if c > 1 else table
    rview = rows.reshape(-1, 128) if c > 1 else rows
    vdim = 128 if c > 1 else dim
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec(
                (block * c, vdim), lambda i, ids: (i, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, block * c, vdim), table.dtype),
            pltpu.SemaphoreType.DMA((2, block)),
            pltpu.SemaphoreType.DMA((2, block)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_scatter_add_kernel, block=block, c=c),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(tview.shape, table.dtype),
        input_output_aliases={2: 0},  # table (arg idx incl. scalar prefetch) -> out
        interpret=interpret,
        compiler_params=_SIDE_EFFECTS,
    )(ids, rview, tview)
    return out.reshape(table.shape) if c > 1 else out


def _scatter_set_kernel(ids_ref, vals_ref, table_ref, out_ref, sems, *, block, c):
    """Write-only row update (Push apply writes new rows; no RMW needed).

    Duplicate ids are tolerated ONLY when they carry identical rows (the
    padded-trash-row case): concurrent same-bytes writes are idempotent.
    """
    i = pl.program_id(0)
    for k in range(block):
        row = ids_ref[i * block + k]
        _copy_rows(vals_ref, k, out_ref, row, sems.at[k], c).start()
    for k in range(block):
        row = ids_ref[i * block + k]
        _copy_rows(vals_ref, k, out_ref, row, sems.at[k], c).wait()


def _pallas_scatter_set(
    table: jax.Array,
    ids: jax.Array,
    rows: jax.Array,
    *,
    interpret: bool,
    block_rows: int | None = None,
) -> jax.Array:
    _check_pallas_args(table, ids)
    n = ids.shape[0]
    block = _pick_block_rows(n, block_rows)
    dim = table.shape[1]
    c = _chunks(dim)
    tview = table.reshape(-1, 128) if c > 1 else table
    rview = rows.reshape(-1, 128) if c > 1 else rows
    vdim = 128 if c > 1 else dim
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec(
                (block * c, vdim), lambda i, ids: (i, 0), memory_space=pltpu.VMEM
            ),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA((block,))],
    )
    out = pl.pallas_call(
        functools.partial(_scatter_set_kernel, block=block, c=c),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(tview.shape, table.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
        compiler_params=_SIDE_EFFECTS,
    )(ids, rview, tview)
    return out.reshape(table.shape) if c > 1 else out


def _apply_rows_xla(
    value: jax.Array,
    state: Dict[str, jax.Array],
    ids: jax.Array,
    grads: jax.Array,
    row_fn: RowFn,
    n: jax.Array | None = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Gather → row_fn → scatter-update, expressed as one XLA graph.

    Op-for-op identical to the legacy three-pass body of
    ``KVTable._push_impl`` (same gathers, same elementwise update, same
    ``.at[].set`` write-backs), so switching a table between fused and
    three-pass mode is bitwise-neutral on the XLA backends.

    A flat plane given the count ``n`` of its real ids (``int32``, traced:
    one program a bucket) is applied a chunk of ids a turn, as many turns
    as hold ``n`` ids (module docstring).
    """
    chunk = min(_FLAT_CHUNK, ids.shape[0])
    if n is not None and value.ndim == 1 and ids.shape[0] % chunk == 0:
        def turn(i, planes):
            at = i * chunk
            return _apply_rows_xla(
                *planes,
                jax.lax.dynamic_slice_in_dim(ids, at, chunk),
                jax.lax.dynamic_slice_in_dim(grads, at, chunk),
                row_fn,
            )

        turns = jax.lax.div(n + (chunk - 1), jnp.int32(chunk))
        return jax.lax.fori_loop(0, turns, turn, (value, state))
    v_rows = gather_rows_xla(value, ids)
    s_rows = {k: gather_rows_xla(v, ids) for k, v in state.items()}
    new_v, new_s = row_fn(v_rows, s_rows, grads)
    value = scatter_update_rows_xla(value, ids, new_v)
    state = {
        k: scatter_update_rows_xla(state[k], ids, new_s[k]) for k in state
    }
    return value, state


def _apply_kernel(ids_ref, grads_ref, *refs, block, c, names, row_fn, dim):
    """Single-pass gather → optimizer step → scatter over value + S states.

    ``refs`` layout (S = len(names)): ``1 + S`` table inputs (HBM, aliased
    to the outputs, so all DMA goes through the output refs), ``1 + S``
    output refs, ``1 + S`` VMEM scratch buffers (2 slots each), then the
    read/write DMA semaphore arrays (shape ``(2, 1 + S, block)``).

    Double-buffered exactly like ``_scatter_add_kernel``: block *i*'s
    compute + write-back overlaps block *i+1*'s row prefetch.  Unique row
    ids keep the overlap race-free for real rows.  The shared trash row is
    the one exception — unlike scatter-add's ``+0`` (bytes unchanged), a
    state rule may rewrite trash bytes (e.g. Adam's per-row ``t``), so
    concurrent trash prefetch/write-back can race.  That nondeterminism is
    confined to the trash row, which the table layer re-zeros immediately
    after every apply — the visible table state stays deterministic.
    """
    ns = 1 + len(names)
    tabs = refs[ns : 2 * ns]  # output refs (alias the input tables)
    scratch = refs[2 * ns : 3 * ns]
    rsems, wsems = refs[3 * ns], refs[3 * ns + 1]
    i = pl.program_id(0)
    nb = pl.num_programs(0)
    slot = i % 2
    nxt = (i + 1) % 2

    def rows(tab_j, row, scr_j, k, sems, slot_k):
        return _copy_rows(tabs[tab_j], row, scratch[scr_j].at[slot_k], k,
                          sems.at[slot_k, tab_j, k], c)

    def back(tab_j, row, scr_j, k, sems, slot_k):
        return _copy_rows(scratch[scr_j].at[slot_k], k, tabs[tab_j], row,
                          sems.at[slot_k, tab_j, k], c)

    @pl.when(i == 0)
    def _first_reads():
        for k in range(block):
            row = ids_ref[k]
            for j in range(ns):
                rows(j, row, j, k, rsems, 0).start()

    @pl.when(i > 0)
    def _drain_prev_writes():
        for k in range(block):
            row = ids_ref[(i - 1) * block + k]
            for j in range(ns):
                back(j, row, j, k, wsems, nxt).wait()

    @pl.when(i + 1 < nb)
    def _prefetch_next():
        for k in range(block):
            row = ids_ref[(i + 1) * block + k]
            for j in range(ns):
                rows(j, row, j, k, rsems, nxt).start()

    for k in range(block):
        row = ids_ref[i * block + k]
        for j in range(ns):
            rows(j, row, j, k, rsems, slot).wait()
    v = scratch[0][slot].reshape(block, dim)
    s = {
        name: scratch[1 + j][slot].reshape(block, dim)
        for j, name in enumerate(names)
    }
    g = grads_ref[...].reshape(block, dim)
    new_v, new_s = row_fn(v, s, g)
    scratch[0][slot] = new_v.reshape(scratch[0].shape[1:])
    for j, name in enumerate(names):
        scratch[1 + j][slot] = new_s[name].reshape(scratch[1 + j].shape[1:])
    for k in range(block):
        row = ids_ref[i * block + k]
        for j in range(ns):
            back(j, row, j, k, wsems, slot).start()

    @pl.when(i + 1 == nb)
    def _drain_last_writes():
        for k in range(block):
            row = ids_ref[i * block + k]
            for j in range(ns):
                back(j, row, j, k, wsems, slot).wait()


def _pallas_apply(
    value: jax.Array,
    state: Dict[str, jax.Array],
    ids: jax.Array,
    grads: jax.Array,
    row_fn: RowFn,
    *,
    interpret: bool,
    block_rows: int | None = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    _check_pallas_args(value, ids)
    n = ids.shape[0]
    names = tuple(sorted(state))
    ns = 1 + len(names)
    # read + write semaphores, two slots, one per plane, for every row
    block = _pick_block_rows(n, block_rows, sems_per_row=4 * ns)
    dim = value.shape[1]
    c = _chunks(dim)
    vdim = 128 if c > 1 else dim
    views = [value] + [state[k] for k in names]
    if c > 1:
        views = [t.reshape(-1, 128) for t in views]
        grads = grads.reshape(-1, 128)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec(
                (block * c, vdim), lambda i, ids: (i, 0),
                memory_space=pltpu.VMEM,
            ),
        ]
        + [pl.BlockSpec(memory_space=pl.ANY)] * ns,
        out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * ns,
        scratch_shapes=[pltpu.VMEM((2, block * c, vdim), value.dtype)] * ns
        + [
            pltpu.SemaphoreType.DMA((2, ns, block)),
            pltpu.SemaphoreType.DMA((2, ns, block)),
        ],
    )
    outs = pl.pallas_call(
        functools.partial(
            _apply_kernel, block=block, c=c, names=names, row_fn=row_fn,
            dim=dim,
        ),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype) for t in views],
        # table j rides at arg 2 + j (after scalar-prefetch ids and grads)
        input_output_aliases={2 + j: j for j in range(ns)},
        interpret=interpret,
        compiler_params=_SIDE_EFFECTS,
    )(ids, grads, *views)
    if c > 1:
        outs = [o.reshape(value.shape) for o in outs]
    return outs[0], {k: outs[1 + j] for j, k in enumerate(names)}


# ---------------------------------------------------------------------------
# Public dispatchers
# ---------------------------------------------------------------------------


# "auto" resolves to XLA.  The one reading taken on the chip (a v5e, PR 36:
# a 2 GiB ``[2^22 + 1, 128]`` plane, 32,768 sorted rows, each call alone) has
# the Pallas fused apply at 2.39 ms against XLA's 5.24 and the scatter-set at
# 0.49 against 2.40, the gather at 0.64 against 0.34; no cell shows it (the
# chips of ``dlrm_emb.skew.x4`` idle 92 %), so selecting by shape is ROADMAP
# D5's.  Until then the Pallas kernels stay selectable by flag and are kept
# compiling by ``chip_smoke.py``.


def gather_rows(
    table: jax.Array,
    ids: jax.Array,
    *,
    impl: Impl = "auto",
    interpret: bool = False,
    block_rows: int | None = None,
) -> jax.Array:
    """Gather ``table[ids]`` (Pull hot loop #2 of the reference server)."""
    if impl != "pallas":
        return gather_rows_xla(table, ids)
    return _pallas_gather(table, ids, interpret=interpret, block_rows=block_rows)


def scatter_add_rows(
    table: jax.Array,
    ids: jax.Array,
    rows: jax.Array,
    *,
    impl: Impl = "auto",
    interpret: bool = False,
    block_rows: int | None = None,
) -> jax.Array:
    """Scatter-add rows into the table (Push hot loop #1 of the reference).

    The pallas path requires unique ``ids`` (pre-combined duplicates); the XLA
    path accepts duplicates.
    """
    if impl != "pallas":
        return scatter_add_rows_xla(table, ids, rows)
    return _pallas_scatter_add(
        table, ids, rows, interpret=interpret, block_rows=block_rows
    )


def scatter_update_rows(
    table: jax.Array,
    ids: jax.Array,
    rows: jax.Array,
    *,
    impl: Impl = "auto",
    interpret: bool = False,
    block_rows: int | None = None,
) -> jax.Array:
    """Overwrite table rows at unique ``ids`` (the Push apply write-back).

    The pallas path is write-only DMA (no read-modify-write); duplicate ids
    are only safe when they carry identical rows (padded trash-row rows do).
    """
    if impl != "pallas":
        return scatter_update_rows_xla(table, ids, rows)
    return _pallas_scatter_set(
        table, ids, rows, interpret=interpret, block_rows=block_rows
    )


def apply_rows(
    value: jax.Array,
    state: Dict[str, jax.Array],
    ids: jax.Array,
    grads: jax.Array,
    row_fn: RowFn,
    *,
    impl: Impl = "auto",
    interpret: bool = False,
    block_rows: int | None = None,
    n: jax.Array | None = None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Fused push apply: gather → ``row_fn`` → scatter-update in one pass.

    Replaces the three kernel groups of the legacy push body (``1 + S``
    gathers, the update, ``1 + S`` scatter-sets) with a single traversal of
    the touched rows.  ``ids`` must be unique real rows (duplicates
    pre-combined; pads all point at the shared trash row, which the caller
    re-zeros).  The pallas path DMAs value + state rows through VMEM once,
    runs ``row_fn`` on the resident block, and writes straight back —
    double-buffered, tables never materialize in VMEM.  ``n``: how many of
    ``ids`` are real, which the XLA path of a flat plane reads (module
    docstring).
    """
    if impl != "pallas":
        return _apply_rows_xla(value, state, ids, grads, row_fn, n)
    return _pallas_apply(
        value, state, ids, grads, row_fn,
        interpret=interpret, block_rows=block_rows,
    )


@functools.partial(jax.jit, static_argnames=("num_rows", "unique_ids", "impl"))
def combine_and_scatter_add(
    table: jax.Array,
    ids: jax.Array,
    inverse: jax.Array,
    values: jax.Array,
    num_rows: int,
    unique_ids: bool = False,
    impl: Impl = "auto",
) -> jax.Array:
    """Fused duplicate pre-combine + scatter-add (the full Push apply).

    ``inverse`` pre-combines duplicates *per unique key*, but distinct keys
    may still share a row slot once the Localizer overflows (feature
    hashing), so the pallas kernel is only legal with ``unique_ids=True``
    (e.g. ``not localizer.overflowed``) AND an explicit ``impl="pallas"`` —
    by measurement "auto" is XLA (see the dispatcher note above).
    """
    if impl == "pallas" and not unique_ids:
        raise ValueError("impl='pallas' requires unique_ids=True (pre-combined)")
    combined = segment_combine(values, inverse, num_rows)
    return scatter_add_rows(table, ids, combined, impl=impl)
