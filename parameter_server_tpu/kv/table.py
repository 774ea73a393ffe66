"""KVTable: a parameter table resident in device memory.

The TPU inversion of the reference server's storage (SURVEY.md §7): where the
reference keeps a sorted key array + value array per channel and merges pushes
with ``ParallelOrderedMatch`` (``src/parameter/kv_vector.h`` [U]), here the
table is a fixed ``jax.Array`` in HBM (last row = trash row for padding), the
host supplies dense unique row ids, and push/pull are jit-compiled steps:

- ``push``: segment-combine duplicate positions -> gather value+state rows ->
  optimizer ``apply`` -> scatter rows back.  Buffers are donated, so the
  update is in-place in HBM.
- ``pull``: gather rows -> ``pull_weights`` (lazy FTRL weights etc.).

Shapes are bucket-padded by the host (``utils.keys``), so each table compiles
one kernel per (bucket, batch) shape pair.

**Plane shapes.**  ``value`` and every ``state[k]`` are ``[rows + 1, dim]`` on
the device, except that a table of dim 1 holds flat ``[rows + 1]`` planes:
the layout its gather and scatter work in, so that no program of the table
passes over a whole plane (``ops/scatter.py``: a rank-1 plane is a dim-1
table).  The form is read from ``cfg.dim`` here and from the plane's rank in
the kernels; nothing selects it.  Rows cross every method as ``[n, dim]``
(``pull`` of a dim-1 table returns ``[n, 1]``), and every host form
(:meth:`host_planes`, :meth:`weights`, :meth:`set_value`,
:meth:`install_rows`, :meth:`resize`, checkpoints, the server's hand-over) is
``[rows(+1), dim]`` NumPy whatever the dim: the conversion is a host
``reshape`` and lives in this class only.

**A flat plane's apply.**  ``push``, ``push_batch`` and ``push_combined`` take
``n``: how many of the bucket's ids are real, the rest being pads at the
trash row.  A dim-1 table with the fused apply then walks the leg a chunk of
ids a turn and stops after the turn that holds the last real id
(``ops/scatter.py``, "A flat plane's apply"): every real row ends as the
whole-bucket apply leaves it, bit for bit, and the trash row is reset as
ever.  ``n`` is a traced operand, so there is one program a bucket.  Any
other table does not hand it to its jitted functions: its programs are the
ones it had, whatever the caller says.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_tpu.config import TableConfig
from parameter_server_tpu.kv.optim import ServerOptimizer, make_optimizer
from parameter_server_tpu.ops import scatter


class KVTable:
    """One table (or one row-range shard of a table) on the local device."""

    def __init__(
        self,
        cfg: TableConfig,
        *,
        rows: Optional[int] = None,
        seed: int = 0,
        device: Optional[jax.Device] = None,
        interpret: bool = False,
    ):
        """``device``: the chip this shard lives on.  Value and optimizer
        state are allocated there and COMMITTED to it, so every jitted step
        runs there and installs (:meth:`resize`, :meth:`set_value`) land
        there too.  ``None`` keeps the arrays uncommitted on the default
        device (single-table trainers that re-shard them under a mesh).

        ``interpret``: run the Pallas kernels in the interpreter — what a CPU
        test of ``scatter_impl="pallas"`` asks for explicitly.  It is never
        inferred: a Pallas table on a non-TPU backend without it is an error,
        so a chip run cannot silently become an interpreter run.
        """
        self.cfg = cfg
        self.device = device
        #: actual row count of this shard (cfg.rows is the global table size);
        #: one extra trash row is appended for padded ids.
        self.rows = cfg.rows if rows is None else rows
        self.dim = cfg.dim
        dtype = jnp.dtype(cfg.dtype)
        self.optimizer: ServerOptimizer = make_optimizer(cfg.optimizer)
        shape = self._plane_shape(self.rows)
        with jax.default_device(device):  # allocate in place, then commit
            if cfg.init_scale > 0.0:
                # the draw depends on the element count alone, so a flat
                # plane holds the numbers its [rows + 1, 1] form held
                key = jax.random.PRNGKey(seed)
                value = jax.random.normal(key, shape, dtype) * cfg.init_scale
                value = value.at[self.rows].set(0.0)
            else:
                value = jnp.zeros(shape, dtype)
            state = {
                name: jnp.full(shape, fill, dtype)
                for name, fill in self.optimizer.state_shapes().items()
            }
        self.value: jax.Array = self._place(value)
        self.state: Dict[str, jax.Array] = {
            k: self._place(v) for k, v in state.items()
        }
        #: hot-path kernel selection: "pallas" routes the gather + write-back
        #: through ops/scatter's DMA kernels; "xla"/"auto" as documented on
        #: the flag.
        if cfg.scatter_impl not in ("auto", "xla", "pallas"):
            raise ValueError(
                f"scatter_impl must be auto|xla|pallas, got {cfg.scatter_impl!r}"
            )
        self.scatter_impl = cfg.scatter_impl
        self.fused_apply = cfg.fused_apply
        if (
            cfg.scatter_impl == "pallas"
            and not interpret
            and jax.default_backend() != "tpu"
        ):
            raise ValueError(
                "scatter_impl='pallas' compiles for the TPU; the backend is "
                f"{jax.default_backend()!r}.  Pass interpret=True to run the "
                "kernels in the Pallas interpreter (CPU tests only)."
            )
        self._interpret = interpret
        self._push_fn = jax.jit(self._push_impl, donate_argnums=(0, 1))
        self._pull_fn = jax.jit(self._pull_impl)
        self._push_batch_fn = jax.jit(
            self._push_batch_impl, donate_argnums=(0, 1)
        )
        self._push_combined_fn = jax.jit(
            self._push_combined_impl, donate_argnums=(0, 1)
        )

    def _plane_shape(self, rows: int) -> Tuple[int, ...]:
        """Device shape of one plane of ``rows`` rows and the trash row: flat
        for a dim-1 table (module docstring)."""
        return (rows + 1,) if self.dim == 1 else (rows + 1, self.dim)

    def _place(self, x, dtype=None) -> jax.Array:
        """``x`` as an array on this table's device (committed if one is set)."""
        if dtype is not None and x.dtype != dtype:
            x = x.astype(dtype)
        if self.device is None:
            return jnp.asarray(x)
        return jax.device_put(x, self.device)

    def _place_plane(self, x, dtype) -> jax.Array:
        """The ``[rows + 1, dim]`` host form ``x`` as a plane on the device."""
        return self._place(x.reshape(self._plane_shape(x.shape[0] - 1)), dtype)

    @property
    def nominal_bytes(self) -> int:
        """``(rows + 1) x dim x itemsize`` over the value and state planes —
        what the shard should cost before layout or allocator padding."""
        return (
            (self.rows + 1)
            * self.dim
            * self.value.dtype.itemsize
            * (1 + len(self.state))
        )

    def _kern(self, fn, *args):
        return fn(*args, impl=self.scatter_impl, interpret=self._interpret)

    # -- jitted bodies ------------------------------------------------------
    def _apply_core(self, value, state, ids, grads, n=None):
        """Apply ``grads`` at unique ``ids``: fused or three-pass, then the
        trash-row reset (shared by every push entry point).  ``n`` is a flat
        plane's count of real ids (module docstring); the three passes walk
        the whole bucket.  The ``jax.named_scope``s put every device
        operation of the apply to its line here (``PERF.md`` section 3)."""
        with jax.named_scope("ps.table.apply"):
            if self.fused_apply:
                with jax.named_scope("ps.apply.fused"):
                    value, state = scatter.apply_rows(
                        value, state, ids, grads, self.optimizer.apply,
                        impl=self.scatter_impl, interpret=self._interpret,
                        n=n,
                    )
            else:
                with jax.named_scope("ps.apply.gather"):
                    v_rows = self._kern(scatter.gather_rows, value, ids)
                    s_rows = {
                        k: self._kern(scatter.gather_rows, v, ids)
                        for k, v in state.items()
                    }
                with jax.named_scope("ps.apply.optimizer"):
                    new_v, new_s = self.optimizer.apply(v_rows, s_rows, grads)
                with jax.named_scope("ps.apply.scatter"):
                    value = self._kern(
                        scatter.scatter_update_rows, value, ids, new_v
                    )
                    state = {
                        k: self._kern(
                            scatter.scatter_update_rows, state[k], ids,
                            new_s[k],
                        )
                        for k in state
                    }
            # Re-zero the trash row: PAD_KEY positions in real (variable-nnz)
            # batches legitimately route gradients here; resetting keeps
            # pulls of padded positions exactly zero and makes
            # duplicate-trash-id scatters deterministic.
            with jax.named_scope("ps.apply.trash_reset"):
                value = value.at[-1].set(0.0)
                fills = self.optimizer.state_shapes()
                state = {k: state[k].at[-1].set(fills[k]) for k in state}
        return value, state

    def _push_impl(self, value, state, ids, combined, n=None):
        return self._apply_core(value, state, ids, combined, n)

    def _push_batch_impl(self, value, state, ids, positions, vals, n=None):
        # vals: (k, bm, dim) member stack; positions index its flattening,
        # with pads pointing at the appended zero row — the device-side
        # bucket pad (no host value copies, exact zeros: bitwise-neutral).
        with jax.named_scope("ps.table.stack"):
            flat = vals.reshape(-1, vals.shape[-1])
            flat = jnp.concatenate(
                [flat, jnp.zeros((1,) + flat.shape[1:], flat.dtype)]
            )
            grads = flat[positions]
        return self._apply_core(value, state, ids, grads, n)

    def _push_combined_impl(self, value, state, ids, inverse, vals, n=None):
        # segment_combine pre-merges duplicate rows across bundle members on
        # device; slots past the unique count only ever receive pad/trash
        # positions, whose values are exact zeros.
        with jax.named_scope("ps.table.stack"):
            flat = vals.reshape(-1, vals.shape[-1])
            combined = scatter.segment_combine(flat, inverse, ids.shape[0])
        return self._apply_core(value, state, ids, combined, n)

    def _pull_impl(self, value, state, ids):
        with jax.named_scope("ps.table.pull"):
            with jax.named_scope("ps.gather"):
                v_rows = self._kern(scatter.gather_rows, value, ids)
                s_rows = {
                    k: self._kern(scatter.gather_rows, v, ids)
                    for k, v in state.items()
                }
            return self.optimizer.pull_weights(v_rows, s_rows)

    # -- public ops ---------------------------------------------------------
    def _real(self, n):
        """``n`` as a push's jitted function takes it: a flat plane's count
        of real ids; nothing for any other table, whose programs then do not
        depend on what the caller says."""
        return n if self.dim == 1 else None

    def push(
        self, ids: jax.Array, combined_grads: jax.Array, n=None
    ) -> jax.Array:
        """Apply pre-combined gradient rows at unique ``ids`` (in place).

        ``ids`` must be unique (host guarantees via ``localize_to_slots``);
        padded ids point at the trash row and must carry zero gradients.
        ``n`` (an ``int32`` scalar) says that ``ids[n:]`` all point at the
        trash row; a dim-1 table then does not visit them (module
        docstring).
        Returns the new ``value`` array so the caller can hand it to the
        ApplyLedger as the readiness ref for this dispatch (the NEXT push
        donates it away, so polling through ``self.value`` would observe a
        later apply, not this one).
        """
        self.value, self.state = self._push_fn(
            self.value, self.state, ids, combined_grads, self._real(n)
        )
        return self.value

    def push_batch(
        self, ids: jax.Array, positions: jax.Array, vals: jax.Array, n=None
    ) -> jax.Array:
        """One bundled apply round: unique ``ids`` gather their gradient rows
        out of the stacked member values by ``positions`` (pad positions index
        the appended zero row).  Donated in-place update, one jit call.
        ``n`` as in :meth:`push`.
        Returns the new ``value`` (ledger readiness ref, as in :meth:`push`).
        """
        self.value, self.state = self._push_batch_fn(
            self.value, self.state, ids, positions, vals, self._real(n)
        )
        return self.value

    def push_combined(
        self, ids: jax.Array, inverse: jax.Array, vals: jax.Array, n=None
    ) -> jax.Array:
        """Bundled apply with device pre-combine: every stacked value row is
        segment-summed into its unique-id slot (``inverse``), then applied in
        one donated jit call — the ``dup_policy="combine"`` engine mode.
        ``n`` as in :meth:`push`.
        Returns the new ``value`` (ledger readiness ref, as in :meth:`push`).
        """
        self.value, self.state = self._push_combined_fn(
            self.value, self.state, ids, inverse, vals, self._real(n)
        )
        return self.value

    def combine(self, inverse: jax.Array, values: jax.Array, num_rows: int) -> jax.Array:
        """Worker-side duplicate pre-combine (device segment_sum)."""
        return _combine_jit(inverse, values, num_rows)

    def pull(self, ids: jax.Array) -> jax.Array:
        """Servable weight rows for unique ``ids``."""
        return self._pull_fn(self.value, self.state, ids)

    # -- direct row access (checkpoint, tests, model eval) ------------------
    def host_planes(self) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
        """``(value, state)`` as ``[rows + 1, dim]`` NumPy arrays, trash row
        last: the host form of the shard, whatever the planes' device shape."""
        shape = (self.rows + 1, self.dim)
        return np.asarray(self.value).reshape(shape), {
            k: np.asarray(v).reshape(shape) for k, v in self.state.items()
        }

    def weights(self) -> np.ndarray:
        """Full servable weight table (excluding the trash row), on the host
        as ``[rows, dim]``."""
        w = self.optimizer.pull_weights(self.value, self.state)
        return np.asarray(w).reshape(self.rows + 1, self.dim)[: self.rows]

    def set_value(self, value: np.ndarray | jax.Array) -> None:
        if value.shape != (self.rows + 1, self.dim):
            raise ValueError(
                f"expected {(self.rows + 1, self.dim)}, got {value.shape}"
            )
        self.value = self._place_plane(value, self.value.dtype)

    def install_rows(
        self, value: np.ndarray, state: Dict[str, np.ndarray]
    ) -> None:
        """Replace the shard with ``[rows, dim]`` host arrays (NO trash row).

        The restore-side counterpart of the checkpoint writers (which save
        rows excluding the trash row): appends a fresh trash row — zero
        value, optimizer init fills — and installs via :meth:`resize`, so
        the shard may change row count (restore onto a different fleet
        shape).
        """
        if set(state) != set(self.state):
            raise ValueError(
                f"optimizer state keys mismatch: {set(state)} != {set(self.state)}"
            )
        n = int(value.shape[0])
        dtype = np.dtype(self.value.dtype)
        fills = self.optimizer.state_shapes()
        buf = np.zeros((n + 1, self.dim), dtype)
        buf[:n] = value
        sbuf = {}
        for k, fill in fills.items():
            sk = np.full((n + 1, self.dim), fill, dtype)
            sk[:n] = state[k]
            sbuf[k] = sk
        self.resize(buf, sbuf)

    def resize(self, value: np.ndarray, state: Dict[str, np.ndarray]) -> None:
        """Replace the shard wholesale with a DIFFERENT row count.

        Live migration grows/shrinks a server's shard (``kv/server.py``
        adopt/release); ``value``/``state`` arrive as ``[new_rows + 1, dim]``
        host arrays INCLUDING the trash row.  The jitted push/pull steps are
        shape-polymorphic (jax.jit retraces per shape), so no re-wiring is
        needed — the next push simply compiles for the new shard size.
        """
        if value.ndim != 2 or value.shape[1] != self.dim or value.shape[0] < 1:
            raise ValueError(f"bad resize value shape {value.shape}")
        if set(state) != set(self.state):
            raise ValueError(
                f"optimizer state keys mismatch: {set(state)} != {set(self.state)}"
            )
        dtype = self.value.dtype
        self.rows = int(value.shape[0]) - 1
        self.value = self._place_plane(value, dtype)
        self.state = {k: self._place_plane(v, dtype) for k, v in state.items()}


@functools.partial(jax.jit, static_argnames=("num_rows",))
def _combine_jit(inverse, values, num_rows: int):
    return scatter.segment_combine(values, inverse, num_rows)
