"""SGD learners: the minibatch pull -> grad -> push scaffolds.

Reference analogue: ``src/learner/sgd.h`` minibatch scaffolds plus the async
SGD / FTRL worker loops of ``src/app/linear_method/async_sgd.h`` [U].

Two drivers over the same model math (``models/linear.py``):

- :class:`LocalLRTrainer` — single-process fast path: the table lives on the
  local device and each step is one fused XLA program (BASELINE config
  #1).
- :class:`AsyncLRLearner` — the classic PS topology over the Van: N worker
  threads pull/push through :class:`~parameter_server_tpu.kv.worker.KVWorker`
  under a :class:`~parameter_server_tpu.core.clock.ConsistencyController`
  (BSP/SSP/ASP), servers apply updates.  This is the semantics/API path and
  the seam where DCN multi-host traffic will attach.
"""

from __future__ import annotations

import functools
import threading
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_tpu.config import (
    ConsistencyConfig,
    OptimizerConfig,
    TableConfig,
)
from parameter_server_tpu.core.clock import ConsistencyController
from parameter_server_tpu.kv.optim import make_optimizer, require_dense_apply
from parameter_server_tpu.kv.table import KVTable
from parameter_server_tpu.kv.worker import KVWorker
from parameter_server_tpu.models import linear
from parameter_server_tpu.utils import metrics as metrics_lib
from parameter_server_tpu.utils.keys import (
    HashLocalizer,
    ensure_uint32_keys,
    localize_to_slots,
)
from parameter_server_tpu.utils.threads import run_threads

Batch = Tuple[np.ndarray, np.ndarray]  # (keys [B, nnz], labels [B])
BatchFn = Callable[[], Batch]


class LocalLRTrainer:
    """Single-device sparse LR: fused pull+grad+apply+scatter per step."""

    def __init__(
        self,
        table_cfg: TableConfig,
        *,
        min_bucket: int = 1024,
        dashboard: Optional[metrics_lib.Dashboard] = None,
        mode: str = "rows",
        device_hash: bool = False,
    ) -> None:
        """``mode="rows"``: bucketed-unique gather/apply/scatter (general).
        ``mode="dense"``: per-position hashed slots + full-table apply — no
        host dedup; requires l1 == l2 == 0 and a g=0-stable optimizer.
        ``device_hash``: hash keys ON DEVICE (32-bit; dense mode) — raw
        uint32 keys ship to the chip and :meth:`step_block` runs K steps per
        dispatch (for hosts where the transfer is the bottleneck)."""
        if table_cfg.dim != 1:
            raise ValueError("LR weight table must have dim=1")
        if mode not in ("rows", "dense"):
            raise ValueError(f"unknown mode {mode!r}")
        if mode == "dense":
            require_dense_apply(table_cfg.optimizer)
        if device_hash and mode != "dense":
            raise ValueError("device_hash requires mode='dense'")
        self.mode = mode
        self.device_hash = device_hash
        self.cfg = table_cfg
        self.table = KVTable(table_cfg)
        self.optimizer = self.table.optimizer
        self.localizer = HashLocalizer(
            table_cfg.rows, hash_bits=32 if device_hash else 64
        )
        self.min_bucket = min_bucket
        self.bias = jnp.zeros((1, 1), dtype=jnp.float32)
        self.bias_state = {
            k: jnp.zeros((1, 1), dtype=jnp.float32)
            for k in self.optimizer.state_shapes()
        }
        self.dashboard = dashboard or metrics_lib.Dashboard(print_every=0)
        self.step_count = 0

    def step(self, keys: np.ndarray, labels: np.ndarray) -> float:
        t = self.table
        if self.mode == "dense":
            slots_pos = self.localizer.assign(keys)  # [B, nnz], no dedup
            (
                t.value,
                t.state,
                self.bias,
                self.bias_state,
                loss,
            ) = linear.dense_fused_train_step(
                t.value,
                t.state,
                self.bias,
                self.bias_state,
                jnp.asarray(slots_pos),
                jnp.asarray(labels),
                self.optimizer,
                self.cfg.rows,
            )
        else:
            slots, inverse, _n = localize_to_slots(
                keys, self.localizer, min_bucket=self.min_bucket
            )
            t.value, t.state, self.bias, self.bias_state, loss = (
                linear.fused_train_step(
                    t.value,
                    t.state,
                    self.bias,
                    self.bias_state,
                    jnp.asarray(slots),
                    jnp.asarray(inverse),
                    jnp.asarray(labels),
                    self.optimizer,
                    slots.shape[0],
                )
            )
        self.step_count += 1
        return float(loss)

    def step_async(self, keys: np.ndarray, labels: np.ndarray) -> jax.Array:
        """Dense-mode step without host sync; returns the device loss.

        Lets the host race ahead preparing batches while the device queue
        drains (the PS pipelining analogue for the single-chip path).
        """
        if self.mode != "dense":
            raise ValueError("step_async requires mode='dense'")
        t = self.table
        slots_pos = self.localizer.assign(keys)
        (
            t.value,
            t.state,
            self.bias,
            self.bias_state,
            loss,
        ) = linear.dense_fused_train_step(
            t.value,
            t.state,
            self.bias,
            self.bias_state,
            jnp.asarray(slots_pos),
            jnp.asarray(labels),
            self.optimizer,
            self.cfg.rows,
        )
        self.step_count += 1
        return loss

    def step_block(
        self, keys_block: np.ndarray, labels_block: np.ndarray
    ) -> jax.Array:
        """K dense steps in one dispatch (requires ``device_hash``).

        ``keys_block``: ``[K, B, nnz]`` keys (must fit uint32);
        ``labels_block``: ``[K, B]``.  Returns the device losses ``[K]``
        without host sync — the block analogue of :meth:`step_async`.

        Pass keys at their RAW width: the out-of-range guard below only runs
        on non-uint32 input, so a caller-side ``astype(np.uint32)`` silently
        wraps bad keys before the check can see them (ADVICE r2).
        """
        if not self.device_hash:
            raise ValueError("step_block requires device_hash=True")
        keys_block = ensure_uint32_keys(keys_block)
        return self.step_block_device(
            jnp.asarray(keys_block), jnp.asarray(labels_block)
        )

    def step_block_device(
        self, keys_block: jax.Array, labels_block: jax.Array
    ) -> jax.Array:
        """:meth:`step_block` for ALREADY device-resident uint32 inputs.

        The overlapped ingest path (``data.prefetch.PrefetchPipeline``)
        validates and casts keys on its producer thread
        (``utils.keys.ensure_uint32_keys``) and stages the H2D copy there
        too, so this method is pure dispatch — no host work on the critical
        path between scan blocks.  Callers own the validation contract:
        feed it anything but checked uint32 keys and bad keys wrap
        silently, which is why the host-side :meth:`step_block` remains the
        default entry point.
        """
        if not self.device_hash:
            raise ValueError("step_block_device requires device_hash=True")
        t = self.table
        (
            t.value,
            t.state,
            self.bias,
            self.bias_state,
            losses,
        ) = linear.dense_scan_train_step(
            t.value,
            t.state,
            self.bias,
            self.bias_state,
            keys_block,
            labels_block,
            self.optimizer,
            self.cfg.rows,
            self.localizer.seed,
        )
        self.step_count += int(keys_block.shape[0])
        return losses

    def train_stream(self, pipeline, num_blocks: Optional[int] = None) -> list:
        """Drain a :class:`~parameter_server_tpu.data.prefetch.PrefetchPipeline`
        of ``(keys_block, labels_block)`` device pairs through
        :meth:`step_block_device`; returns the per-block device loss arrays.

        The prefetch producer assembles and stages block ``i+1`` while the
        device executes block ``i`` — the ingest-overlap loop the scan-block
        design was built for.
        """
        losses = []
        for kd, yd in pipeline:
            losses.append(self.step_block_device(kd, yd))
            if num_blocks is not None and len(losses) >= num_blocks:
                break
        return losses

    def train(self, batch_fn: BatchFn, num_steps: int) -> None:
        for _ in range(num_steps):
            keys, labels = batch_fn()
            loss = self.step(keys, labels)
            self.dashboard.record(
                self.step_count, loss, examples=labels.shape[0]
            )

    def eval_auc(self, batch_fn: BatchFn, num_batches: int) -> float:
        scores, labels_all = [], []
        for _ in range(num_batches):
            keys, labels = batch_fn()
            slots, inverse, _n = localize_to_slots(
                keys, self.localizer, min_bucket=self.min_bucket
            )
            logits = linear.eval_logits(
                self.table.value,
                self.table.state,
                self.bias,
                self.bias_state,
                jnp.asarray(slots),
                jnp.asarray(inverse),
                labels.shape[0],
                self.optimizer,
            )
            scores.append(np.asarray(logits))
            labels_all.append(labels)
        return metrics_lib.auc(np.concatenate(labels_all), np.concatenate(scores))


class AsyncLRLearner:
    """Multi-worker PS loop over the Van with BSP/SSP/ASP gating.

    Each worker thread: ``wait_turn -> pull(w) -> grad -> push(g) -> advance``.
    Under ASP pushes from stale pulls interleave freely; under BSP the vector
    clock enforces lockstep — same mechanism, same code path, mirroring the
    reference's single DAG mechanism for all three modes.
    """

    def __init__(
        self,
        workers: list[KVWorker],
        consistency: ConsistencyConfig,
        *,
        table: str = "w",
        dashboard: Optional[metrics_lib.Dashboard] = None,
    ) -> None:
        self.workers = workers
        self.controller = ConsistencyController(consistency, len(workers))
        self.table = table
        self.dashboard = dashboard or metrics_lib.Dashboard(print_every=0)
        self._lock = threading.Lock()
        self._losses: list[float] = []

    def run(
        self,
        batch_fns: list[BatchFn],
        steps_per_worker: int,
        *,
        timeout: float = 60.0,
    ) -> list[float]:
        """Run all workers to completion; returns per-iteration mean losses."""
        run_threads(
            [
                functools.partial(
                    self._worker_loop, w, batch_fns[i], i, steps_per_worker,
                    timeout,
                )
                for i, w in enumerate(self.workers)
            ],
            name="sgd-worker",
        )
        return list(self._losses)

    def _worker_loop(
        self,
        kv: KVWorker,
        batch_fn: BatchFn,
        index: int,
        steps: int,
        timeout: float,
    ) -> None:
        for t in range(steps):
            if not self.controller.wait_turn(index, t, timeout=timeout):
                raise TimeoutError(f"worker {index} stalled at iter {t} (SSP bound)")
            keys, labels = batch_fn()
            w_pos = kv.pull_sync(self.table, keys, timeout=timeout)
            g, _gb, loss = linear.grad_rows(
                jax.device_put(w_pos, kv.device),
                jax.device_put(labels, kv.device),
            )
            push_ts = kv.push(self.table, keys, np.asarray(g) / labels.shape[0])
            kv.wait(push_ts, timeout=timeout)
            self.controller.finish_iteration(index)
            with self._lock:
                self._losses.append(float(loss))
                self.dashboard.record(
                    len(self._losses), float(loss), examples=labels.shape[0]
                )
