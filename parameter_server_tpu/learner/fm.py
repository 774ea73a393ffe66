"""FM learner: single-device fused trainer for the factorization machine.

Reference analogue: the factorization-machine app over the SGD scaffold
(``src/app/factorization_machine/`` + ``src/learner/sgd.h`` [U]).  The Van
path needs no dedicated class — ``KVWorker.pull/push`` with
``models.fm.fm_grad_rows`` is the loop (see ``tests/test_fm.py``); this
module provides the fused local path mirroring
:class:`~parameter_server_tpu.learner.sgd.LocalLRTrainer`.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_tpu.config import TableConfig
from parameter_server_tpu.kv.table import KVTable
from parameter_server_tpu.models import fm
from parameter_server_tpu.utils import metrics as metrics_lib
from parameter_server_tpu.utils.keys import HashLocalizer, localize_to_slots


class LocalFMTrainer:
    """Single-device FM: fused pull+grad+apply+scatter per step.

    ``table_cfg.dim`` must be ``1 + k`` (linear weight + k factors); use
    ``init_scale > 0`` so factor vectors break symmetry (column 0's linear
    weight tolerates random init like the reference's FM).
    """

    def __init__(
        self,
        table_cfg: TableConfig,
        *,
        min_bucket: int = 1024,
        dashboard: Optional[metrics_lib.Dashboard] = None,
        seed: int = 0,
    ) -> None:
        if table_cfg.dim < 2:
            raise ValueError("FM table dim must be 1 + k (k >= 1 factors)")
        self.cfg = table_cfg
        self.table = KVTable(table_cfg, seed=seed)
        self.optimizer = self.table.optimizer
        self.localizer = HashLocalizer(table_cfg.rows)
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
        slots, inverse, _n = localize_to_slots(
            keys, self.localizer, min_bucket=self.min_bucket
        )
        t.value, t.state, self.bias, self.bias_state, loss = fm.fused_train_step(
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
        self.step_count += 1
        return float(loss)

    def train(self, batch_fn, num_steps: int) -> None:
        for _ in range(num_steps):
            keys, labels = batch_fn()
            loss = self.step(keys, labels)
            self.dashboard.record(self.step_count, loss, examples=labels.shape[0])

    def eval_auc(self, batch_fn, num_batches: int) -> float:
        weights = self.table.weights()
        bias = float(
            np.asarray(self.optimizer.pull_weights(self.bias, self.bias_state))[0, 0]
        )
        scores, labels_all = [], []
        for _ in range(num_batches):
            keys, labels = batch_fn()
            slots_pos = self.localizer.assign(keys)
            # PAD slots (== capacity) cannot appear with fixed-nnz batches;
            # guard anyway by clipping into the real row range
            slots_pos = np.minimum(slots_pos, self.cfg.rows - 1)
            scores.append(fm.eval_logits_np(weights, bias, slots_pos))
            labels_all.append(labels)
        return metrics_lib.auc(np.concatenate(labels_all), np.concatenate(scores))
