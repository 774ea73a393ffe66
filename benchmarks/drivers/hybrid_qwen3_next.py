"""Driver ``hybrid_qwen3_next``: Qwen3-Next-80B-A3B's body
(``models/qwen3_next.py``: gated DeltaNet with a scalar decay a head over
grouped key heads, gated attention at head size 256, a held share of
softmax-routed experts beside a gated shared one in every layer) on the
hybrid path.  Set-up, the loop and the window are ``drivers/hybrid_lm.py``'s:
the package's ``HybridLMTrainer`` on a 1 x 1 mesh of the cell's chip, the
embedding rows pulled from and pushed to the cluster's ``KVServer``s as
device arrays, a step that drops a token slot of a held expert retiring the
worker.  The batches are ``generators/lm.py``'s.

``grad_check`` is ``drivers/hybrid_sdar.py``'s, written once for any body
on this path, through this class's ``reference`` and ``leaves``; what this
file adds is what differs for a next-token body: its counters (no masked
positions) and the row of the PS plane the comparison reads, the batch's
hottest token, whose gradient the worker combines from the most positions
(``plane_fails``; the configuration's ``grad_check.mask_row`` is the name
the shared comparison reads that limit under).
"""

from __future__ import annotations

import os

import numpy as np

# this body's operations, bytes and device scopes (``harness/model_scopes.py``)
from benchmarks.harness import qwen3_next_flops as body  # noqa: F401
from benchmarks.harness.cell import load_module
from benchmarks.reference import qwen3_next as ref

# the driver beside this file, found as the harness finds a driver
hybrid_sdar = load_module(
    "drivers", "hybrid_sdar", os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_norm = hybrid_sdar._norm


class Driver(hybrid_sdar.Driver):
    #: the plain reference the comparison holds the step to
    reference = ref
    #: the parameter leaves whose gradients and change it compares: the
    #: DeltaNet's decay rate, its beta and decay projection and its
    #: q / k / v / z projection, attention's queries with their gates, the
    #: router, the held experts' gate, the shared expert's gate and the head
    leaves = ("layer_0/gdn/A_log", "layer_1/gdn/ba/kernel",
              "layer_2/gdn/qkvz/kernel", "layer_3/full/q/kernel",
              "layer_0/moe/router/kernel", "layer_1/moe/experts/gate",
              "layer_2/moe/shared_gate/kernel", "lm_head/kernel")

    def __init__(self, run):
        # a program without this body stops here, before a cluster is built
        from parameter_server_tpu.models import qwen3_next  # noqa: F401

        super().__init__(run)

    def model_config(self):
        from parameter_server_tpu.models.qwen3_next import Qwen3NextConfig

        cfg = self.run.config
        cut = dict(
            n_layers=cfg["n_layers"], layers_first=cfg["layers_first"],
            experts_held=cfg["experts_held"], experts_first=cfg["experts_first"],
        )
        model = dict(cfg["model"])
        if self.run.dry_run:  # tiny sizes: proves nothing
            model.update(cfg["dry_run"]["model"])
        self.loss_chunk = model.pop("loss_chunk")
        self.peak_rate = model.pop("learning_rate")
        self.warmup_steps = model.pop("warmup_steps")
        #: the first step's rate, which ``grad_check`` holds the update to
        self.learning_rate = self.peak_rate / max(1, self.warmup_steps)
        return Qwen3NextConfig.from_published(
            cfg, **{**cut, **model, "vocab_size": self.run.sizes["rows"]}
        )

    def counter_fails(self, counters, tokens) -> list:
        """No dropped slot on the first batch."""
        if counters.get("moe_dropped_slots"):
            return [f"moe_dropped_slots = {counters['moe_dropped_slots']}"]
        return []

    def plane_fails(self, tokens, g_emb, want_emb, info, limit) -> list:
        """The batch's hottest row as the window's push hands it to the PS
        plane: the batch's ids localized and the step's per-position
        gradients combined on the device, as ``KVWorker.push_device`` does
        before it sends (``hybrid_sdar.Driver.plane_fails`` reads the mask
        row so), against the sum of the reference's gradients over that
        token's positions.  The push is not sent."""
        import jax.numpy as jnp

        from parameter_server_tpu.kv import worker as kv_worker

        keys = tokens.reshape(-1)
        slots, inverse = self.kv._localize(self.table.name, keys)
        combined = kv_worker._segment_combine(
            jnp.asarray(inverse), g_emb.reshape(keys.size, -1), slots.shape[0]
        )
        ids, counts = np.unique(keys, return_counts=True)
        hot = keys == ids[np.argmax(counts)]
        got = np.asarray(combined[int(inverse[np.argmax(hot)])], np.float64)
        want = want_emb.reshape(keys.size, -1)[hot].sum(axis=0, dtype=np.float64)
        info["hot_row_positions"] = int(counts.max())
        info["hot_row"] = _norm(got - want) / (_norm(want) or 1.0)
        if not info["hot_row"] <= limit:
            return [f"the hottest row's combined gradient is off by "
                    f"{info['hot_row']:.3e} (limit {limit:.0e})"]
        return []
