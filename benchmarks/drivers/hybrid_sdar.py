"""Driver ``hybrid_sdar``: SDAR-30B-A3B's body (``models/sdar_moe.py``:
grouped-query attention with per-head norms over a masked copy of every
sequence beside its clean one, block-diffusion attention, a held share of
softmax-routed experts in every layer) trained by block diffusion on the
hybrid path.  Set-up, the loop and the window are ``drivers/hybrid_lm.py``'s:
the package's ``HybridLMTrainer`` on a 1 x 1 mesh of the cell's chip, the
embedding rows of all ``2L`` positions of ``[xt ; x0]`` pulled from and
pushed to the cluster's ``KVServer``s as device arrays, a step that drops a
token slot of a held expert retiring the worker.  The batches are
``generators/lm_blockdiff.py``'s, the block length the generator's.

``grad_check`` is written once here, for any body on this path: what it
reads of a body is the class's ``reference`` (a module with ``sizes_of``
and ``sequence_loss(sizes, params, emb, tokens)``) and ``leaves`` (the
parameter leaves it compares, each with its limit in the configuration's
``grad_check.leaves``), and a body's own counters are checked in
``counter_fails``.  At the timed sizes and on the first batch, what the
jitted step the window runs does against the reference computed from the
trainer's own parameter arrays, one sequence at a time: the loss; the
embedding gradient per position of all ``2L``, against the batch's largest
gradient at the median and the worst position and against the position's
own gradient at the first quartile (``own_p25``); the gradients of the named
leaves, as norms, read from the step's own first moments (a fresh AdamW
state holds ``(1 - b1)`` times the gradient); those leaves' change against
AdamW computed in NumPy; a buffer (``cfg.buffers``) left without gradient or
update; ``moe_dropped_slots`` 0 and ``bd_masked_positions`` the batch's count
of mask ids (``counter_fails``); and the mask row's gradient as the
worker combines it for the push against the sum of the reference's over the
batch's mask positions (``plane_fails``).
"""

from __future__ import annotations

import functools
import json
import os
import time

import numpy as np

# this body's operations, bytes and device scopes (``harness/model_scopes.py``)
from benchmarks.harness import sdar_flops as body  # noqa: F401
from benchmarks.harness.cell import load_module
from benchmarks.harness.correctness import TIMEOUT, compare_grads
from benchmarks.reference import sdar_moe as ref

# the driver beside this file, found as the harness finds a driver
hybrid_lm = load_module(
    "drivers", "hybrid_lm", os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_leaf, _with, log = hybrid_lm._leaf, hybrid_lm._with, hybrid_lm.log


def _norm(x) -> float:
    return float(np.sqrt(np.sum(np.square(x))))


@functools.lru_cache(maxsize=None)
def _reference_grad(reference, sizes: tuple):
    """The reference's loss of one sequence and its gradients of the named
    leaves and of the rows, jitted once a reference and its sizes (``sizes``
    the items of ``reference.sizes_of``)."""
    import jax

    sz = dict(sizes)

    def loss(leaves, params, emb_b, tok_b):
        return reference.sequence_loss(sz, _with(params, leaves), emb_b, tok_b)

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 2)))


class Driver(hybrid_lm.Driver):
    #: the plain reference the comparison holds the step to
    reference = ref
    #: the parameter leaves whose gradients and change it compares: attention
    #: (a query and a key kernel, a head norm), the router, the held experts'
    #: gate and the head
    leaves = ("layer_0/attn/q/kernel", "layer_1/attn/k/kernel",
              "layer_2/attn/q_norm/scale", "layer_0/moe/router/kernel",
              "layer_1/moe/experts/gate", "lm_head/kernel")

    def __init__(self, run):
        # a program without this body stops here, before a cluster is built
        from parameter_server_tpu.models import sdar_moe  # noqa: F401

        super().__init__(run)

    def model_config(self):
        from parameter_server_tpu.models.sdar_moe import SdarMoeConfig

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
        return SdarMoeConfig.from_published(cfg, **{
            **cut, **model, "vocab_size": self.run.sizes["rows"],
            "block_length": cfg["generator_params"]["block_length"],
        })

    def train(self, clock):
        from parameter_server_tpu.models import moe, sdar_moe

        super().train(clock)
        masked = [c["bd_masked_positions"] for c in self.counters
                  if "bd_masked_positions" in c]
        if masked:
            log("[bd] " + json.dumps({
                "steps": len(masked), "masked_positions_mean": float(np.mean(masked)),
                "masked_positions_min": int(min(masked)),
                "masked_positions_max": int(max(masked)),
            }))
        # the expert loops' work a step against the static capacity's: a step
        # over it ran blocks the capacity had no room for
        run = [c["moe_blocks_run"] for c in self.counters if "moe_blocks_run" in c]
        if run:
            floor = self.model.n_layers * moe.capacity_blocks(
                sdar_moe.expert_layer(self.model), self.batches[0][0].size
            )
            log("[capacity] " + json.dumps({
                "steps": len(run), "blocks_capacity": floor,
                "blocks_run_median": float(np.median(run)),
                "blocks_run_max": int(max(run)),
                "steps_over": sum(r > floor for r in run),
            }))

    # -- the comparison that decides ``correct`` ----------------------------
    def counter_fails(self, counters, tokens) -> list:
        """A body's own counters on the first batch: no dropped slot, and
        the masked positions the step counted are the batch's mask ids."""
        fails = []
        if counters.get("moe_dropped_slots"):
            fails.append(f"moe_dropped_slots = {counters['moe_dropped_slots']}")
        L = tokens.shape[1] // 2
        want = int(np.sum(tokens[:, :L] == self.model.mask_id))
        if counters.get("bd_masked_positions") != want:
            fails.append(f"bd_masked_positions = {counters.get('bd_masked_positions')}"
                         f", the batch holds {want} mask ids")
        return fails

    def plane_fails(self, tokens, g_emb, want_emb, info, limit) -> list:
        """The mask row as the window's push hands it to the PS plane: the
        batch's ids localized and the step's per-position gradients combined
        on the device, as ``KVWorker.push_device`` does before it sends (a
        server then sees the row once).  The push is not sent: the servers'
        count of pushes is held to the legs the window acknowledged.  The
        mask row's combined gradient is held to the sum of the reference's
        over the batch's mask positions."""
        import jax.numpy as jnp

        from parameter_server_tpu.kv import worker as kv_worker

        keys = tokens.reshape(-1)
        slots, inverse = self.kv._localize(self.table.name, keys)
        combined = kv_worker._segment_combine(
            jnp.asarray(inverse), g_emb.reshape(keys.size, -1), slots.shape[0]
        )
        mask = keys == self.model.mask_id
        got = np.asarray(combined[int(inverse[np.argmax(mask)])], np.float64)
        want = want_emb.reshape(keys.size, -1)[mask].sum(axis=0, dtype=np.float64)
        info["mask_row"] = _norm(got - want) / (_norm(want) or 1.0)
        if not info["mask_row"] <= limit:
            return [f"the mask row's combined gradient is off by "
                    f"{info['mask_row']:.3e} (limit {limit:.0e})"]
        return []

    def grad_check(self):
        import jax
        import jax.numpy as jnp

        run, tr = self.run, self.trainer
        limits = dict(run.config["grad_check"])
        if run.dry_run:
            limits.update(run.config["dry_run"]["grad_check"])
        adam = limits["adamw"]
        named = {path: limits["leaves"][path] for path in self.leaves}
        tokens = self.batches[0][0]
        rows = self.kv.pull_sync(self.table.name, tokens, timeout=TIMEOUT)
        # placed as ``HybridLMTrainer.step`` places them: the step below is
        # then the program the window runs, not a second one of its size
        emb = jax.device_put(
            jnp.asarray(rows, jnp.float32).reshape(*tokens.shape, -1),
            tr._batch3,
        )
        tok = jax.device_put(jnp.asarray(tokens, jnp.int32), tr._batch2)

        # the reference first, from the trainer's own arrays (the step below
        # donates them), one sequence at a time: the loss, the gradient of
        # every position's row and of the named parameter leaves
        blocks = {} if run.dry_run else limits["reference_blocks"]
        ref_grad = _reference_grad(self.reference, tuple(sorted(
            self.reference.sizes_of(self.model, **blocks).items()
        )))
        B = tokens.shape[0]
        before = {path: _leaf(tr.params, path) for path in named}
        theta0 = {path: np.asarray(x, np.float64) for path, x in before.items()}
        buffers = {
            "/".join(k.key for k in path): np.asarray(x)
            for path, x in jax.tree_util.tree_flatten_with_path(tr.params)[0]
            if path[-1].key in getattr(self.model, "buffers", ())
        }
        want_loss, want_emb = 0.0, []
        want = {path: 0.0 for path in named}
        t0 = time.perf_counter()
        for b in range(B):
            loss_b, (g_named, g_emb) = ref_grad(before, tr.params, emb[b], tok[b])
            want_loss += float(loss_b) / B
            want_emb.append(np.asarray(g_emb) / B)
            for path, g in g_named.items():
                want[path] = want[path] + np.asarray(g, np.float64) / B
        del before
        want_emb = np.stack(want_emb)

        t1 = time.perf_counter()
        # the step the window runs (it updates the body once: the rows are
        # not pushed, so the window starts one body update in).  Its first
        # moments, from a fresh state, are (1 - b1) x the gradients it took
        if int(tr.opt_state[0].count) != 0:
            return ["the optimizer state is not fresh: its first moments are "
                    "not the first step's gradients"]
        tr.params, tr.opt_state, loss, g_emb, counters = tr._step(
            tr.params, tr.opt_state, emb, tok
        )
        fails, info = compare_grads(
            np.asarray(g_emb), want_emb, "embedding gradients",
            tokens.size, median=limits["median"], worst=limits["worst"],
        )
        # per position, the error over that position's own gradient: the
        # first quartile reads the positions that rounding alone moved (one
        # some layer's top-k flipped for reads of order 1)
        rows_want = want_emb.reshape(tokens.size, -1)
        err = np.asarray(g_emb, np.float64).reshape(tokens.size, -1) - rows_want
        share = np.linalg.norm(err, axis=1) / np.maximum(
            np.linalg.norm(rows_want, axis=1), 1e-30
        )
        info["own_p25"], info["own_median"] = (
            float(np.percentile(share, 25)), float(np.median(share))
        )
        if not info["own_p25"] <= limits["own_p25"]:
            fails.append(
                f"embedding gradients: the first-quartile position is off by "
                f"{info['own_p25']:.4e} of its own gradient "
                f"(limit {limits['own_p25']:.4e})"
            )
        info["reference_s"] = round(t1 - t0, 1)
        info["first_step_s"] = round(time.perf_counter() - t1, 1)
        info["loss"] = abs(float(loss) - want_loss) / max(1.0, abs(want_loss))
        if not info["loss"] <= limits["loss"]:
            fails.append(f"loss {float(loss)} against {want_loss} "
                         f"(limit {limits['loss']:.0e})")
        # the named leaves: the step's own gradient against the reference's,
        # and the step's change of the leaf against AdamW in NumPy from the
        # step's own gradient (a state or a leaf left as it was reads 1)
        mu = tr.opt_state[0].mu
        info["leaves"], off, due = {}, 0.0, 0.0
        for path, limit in named.items():
            g = np.asarray(_leaf(mu, path), np.float64) / (1.0 - adam["b1"])
            info["leaves"][path] = _norm(g - want[path]) / (_norm(want[path]) or 1.0)
            if not info["leaves"][path] <= limit:
                fails.append(f"gradient of {path} off by "
                             f"{info['leaves'][path]:.3e} (limit {limit:.0e})")
            update = -self.learning_rate * (
                g / (np.abs(g) + adam["eps"]) + adam["weight_decay"] * theta0[path]
            )
            change = np.asarray(_leaf(tr.params, path), np.float64) - theta0[path]
            # the step adds in float32: at a warm-up's first rate the sum's
            # rounding is a per cent of the change, and is no fault
            due_change = (
                theta0[path].astype(np.float32) + update.astype(np.float32)
            ).astype(np.float64) - theta0[path]
            off += np.sum(np.square(change - due_change))
            due += np.sum(np.square(update))
        info["update"] = float(np.sqrt(off / due)) if due else 1.0
        if not info["update"] <= limits["update"]:
            fails.append(f"the parameters' change is off AdamW's by "
                         f"{info['update']:.3e} (limit {limits['update']:.0e})")
        # a buffer takes part in the selection and in nothing else
        for path, was in buffers.items():
            if np.any(np.asarray(_leaf(mu, path))) or not np.array_equal(
                np.asarray(_leaf(tr.params, path)), was
            ):
                fails.append(f"the buffer {path} took a gradient or an update")
        counters = {k: int(v) for k, v in counters.items()}
        info["counters"] = counters
        fails += self.counter_fails(counters, tokens)
        fails += self.plane_fails(tokens, g_emb, want_emb, info, limits["mask_row"])
        log(f"[grad_check] {json.dumps(info)}")
        return fails
