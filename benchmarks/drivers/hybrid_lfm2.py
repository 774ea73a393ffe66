"""Driver ``hybrid_lfm2``: LFM2-8B-A1B's body (``models/lfm2_moe.py``: gated
short convolutions, grouped-query attention, a held share of bias-selected
experts) on the hybrid path.  Set-up, the loop and the window are
``drivers/hybrid_lm.py``'s: the package's ``HybridLMTrainer`` on a 1 x 1
mesh of the cell's chip, the embedding rows pulled from and pushed to the
cluster's ``KVServer``s as device arrays, a step that drops a token slot of
a held expert retiring the worker.  This file names what that driver names
in its body: the model's config, the reference, and the leaves the
comparison reads.

``grad_check`` compares, at the timed sizes and on the first batch, what the
jitted step the window runs does with ``reference/lfm2_moe.py`` computed
from the trainer's own parameter arrays (no second copy, the selection bias
included), one sequence at a time: the loss; the embedding gradient per
token position (the forward and the backward of every layer), against the
batch's largest gradient at the median and the worst position and against
the position's own gradient at the first quartile (the reading that tells a
bfloat16 residual stream from the stated precision); the gradients
of the parameter leaves the configuration names, as norms; and those leaves'
change.  The step returns no parameter gradient, and a second program that
did would be a third executable of the step's size: the step runs from a
fresh optimizer state, so its first moments are ``(1 - b1)`` times the
gradients it took.  The leaves' change is held to AdamW computed in NumPy
from those gradients: an update that is not AdamW's at the stated rate, or a
state or a parameter the step left as it was, reads of order 1.  A buffer
(``expert_bias``) must have a zero first moment and come out of the step
bit for bit as it went in.

"""

from __future__ import annotations

import json
import os
import time

import numpy as np

# this body's operations, bytes and device scopes (``harness/model_scopes.py``)
from benchmarks.harness import lfm2_flops as body  # noqa: F401
from benchmarks.harness.cell import load_module
from benchmarks.harness.correctness import TIMEOUT, compare_grads
from benchmarks.reference import lfm2_moe as ref

# the driver beside this file, found as the harness finds a driver
hybrid_lm = load_module(
    "drivers", "hybrid_lm", os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_leaf, _with, log = hybrid_lm._leaf, hybrid_lm._with, hybrid_lm.log


def _norm(x) -> float:
    return float(np.sqrt(np.sum(np.square(x))))


class Driver(hybrid_lm.Driver):
    def __init__(self, run):
        # a program without this body stops here, before a cluster is built
        from parameter_server_tpu.models import lfm2_moe  # noqa: F401

        super().__init__(run)

    def model_config(self):
        from parameter_server_tpu.models.lfm2_moe import Lfm2MoeConfig

        cfg = self.run.config
        cut = dict(
            n_layers=cfg["n_layers"], layers_first=cfg["layers_first"],
            experts_held=cfg["experts_held"], experts_first=cfg["experts_first"],
        )
        model = dict(cfg["model"])
        if self.run.dry_run:  # tiny sizes: proves nothing
            model.update(cfg["dry_run"]["model"])
            model["layer_types"] = tuple(model["layer_types"])
        self.loss_chunk = model.pop("loss_chunk")
        self.peak_rate = model.pop("learning_rate")
        self.warmup_steps = model.pop("warmup_steps")
        #: the first step's rate, which ``grad_check`` holds the update to
        self.learning_rate = self.peak_rate / max(1, self.warmup_steps)
        return Lfm2MoeConfig.from_published(
            cfg, **{**cut, **model, "vocab_size": self.run.sizes["rows"]}
        )

    # -- the comparison that decides ``correct`` ----------------------------
    def grad_check(self):
        import jax
        import jax.numpy as jnp

        run, tr = self.run, self.trainer
        limits = dict(run.config["grad_check"])
        if run.dry_run:
            limits.update(run.config["dry_run"]["grad_check"])
        adam, named = limits["adamw"], limits["leaves"]
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
        # every token position's row and of the named parameter leaves
        blocks = {} if run.dry_run else limits["reference_blocks"]
        sz = ref.sizes_of(self.model, **blocks)

        def ref_loss(leaves, params, emb_b, tok_b):
            return ref.sequence_loss(sz, _with(params, leaves), emb_b, tok_b)

        ref_grad = jax.jit(jax.value_and_grad(ref_loss, argnums=(0, 2)))
        B = tokens.shape[0]
        before = {path: _leaf(tr.params, path) for path in named}
        theta0 = {path: np.asarray(x, np.float64) for path, x in before.items()}
        buffers = {
            "/".join(k.key for k in path): np.asarray(x)
            for path, x in jax.tree_util.tree_flatten_with_path(tr.params)[0]
            if path[-1].key in self.model.buffers
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
            np.asarray(g_emb), np.stack(want_emb), "embedding gradients",
            tokens.size, median=limits["median"], worst=limits["worst"],
        )
        # per position, the error over that position's own gradient.  A
        # position some layer's top-k flipped for reads of order 1 (a whole
        # expert's part present or absent) and swamps every norm over the
        # batch; the first quartile reads the positions that rounding alone
        # moved, so it is the reading that tells the precisions apart
        rows_want = np.stack(want_emb).reshape(tokens.size, -1)
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
        info["buffers"] = len(buffers)
        counters = {k: int(v) for k, v in counters.items()}
        info["counters"] = counters
        if counters.get("moe_dropped_slots"):
            fails.append(f"moe_dropped_slots = {counters['moe_dropped_slots']}")
        log(f"[grad_check] {json.dumps(info)}")
        return fails
