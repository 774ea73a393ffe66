"""Driver ``hybrid_lm``: a language model on the hybrid path.  The package's
``learner/hybrid.py::HybridLMTrainer`` trains the configuration's body (built
from the published keys of the configuration's file and its cut) on a 1 x 1
mesh of the cell's chip; the embedding rows of every batch are pulled from
and pushed to the cluster's ``KVServer``s as device arrays
(``device_replies=True``, an identity-localised table).

The loop is the program's: the driver hands ``trainer.step(tokens,
next_tokens=...)`` its batches under the step clock and calls ``drain()``
before ``train`` returns, so that the pushes the servers counted equal the
legs acknowledged.  A step that drops a token slot of a held expert
(``moe_dropped_slots``) retires the worker: the run is incorrect.

``grad_check`` compares, at the timed sizes and on the first batch, what the
jitted step the window runs does with ``reference/kimi_linear.py`` computed
from the trainer's own parameter arrays (no second copy), one sequence at a
time: the loss; the embedding gradient per token position (the forward and
the backward of every layer); the gradients of the parameter leaves the
configuration names (router, the held experts' gate, ``A_log`` and the
decay pair, ``W_kvb``, the head); and those leaves' change.  The step
returns no parameter gradient, and a second program that did would be a
third executable of the step's size: the step runs from a fresh optimizer
state, so its first moments are ``(1 - b1)`` times the gradients it took.
The leaves' change is held to AdamW computed in NumPy from those gradients:
an update that is not AdamW's at the stated rate, or a state or a parameter
the step left as it was, reads of order 1.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np

from benchmarks.harness import cluster as cluster_lib
# this body's operations, bytes and device scopes: ``harness/model_scopes.py``
# reads them through the driver the configuration names
from benchmarks.harness import flops_model as body  # noqa: F401
from benchmarks.harness.correctness import TIMEOUT, compare_grads
from benchmarks.harness.spans import spanned
from benchmarks.reference import kimi_linear as ref


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _leaf(tree, path: str):
    """The leaf of a nested dict at ``"a/b/c"``."""
    for key in path.split("/"):
        tree = tree[key]
    return tree


def _with(tree, leaves: dict):
    """``tree`` with the leaves at ``{"a/b/c": value}`` put in their place
    (the dicts on the way copied, nothing else)."""
    tree = dict(tree)
    for path, value in leaves.items():
        node, keys = tree, path.split("/")
        for key in keys[:-1]:
            node[key] = dict(node[key])
            node = node[key]
        node[keys[-1]] = value
    return tree


class Driver:
    def __init__(self, run):
        self.run = run
        self._losses = []
        self._retired = set()
        self.counters = []  # one dict a step, warm-up included

    # -- set-up -----------------------------------------------------------
    def model_config(self):
        from parameter_server_tpu.models.kimi_linear import KimiLinearConfig

        cfg = self.run.config
        cut = dict(
            n_layers=cfg["n_layers"], experts_held=cfg["experts_held"],
            experts_first=cfg["experts_first"],
        )
        model = dict(cfg["model"])
        if self.run.dry_run:  # tiny sizes: proves nothing
            model.update(cfg["dry_run"]["model"])
            for k in ("kda_layers", "full_attn_layers"):
                model[k] = tuple(model[k])
        self.loss_chunk = model.pop("loss_chunk")
        self.peak_rate = model.pop("learning_rate")
        self.warmup_steps = model.pop("warmup_steps")
        #: the first step's rate, which ``grad_check`` holds the update to
        self.learning_rate = self.peak_rate / max(1, self.warmup_steps)
        return KimiLinearConfig.from_published(
            cfg, **{**cut, **model, "vocab_size": self.run.sizes["rows"]}
        )

    def setup(self):
        import jax

        from parameter_server_tpu.learner.hybrid import HybridLMTrainer
        from parameter_server_tpu.parallel import mesh as mesh_lib

        run = self.run
        if run.dry_run:  # the tiny body's width is the tiny table's
            run.config = dict(run.config, table=dict(
                run.config["table"], dim=run.config["dry_run"]["table_dim"]
            ))
        self.table, self.cluster, self.batches, self.keys_of = (
            cluster_lib.cluster_and_batches(run, device_replies=True)
        )
        self.model = self.model_config()
        if self.model.hidden_size != self.table.dim:
            raise ValueError("the table's rows are not the body's width")
        (self.wid, self.kv), = self.cluster.workers.items()
        self.trainer = HybridLMTrainer(
            self.model, mesh_lib.make_mesh((1, 1), devices=[self.kv.device]),
            self.kv, table=self.table.name,
            learning_rate=self.peak_rate, warmup_steps=self.warmup_steps,
            max_delay=run.config["consistency"]["max_delay"],
            seed=run.seed % (1 << 31), push_timeout=TIMEOUT,
            loss_chunk=self.loss_chunk,
        )
        jax.block_until_ready(self.trainer.params)
        log(f"[setup] body: {self.trainer.n_body_params} parameters held, "
            f"{self.trainer.n_active_params} active, layers "
            f"{self.model.layer_kinds()}")
        return self.cluster

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
        # then the program the window runs, not a second one of 90 MB
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
        adam_state = tr.opt_state[0]
        if int(adam_state.count) != 0:
            return ["the optimizer state is not fresh: its first moments are "
                    "not the first step's gradients"]
        tr.params, tr.opt_state, loss, g_emb, counters = tr._step(
            tr.params, tr.opt_state, emb, tok
        )
        fails, info = compare_grads(
            np.asarray(g_emb), np.stack(want_emb), "embedding gradients",
            tokens.size, median=limits["median"], worst=limits["worst"],
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
        norm = lambda x: float(np.sqrt(np.sum(np.square(x))))  # noqa: E731
        info["leaves"], off, due = {}, 0.0, 0.0
        for path, limit in named.items():
            g = np.asarray(_leaf(tr.opt_state[0].mu, path), np.float64) / (
                1.0 - adam["b1"]
            )
            info["leaves"][path] = norm(g - want[path]) / (norm(want[path]) or 1.0)
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
        counters = {k: int(v) for k, v in counters.items()}
        info["counters"] = counters
        if counters.get("moe_dropped_slots"):
            fails.append(f"moe_dropped_slots = {counters['moe_dropped_slots']}")
        log(f"[grad_check] {json.dumps(info)}")
        return fails

    # -- the window -----------------------------------------------------------
    def _loop(self, clock, errors):
        tr = self.trainer
        try:
            mine = self.batches[clock.slot()]
            while True:
                i = clock.take()
                if i is None:
                    break
                loss = tr.step(
                    mine[i % len(mine)], next_tokens=mine[(i + 1) % len(mine)],
                    pull_timeout=TIMEOUT,
                )
                self._losses.append(loss)
                self.counters.append(dict(tr.counters))
                if i + 1 == clock.warmup_steps:
                    # the warm-up's last push is applied before the window
                    # opens (its server-side programs are warm-up's to load)
                    tr.wait_pushes()
                if tr.counters.get("moe_dropped_slots"):
                    self._retired.add(
                        f"{self.wid}: moe_dropped_slots = "
                        f"{tr.counters['moe_dropped_slots']} at step {i}"
                    )
                    log(f"[retired] {sorted(self._retired)}")
                    clock.finish(ok=False)
                    clock.abort()
                    break
            tr.drain()
        except BaseException as e:
            clock.finish(ok=False)
            self._retired.add(self.wid)
            errors.append(e)
            clock.abort()

    def train(self, clock):
        tr, kv = self.trainer, self.kv
        if self.run.trace:  # the benchmark's spans, from outside
            kv.pull_result_device = spanned(clock, "pull", kv.pull_result_device)
            kv.push_device = spanned(clock, "push", kv.push_device)
            tr._step = spanned(clock, "grad", tr._step, block=True)
        errors = []
        t = threading.Thread(
            target=self._loop, args=(clock, errors), name="bench-worker-0",
            daemon=True,
        )
        with cluster_lib.Heartbeats(self.cluster):
            t.start()
            t.join()
        if errors:
            raise errors[0]
        # every step's loss, warm-up included: where a window's loss does
        # not fall, this says what the steps before it did
        log("[losses] " + json.dumps([round(x, 4) for x in self._losses]))
        steps = [c for c in self.counters if c.get("moe_held_slots")]
        if not steps:
            return
        # the expert layers' load over the run's steps: for the readers of
        # the body (``harness/model_scopes.py``) and, as a file beside the
        # series, for its command
        run = self.run
        experts = self.model.experts_held * sum(
            "experts" in kinds for kinds in self.model.layer_kinds()
        )
        held = [c["moe_held_slots"] for c in steps]
        run.moe = {
            "steps": len(steps), "held_slots_mean": float(np.mean(held)),
            "held_slots_max": int(max(held)),
            "max_expert_slots": max(c["moe_max_expert_slots"] for c in steps),
            "load_max_over_mean_p50": float(np.median([
                c["moe_max_expert_slots"] * experts / c["moe_held_slots"]
                for c in steps
            ])),
            "dropped_slots": sum(c["moe_dropped_slots"] for c in self.counters),
        }
        log("[moe] " + json.dumps(run.moe))
        out = os.path.join(run.bench_dir, "out", "series")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(
            out, f"{run.name}.seed{run.seed}.trace{run.trace}.moe.json"
        ), "w") as f:
            json.dump(run.moe, f)

    def loss_count(self):
        return len(self._losses)

    def losses(self):
        return list(self._losses)

    def retired(self):
        return set(self._retired)

    def close(self):
        self.cluster.close()
