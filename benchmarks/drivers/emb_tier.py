"""Driver ``emb_tier``: the embedding tier of a DLRM served by the parameter
server.  Each worker thread loops ``KVWorker.pull_sync`` -> forward and
backward of ``models/dlrm.py::DLRM`` on the pulled rows on its device ->
``KVWorker.push_sync`` of the row gradients.  The dense MLPs are small and
stay replicated on the worker, updated locally.

The package has no PS-path DLRM learner (ROADMAP R0: "registers ``dlrm`` in
``app.py``" is a program change), so this loop is the benchmark's own client
of the KVWorker API, composed as ``ElasticTrainer`` composes its own: the
worker-side ``ConsistencyController`` holds a worker that is ``max_delay``
iterations ahead (on a condition variable; without it the leader would sit
at the servers' gate and retry every few milliseconds, which slows both),
and heartbeats and the scheduler's monitor run beside the loops."""

from __future__ import annotations

import json
import sys
import threading

import numpy as np

from benchmarks.harness import cluster as cluster_lib
from benchmarks.harness.correctness import TIMEOUT, compare_grads
from benchmarks.harness.spans import span_workers, spanned
from benchmarks.reference import dlrm as dlrm_ref


def _layers(mlp_params):
    """flax ``MLP`` params -> ``[(W, b), ...]`` in layer order."""
    names = sorted(mlp_params, key=lambda n: int(n.rsplit("_", 1)[1]))
    return [
        (np.asarray(mlp_params[n]["kernel"]), np.asarray(mlp_params[n]["bias"]))
        for n in names
    ]


class Driver:
    def __init__(self, run):
        self.run = run
        self._losses = []
        self._lock = threading.Lock()
        self._retired = set()

    def setup(self):
        import jax
        import optax

        from parameter_server_tpu.models.dlrm import DLRM
        from parameter_server_tpu.models.linear import logloss

        run, cfg = self.run, self.run.config
        self.table, self.cluster, self.batches, self.keys_of = (
            cluster_lib.cluster_and_batches(run)
        )
        params = cfg["generator_params"]

        m = cfg["model"]
        dim, n_sparse = self.table.dim, params["n_sparse"]
        if m["bottom_mlp"][-1] != dim or m["top_mlp"][-1] != 1:
            raise ValueError("bottom MLP must end at the row width, top at 1")
        model = DLRM(
            bottom_mlp=tuple(m["bottom_mlp"][:-1]),
            top_mlp=tuple(m["top_mlp"][:-1]), emb_dim=dim,
        )
        tx = optax.sgd(m["mlp_learning_rate"])

        def loss_fn(p, emb, dense, labels):
            return logloss(model.apply({"params": p}, dense, emb), labels)

        def step(p, opt_state, dense, rows, labels):
            emb = rows.reshape(labels.shape[0], n_sparse, dim)
            loss, (gp, gemb) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
                p, emb, dense, labels
            )
            updates, opt_state = tx.update(gp, opt_state, p)
            return loss, gemb.reshape(-1, dim), optax.apply_updates(p, updates), opt_state

        self.step = jax.jit(step, donate_argnums=(0, 1))

        def init(key):
            p = model.init(
                key, np.zeros((1, params["n_dense"]), np.float32),
                np.zeros((1, n_sparse, dim), np.float32),
            )["params"]
            return p, tx.init(p)

        # every worker starts from the same weights, made on its own device
        # in one jitted call from the seed
        key = jax.random.PRNGKey(run.seed % (1 << 31))
        make = jax.jit(init)

        def make_mlp(kv):
            with jax.default_device(kv.device):
                return jax.device_put(make(key), kv.device)

        self.make_mlp = make_mlp
        self.mlp = {wid: make_mlp(kv) for wid, kv in self.cluster.workers.items()}
        return self.cluster

    def grad_check(self):
        """The first batch through ``self.step``, the jitted program the
        window runs, at the precision the window runs it: its loss and its
        row gradients against float32 NumPy, within the limits the
        configuration's ``grad_check`` group fits to its stated
        ``compute_precision``.  The step donates its MLP, so it is handed a
        second copy from the same seed; the workers' own stay untouched."""
        import jax

        keys, dense, labels = self.batches[0][0]
        kv = next(iter(self.cluster.workers.values()))
        rows = kv.pull_sync(self.table.name, keys, timeout=TIMEOUT)
        p, opt_state = self.make_mlp(kv)
        host = jax.device_get(p)
        loss, g_rows, _p, _opt = self.step(
            p, opt_state, *(jax.device_put(a, kv.device)
                            for a in (dense, rows, labels))
        )
        emb = np.asarray(rows).reshape(*keys.shape, self.table.dim)
        want_loss, want = dlrm_ref.loss_and_row_grads(
            _layers(host["MLP_0"]), _layers(host["MLP_1"]), dense, emb, labels
        )
        limits = self.run.config["grad_check"]
        fails, info = compare_grads(
            np.asarray(g_rows).reshape(want.shape), want, "dlrm row gradients",
            labels.shape[0], median=limits["median"], worst=limits["worst"],
        )
        info["loss"] = abs(float(loss) - want_loss) / max(1.0, abs(want_loss))
        print(f"[grad_check] {json.dumps(info)}", file=sys.stderr, flush=True)
        if info["loss"] > limits["loss"]:
            fails.append(f"dlrm loss {float(loss)} against {want_loss}")
        return fails

    def _loop(self, clock, slot, wid, kv, errors):
        import jax

        control, iteration = self.controller, 0
        p, opt_state = self.mlp[wid]
        name, step = self.table.name, self.step
        if self.run.trace:
            step = spanned(clock, "grad", step, block=True)
        try:
            mine = self.batches[clock.slot()]
            while True:
                i = clock.take()
                if i is None:
                    return
                keys, dense, labels = mine[i % len(mine)]
                if not control.wait_turn(slot, iteration, timeout=TIMEOUT):
                    raise TimeoutError(f"{wid} stalled (SSP bound)")
                rows = kv.pull_sync(name, keys, timeout=TIMEOUT)
                loss, g_rows, p, opt_state = step(
                    p, opt_state, jax.device_put(dense, kv.device),
                    jax.device_put(rows, kv.device),
                    jax.device_put(labels, kv.device),
                )
                # the device array goes in: its D2H is part of the push
                kv.push_sync(name, keys, g_rows, timeout=TIMEOUT)
                control.finish_iteration(slot)
                iteration += 1
                loss = float(loss)
                with self._lock:
                    self._losses.append(loss)
        except BaseException as e:
            clock.finish(ok=False)
            self._retired.add(wid)
            errors.append(e)
            clock.abort()
        finally:
            control.mark_dead(slot)  # a stopped clock must not hold the others

    def train(self, clock):
        from parameter_server_tpu.core.clock import ConsistencyController

        self.controller = ConsistencyController(
            cluster_lib.consistency_config(self.run.config["consistency"]),
            len(self.cluster.workers),
        )
        if self.run.trace:
            span_workers(clock, self.cluster.workers.values())
        for kv in self.cluster.workers.values():
            kv.consist_hello(table=self.table.name, timeout=TIMEOUT)
        errors = []
        threads = [
            threading.Thread(
                target=self._loop, args=(clock, slot, wid, kv, errors),
                name=f"bench-worker-{slot}", daemon=True,
            )
            for slot, (wid, kv) in enumerate(self.cluster.workers.items())
        ]
        with cluster_lib.Heartbeats(self.cluster):
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        if errors:
            raise errors[0]

    def loss_count(self):
        return len(self._losses)

    def losses(self):
        return list(self._losses)

    def retired(self):
        return set(self._retired)

    def close(self):
        self.cluster.close()
