"""Driver ``lr_elastic``: sparse logistic regression through the program's
``ElasticTrainer`` over ``KVWorker``s, ``KVServer``s and the metered loopback
van, wired as ``app._build_async_lr`` wires them and fed by the benchmark's
own shards.

The trainer iterates ``for keys, labels in wl.payload``, so a payload that
is the benchmark's iterator sees one ``next()`` per step: that is the step
clock, and the trainer needs no change.  Shards hold 4 batches as the app's
do; far more are provisioned than a window needs, and once the clock has
passed for every worker the remaining payloads yield nothing and the pool
drains at once.  Each ``iter(payload)`` is a fresh iterator, so a shard the
pool hands to a second worker as a straggler duplicate shares no state."""

from __future__ import annotations


import numpy as np

from benchmarks.harness import cluster as cluster_lib
from benchmarks.harness.correctness import TIMEOUT, compare_grads
from benchmarks.harness.spans import span_workers, spanned
from benchmarks.reference import lr as lr_ref

BATCHES_PER_SHARD = 4  # app._build_async_lr's
SHARDS = 4096  # 16,384 steps: no window at any size here needs as many


class _ShardIter:
    def __init__(self, drv):
        self.drv, self.n = drv, 0

    def __iter__(self):
        return self

    def __next__(self):
        clock = self.drv.clock
        if self.n == BATCHES_PER_SHARD:
            clock.finish()  # the shard's last step ends here, not in the pool
            raise StopIteration
        i = clock.take()
        if i is None:
            raise StopIteration
        self.n += 1
        mine = self.drv.batches[clock.slot()]
        return mine[i % len(mine)]


class _Shard:
    def __init__(self, drv):
        self.drv = drv

    def __iter__(self):
        return _ShardIter(self.drv)


class Driver:
    def __init__(self, run):
        self.run = run
        self.clock = None
        self.trainer = None
        self._unpatch = None

    def setup(self):
        self.table, self.cluster, self.batches, self.keys_of = (
            cluster_lib.cluster_and_batches(self.run)
        )
        return self.cluster

    def grad_check(self):
        """The first step's gradient rows on 256 examples, program against
        NumPy, from the weights the system serves."""
        import jax

        from parameter_server_tpu.models import linear

        keys, labels = self.batches[0][0]
        keys, labels = keys[:256], labels[:256]
        kv = next(iter(self.cluster.workers.values()))
        w_pos = kv.pull_sync(self.table.name, keys, timeout=TIMEOUT)
        g, _gb, loss = linear.grad_rows(
            jax.device_put(w_pos, kv.device), jax.device_put(labels, kv.device)
        )
        want, want_loss = lr_ref.grad_rows(w_pos, labels)
        # elementwise float32 on both sides: no example is free
        fails, _info = compare_grads(
            np.asarray(g), want, "lr gradient rows", labels.shape[0],
            median=1e-5, worst=1e-5,
        )
        if abs(float(loss) - want_loss) > 1e-5 * max(1.0, abs(want_loss)):
            fails.append(f"lr loss {float(loss)} against {want_loss}")
        return fails

    def train(self, clock):
        from parameter_server_tpu.learner.elastic import ElasticTrainer
        from parameter_server_tpu.models import linear

        self.clock = clock
        if self.run.trace:
            span_workers(clock, self.cluster.workers.values())
            original = linear.grad_rows
            linear.grad_rows = spanned(clock, "grad", original, block=True)
            self._unpatch = lambda: setattr(linear, "grad_rows", original)
        self.trainer = ElasticTrainer(
            self.cluster.workers,
            self.cluster.sched,
            [_Shard(self) for _ in range(SHARDS)],
            cluster_lib.consistency_config(self.run.config["consistency"]),
            managers=self.cluster.managers,
            table=self.table.name,
            timeout=TIMEOUT,
        )
        try:
            self.trainer.run()
        finally:
            if self._unpatch is not None:
                self._unpatch()

    def loss_count(self):
        return len(self.trainer.losses) if self.trainer is not None else 0

    def losses(self):
        return list(self.trainer.losses)

    def retired(self):
        return set(self.trainer._killed)

    def close(self):
        self.cluster.close()
