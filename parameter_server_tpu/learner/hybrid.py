"""Hybrid LM trainer: PS-served embeddings + GSPMD-synchronous transformer.

BASELINE config #5 as specified ("Llama-3 8B hybrid PS-embeddings + XLA
allreduce transformer", SURVEY.md §7 step 7; the composition VERDICT r1
flagged missing): ONE training step combines both planes —

- **embedding rows ride the Van**: pulled from / pushed to a
  :class:`~parameter_server_tpu.kv.server.KVServer` through
  :class:`~parameter_server_tpu.kv.worker.KVWorker` (async timestamps,
  filter-capable, DCN-routable, elastic) with an
  :class:`~parameter_server_tpu.utils.keys.IdentityLocalizer` so token id ==
  table row (the reference's key-range partition over the vocabulary);
- **the dense body is synchronous GSPMD**: batch sharded over the mesh's
  ``data`` axis, params TP-sharded per ``parallel/tp.py``; XLA inserts the
  gradient allreduce (the "NCCL allreduce" half of the config).

Why this split scales: the embedding table is the memory giant (Llama-3 8B:
128k x 4096 x 4 B = 2.1 GB plus optimizer rows — and DLRM-class tables are
100x that) with *sparse* per-step access (only the batch's unique tokens),
exactly the PS access pattern; the body is dense compute, exactly the GSPMD
pattern.  Serving rows from PS also admits staleness: pushes are not waited
on individually but bounded by a delay window τ (SSP; τ=0 = BSP), so
embedding traffic overlaps body compute — the reference's bounded-delay
pipelining (``Task.wait_time``) applied to the embedding plane.
"""

from __future__ import annotations

import collections
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec

from parameter_server_tpu.config import OptimizerConfig, TableConfig
from parameter_server_tpu.kv.worker import KVWorker
from parameter_server_tpu.parallel import mesh as mesh_lib
from parameter_server_tpu.parallel.tp import place_params
from parameter_server_tpu.utils import metrics as metrics_lib
from parameter_server_tpu.utils.keys import IdentityLocalizer
from parameter_server_tpu.utils.trace import NULL_TRACER


def embedding_table_cfg(
    cfg,
    *,
    learning_rate: float = 0.05,
    optimizer: str = "adagrad",
) -> TableConfig:
    """KV table config for the PS-served embedding: row per token id."""
    return TableConfig(
        name="emb",
        rows=cfg.vocab_size,
        dim=cfg.d_model,
        optimizer=OptimizerConfig(kind=optimizer, learning_rate=learning_rate),
        init_scale=0.02,  # normal(0.02) rows, matching the dense init
    )


def embedding_localizers(cfg) -> Dict[str, object]:
    """Localizer map for :class:`KVWorker`: identity (token id == row)."""
    return {"emb": IdentityLocalizer(cfg.vocab_size)}


class HybridLMTrainer:
    """One step = Van pull (rows) -> GSPMD body fwd/bwd -> Van push (grads).

    ``cfg`` is the body's model config, whose ``hybrid_body(seed,
    loss_chunk)`` builds what is trained: a ``TransformerConfig`` (one kind
    of block), a ``KimiLinearConfig`` (a layer pattern of delta-rule and
    latent-attention mixers, dense and expert MLPs), an ``Lfm2MoeConfig``
    (gated short convolutions and grouped-query attention, dense and
    bias-selected expert MLPs) or a ``LagunaConfig`` (window and full
    attention layers with their own head counts and rotary tables, a gated
    attention output, dense and expert MLPs with a shared expert).

    **Buffers.**  A body may hold leaves that are state and not weights (an
    expert layer's selection bias): its config names them (``cfg.buffers``,
    leaf names), they ride in the parameter tree, and the step gives them no
    update: AdamW's weight decay would otherwise move a non-zero one.

    ``max_delay``: how many embedding pushes may be in flight before the
    next step blocks on the oldest ack (τ of SSP; 0 = BSP, every push
    waited before the next pull).
    """

    def __init__(
        self,
        cfg,
        mesh,
        worker: KVWorker,
        *,
        table: str = "emb",
        learning_rate: float = 1e-3,
        warmup_steps: int = 0,
        max_delay: int = 0,
        seed: int = 0,
        dashboard: Optional[metrics_lib.Dashboard] = None,
        push_timeout: float = 60.0,
        tracer=None,
        loss_chunk: int = 0,
    ) -> None:
        """``loss_chunk > 0`` fuses the lm_head into the rematerialized
        chunked loss (``chunked_causal_lm_loss``): the f32 [B, S, vocab]
        logits never materialize — one of the three knobs (with
        ``cfg.scan_blocks`` and ``cfg.remat``) that fit the 8B body on a
        v5e-16 (see ``parallel/feasibility.py``).

        ``warmup_steps > 1``: the body's rate rises linearly, step ``t``
        (from 0) taking ``learning_rate * (t + 1) / warmup_steps`` until it
        stands at ``learning_rate``."""
        if cfg.tie_embeddings:
            raise ValueError(
                "hybrid requires untied embeddings: the lm_head is dense "
                "(GSPMD), the input table is PS-served"
            )
        self.cfg = cfg
        self.mesh = mesh
        self.worker = worker
        self.table = table
        self.max_delay = max_delay
        self.push_timeout = push_timeout
        self.dashboard = metrics_lib.trainer_dashboard(
            dashboard, mesh.devices.size
        )
        if warmup_steps > 1:
            learning_rate = optax.linear_schedule(
                learning_rate / warmup_steps, learning_rate, warmup_steps - 1
            )
        self.tx = optax.adamw(learning_rate)
        params, loss_fn, self._logits, self.n_active_params, scope = (
            cfg.hybrid_body(seed, loss_chunk)
        )
        buffers = frozenset(getattr(cfg, "buffers", ()))
        self.params = place_params(params, mesh)
        del params
        # the optimizer state placed like the parameters it shadows (what has
        # no mesh sharding yet, the step count, replicated), and the step's
        # outputs pinned to the same shardings: a step's outputs are the
        # next step's inputs, and left to the compiler they came back under
        # other specs than ``place_params`` gave, so the second call
        # compiled the whole step a second time
        replicated = NamedSharding(mesh, PartitionSpec())
        param_sh = jax.tree.map(lambda x: x.sharding, self.params)
        self.opt_state = self.tx.init(self.params)
        opt_sh = jax.tree.map(
            lambda x: x.sharding if isinstance(x.sharding, NamedSharding)
            else replicated,
            self.opt_state,
        )
        self.opt_state = jax.device_put(self.opt_state, opt_sh)
        self._batch3 = mesh_lib.batch_sharding(mesh, 3)
        self._batch2 = mesh_lib.batch_sharding(mesh, 2)
        self._inflight: collections.deque[int] = collections.deque()
        #: (pull_ts, tokens) announced via ``step(next_tokens=...)``
        self._prefetch: Optional[tuple] = None
        self.tracer = tracer or NULL_TRACER
        self.step_count = 0
        #: what the last step counted beside its loss (``models/moe.py::COUNTERS``
        #: of a body with experts: held, dropped and the fullest expert's
        #: token slots; empty otherwise), as ints
        self.counters: Dict[str, int] = {}
        #: the body's loss: ``loss_fn(params, emb_in, targets) -> (loss,
        #: counters)``; what ``step`` differentiates
        self.loss_fn = loss_fn
        tx = self.tx
        batch3 = self._batch3

        def step_fn(params, opt_state, emb_in, targets):
            # grads w.r.t. (params, emb_in): the emb_in gradient is what
            # flows back to the PS table as per-position row updates
            # one device scope holds the whole step (the body's own scopes
            # and the optimizer's nest in it), so that an operation the
            # compiler made is still the step's in a trace
            with jax.named_scope(scope):
                (loss, counters), grads = jax.value_and_grad(
                    loss_fn, argnums=(0, 1), has_aux=True
                )(params, emb_in, targets)
                g_params, g_emb = grads
                # pin the embedding gradient to the batch sharding: each pod
                # host then extracts exactly ITS batch rows from addressable
                # shards for the local Van push (no cross-host gather)
                g_emb = jax.lax.with_sharding_constraint(g_emb, batch3)
                with jax.named_scope("ps.model.optimizer"):
                    updates, opt_state = tx.update(g_params, opt_state, params)
                    if buffers:  # a buffer takes no update, weight decay included
                        updates = jax.tree_util.tree_map_with_path(
                            lambda path, u: jnp.zeros_like(u)
                            if path[-1].key in buffers else u,
                            updates,
                        )
                    params = optax.apply_updates(params, updates)
            return params, opt_state, loss, g_emb, counters

        self._step = jax.jit(
            step_fn, donate_argnums=(0, 1),
            out_shardings=(param_sh, opt_sh, None, None, None),
        )
        #: body parameter count (held here).  ``n_active_params`` is what the
        #: MFU column's 6ND rule takes (fwd+bwd train FLOPs ~ 6 x params a
        #: token multiplies with x tokens): equal to it for a dense body, less
        #: for one with experts.
        self.n_body_params = sum(
            int(np.prod(p.shape)) for p in jax.tree.leaves(self.params)
        )

    def _local_batch_rows(self, arr: jax.Array, sl: slice) -> np.ndarray:
        """This process's rows ``[sl]`` of a batch-sharded global array.

        Reads only addressable shards (no cross-host transfer): the array is
        constrained to the batch sharding, whose data-axis layout is
        process-major — a host's devices hold exactly its batch slice
        (model-axis replicas repeat rows; idempotent overwrite).
        """
        shape = (sl.stop - sl.start,) + tuple(arr.shape[1:])
        out = np.zeros(shape, np.float32)
        for shard in arr.addressable_shards:
            r = shard.index[0]
            start = 0 if r.start is None else int(r.start)
            stop = arr.shape[0] if r.stop is None else int(r.stop)
            # a non-process-major data-axis layout would put addressable
            # rows OUTSIDE this process's slice; the Python slice below
            # would then silently write wrong rows — fail loudly instead
            # (ADVICE r4)
            if not (sl.start <= start and stop <= sl.stop):
                raise AssertionError(
                    f"addressable shard rows [{start}, {stop}) fall outside "
                    f"this process's batch slice [{sl.start}, {sl.stop}) — "
                    "mesh data-axis layout is not process-major"
                )
            out[start - sl.start : stop - sl.start] = np.asarray(shard.data)
        return out

    # -- the hybrid hot path -------------------------------------------------
    def step(
        self,
        tokens: np.ndarray,
        *,
        next_tokens: Optional[np.ndarray] = None,
        pull_timeout: float = 60.0,
    ) -> float:
        """tokens [B, S] -> loss.  Van pull + GSPMD step + Van push.

        Device-resident embedding plane (VERDICT r2 #2): rows arrive as
        device arrays (``pull_result_device``) and gradients leave as device
        arrays (``push_device``) — the only host traffic is the int32 token
        ids.  Pass ``next_tokens`` to PREFETCH the following step's rows:
        the pull is issued right after this step's body dispatch, so its Van
        latency hides behind device compute exactly like the push τ window
        hides ack latency (pulls get the same overlap pushes have).
        """
        tokens = np.asarray(tokens)
        with self.tracer.span("ps.hybrid.step", tokens=int(tokens.size)) as sp:
            if sp.recording:  # nothing to count for when nothing records
                sp.set(unique=int(np.unique(tokens).size))
            return self._step_traced(tokens, next_tokens, pull_timeout)

    def _step_traced(self, tokens, next_tokens, pull_timeout) -> float:
        # Dual-plane pod shape (VERDICT r3 #2): when the GSPMD mesh spans OS
        # processes, each process owns its local_batch_slice of the global
        # batch end to end — pulls only its rows' embeddings over ITS Van
        # connection, feeds them to its own devices
        # (make_array_from_process_local_data), and later pushes only its
        # rows' gradients.  Single-process runs keep the device-resident
        # reply path.
        multiproc = jax.process_count() > 1
        if multiproc:
            from parameter_server_tpu.parallel import distributed

            sl = distributed.local_batch_slice(
                jax.process_index(), jax.process_count(), tokens.shape[0]
            )
            tokens_feed = tokens[sl]
        else:
            sl = slice(0, tokens.shape[0])
            tokens_feed = tokens
        # 1) PS plane: this batch's embedding rows — from the prefetch if
        # step(t-1) announced them, else pulled synchronously now
        ts = None
        if self._prefetch is not None:
            pts, ptok = self._prefetch
            self._prefetch = None
            if ptok.shape == tokens.shape and np.array_equal(ptok, tokens):
                ts = pts
            else:  # caller deviated from the announced batch: drain + repull
                self.worker.pull_result(pts, timeout=pull_timeout)
        if ts is None:
            ts = self.worker.pull(self.table, tokens_feed)
        if multiproc:
            from parameter_server_tpu.parallel import distributed

            with self.tracer.span("ps.hybrid.pull_wait"):
                emb_local = self.worker.pull_result(ts, timeout=pull_timeout)
            emb_d = distributed.host_local_batch(
                self._batch3,
                np.asarray(emb_local, np.float32),
                (tokens.shape[0], tokens.shape[1], self.cfg.d_model),
            )
            tok_d = distributed.host_local_batch(
                self._batch2,
                np.ascontiguousarray(tokens_feed.astype(np.int32)),
                tokens.shape,
            )
        else:
            with self.tracer.span("ps.hybrid.pull_wait"):
                emb_in = self.worker.pull_result_device(
                    ts, timeout=pull_timeout
                )
            emb_d = jax.device_put(
                jnp.asarray(emb_in, jnp.float32), self._batch3
            )
            tok_d = jax.device_put(jnp.asarray(tokens, jnp.int32), self._batch2)
        # 2) dense plane: synchronous GSPMD body step (XLA allreduce).
        # Single-process: dispatch is async — the arrays below are futures,
        # so the prefetch and push issue while the body still runs on
        # device.  Multi-process: _local_batch_rows below must block on the
        # device step to read g_emb shards, so push/prefetch issue AFTER
        # device compute there (the overlap window is the Van RTT against
        # the NEXT step's host work, not against this body step).
        with self.tracer.span("ps.hybrid.body_dispatch"):
            self.params, self.opt_state, loss, g_emb, counters = self._step(
                self.params, self.opt_state, emb_d, tok_d
            )
        # 3) PS plane: push per-position embedding gradients device-to-device
        # (server-side optimizer applies them); bounded-delay, not per-push
        # blocking.  Push MUST precede the prefetch pull: both are async
        # submits, and per-link FIFO then guarantees the prefetched rows
        # include this step's update (pull-before-push would silently hand
        # back one-update-stale rows even at max_delay=0).
        with self.tracer.span("ps.hybrid.push_submit"):
            if multiproc:
                g_local = self._local_batch_rows(g_emb, sl)
                ts = self.worker.push(
                    self.table,
                    tokens_feed.reshape(-1),
                    g_local.reshape(-1, self.cfg.d_model),
                )
            else:
                ts = self.worker.push_device(
                    self.table,
                    tokens.reshape(-1),
                    g_emb.reshape(-1, self.cfg.d_model),
                )
        # 4) prefetch the NEXT batch's rows while the body computes
        if next_tokens is not None:
            next_tokens = np.asarray(next_tokens)
            if multiproc:
                from parameter_server_tpu.parallel import distributed

                # slice by the NEXT batch's size (it may differ from this
                # step's), not this step's sl
                nsl = distributed.local_batch_slice(
                    jax.process_index(),
                    jax.process_count(),
                    next_tokens.shape[0],
                )
            else:
                nsl = slice(0, next_tokens.shape[0])
            with self.tracer.span("ps.hybrid.prefetch"):
                self._prefetch = (
                    self.worker.pull(self.table, next_tokens[nsl]),
                    next_tokens,
                )
        self._inflight.append(ts)
        while len(self._inflight) > self.max_delay:
            old = self._inflight.popleft()
            if not self.worker.wait(old, timeout=self.push_timeout):
                raise TimeoutError(f"embedding push ts={old} not acked")
        self.step_count += 1
        with self.tracer.span("ps.hybrid.loss_sync"):
            loss_f = float(loss)
            self.counters = {k: int(v) for k, v in counters.items()}
        emb_mb = tokens.size * self.cfg.d_model * 4 * 2 / 1e6  # pull + push
        # one example = one sequence: 6 x active body params x seq tokens
        self.dashboard.flops_per_example = (
            6.0 * self.n_active_params * tokens.shape[1]
        )
        self.dashboard.record(
            self.step_count,
            loss_f,
            examples=tokens.shape[0],
            extra={"emb_plane_mb": round(emb_mb, 3)},
        )
        return loss_f

    def wait_pushes(self) -> None:
        """Block until every in-flight embedding push is acked; an announced
        prefetch stays announced (the next ``step`` still finds it)."""
        while self._inflight:
            old = self._inflight.popleft()
            if not self.worker.wait(old, timeout=self.push_timeout):
                raise TimeoutError(f"embedding push ts={old} not acked")

    def drain(self) -> None:
        """Block until every in-flight embedding push is acked (epoch end).

        Also consumes a dangling announced prefetch — otherwise its kept
        responses (full embedding-row arrays under ``device_replies``) stay
        pinned in the Customer for the process lifetime.
        """
        self.wait_pushes()
        if self._prefetch is not None:
            pts, _ptok = self._prefetch
            self._prefetch = None
            self.worker.pull_result(pts, timeout=self.push_timeout)

    # -- checkpoint/resume for the WHOLE config-#5 state --------------------
    # The embedding plane already checkpoints through the PS machinery
    # (KVWorker.save_model -> per-server shards + manifest); the body's
    # params/adamw moments are the missing half.  Both planes commit under
    # one step so a resumed run is consistent across them.
    def save(self, root: str, step: int, *, timeout: float = 600.0) -> None:
        """Checkpoint emb table (PS shards) + body params/opt (npz)."""
        import os

        self.drain()  # every push applied before the server shards snapshot
        self.worker.save_model(root, step, timeout=timeout)
        flat = {}
        for i, leaf in enumerate(jax.tree.leaves(self.params)):
            flat[f"p{i}"] = self._full_host(leaf)
        for i, leaf in enumerate(jax.tree.leaves(self.opt_state)):
            flat[f"o{i}"] = self._full_host(leaf)
        if jax.process_index() == 0:
            path = os.path.join(root, f"hybrid_body_{step:06d}.npz")
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                np.savez(f, **flat)
            os.replace(tmp, path)
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices(f"hybrid-ckpt-{step}")

    def restore(self, root: str, step: int, *, timeout: float = 600.0) -> None:
        """Restore both planes; the trainer continues mid-trajectory."""
        import os

        self.worker.load_model(root, step, timeout=timeout)
        path = os.path.join(root, f"hybrid_body_{step:06d}.npz")
        with np.load(path) as z:
            p_leaves = jax.tree.leaves(self.params)
            o_leaves = jax.tree.leaves(self.opt_state)
            new_p = [
                jax.device_put(z[f"p{i}"], leaf.sharding)
                for i, leaf in enumerate(p_leaves)
            ]
            new_o = [
                jax.device_put(
                    np.asarray(z[f"o{i}"], jax.tree.leaves(self.opt_state)[i].dtype),
                    leaf.sharding,
                )
                for i, leaf in enumerate(o_leaves)
            ]
        self.params = jax.tree.unflatten(
            jax.tree.structure(self.params), new_p
        )
        self.opt_state = jax.tree.unflatten(
            jax.tree.structure(self.opt_state), new_o
        )

    @staticmethod
    def _full_host(leaf) -> np.ndarray:
        """Host copy of a (possibly multi-process sharded) array."""
        if jax.process_count() > 1 and not leaf.is_fully_addressable:
            from jax.experimental import multihost_utils

            return np.asarray(
                multihost_utils.process_allgather(leaf, tiled=True)
            )
        return np.asarray(leaf)

    def logits(self, tokens: np.ndarray, *, pull_timeout: float = 60.0):
        tokens = np.asarray(tokens)
        emb_in = self.worker.pull_sync(self.table, tokens, timeout=pull_timeout)
        return np.asarray(
            self._logits(self.params, jnp.asarray(emb_in, jnp.float32))
        )
