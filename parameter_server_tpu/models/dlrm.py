"""DLRM / Wide&Deep — BASELINE config #3 (billion-row sparse embeddings).

Architecture (standard DLRM): dense features -> bottom MLP; categorical
features -> embedding rows from the PS table; pairwise dot-product feature
interactions; top MLP -> CTR logit.

The embedding table is the parameter-server table: row-sharded over the
``model`` mesh axis (the reference's key-range server partition — and the EP
analogue called out in SURVEY.md §2: embedding shards ARE the expert shards).
The train step differentiates w.r.t. the *gathered unique rows* — XLA's AD
turns the ``rows[inverse]`` indexing into the duplicate-combining segment-sum
(the reference's ParallelOrderedMatch merge) — and the row-wise ServerOptimizer
applies the sparse update, so per-step memory is O(batch), never O(table).
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh

from parameter_server_tpu.config import TableConfig
from parameter_server_tpu.kv.optim import ServerOptimizer, make_optimizer
from parameter_server_tpu.models.linear import logloss
from parameter_server_tpu.ops import scatter
from parameter_server_tpu.parallel import mesh as mesh_lib
from parameter_server_tpu.utils.keys import HashLocalizer, localize_to_slots


class MLP(nn.Module):
    features: Sequence[int]
    final_activation: bool = True

    @nn.compact
    def __call__(self, x):
        for i, f in enumerate(self.features):
            x = nn.Dense(f)(x)
            if i < len(self.features) - 1 or self.final_activation:
                x = nn.relu(x)
        return x


class DLRM(nn.Module):
    """Dense part of DLRM: bottom MLP, interactions, top MLP.

    The embedding rows come in as an argument (they live in the PS table).
    """

    bottom_mlp: Sequence[int]
    top_mlp: Sequence[int]
    emb_dim: int

    @nn.compact
    def __call__(self, dense_feats: jax.Array, emb: jax.Array) -> jax.Array:
        """dense_feats [B, n_dense]; emb [B, n_sparse, emb_dim] -> logits [B]."""
        with jax.named_scope("ps.model.dlrm"):
            bottom = MLP(tuple(self.bottom_mlp) + (self.emb_dim,))(dense_feats)
            feats = jnp.concatenate([bottom[:, None, :], emb], axis=1)  # [B, F, D]
            inter = jnp.einsum(
                "bfd,bgd->bfg", feats, feats,
                preferred_element_type=jnp.float32,
            )
            f = feats.shape[1]
            iu, ju = jnp.triu_indices(f, k=1)
            inter_flat = inter[:, iu, ju]  # [B, F*(F-1)/2]
            top_in = jnp.concatenate([bottom, inter_flat], axis=1)
            logits = MLP(
                tuple(self.top_mlp) + (1,), final_activation=False
            )(top_in)
            return logits[:, 0]


def make_dlrm_step(
    table_cfg: TableConfig,
    mesh: Mesh,
    model: DLRM,
    optimizer: ServerOptimizer,
    tx,
    n_sparse: int,
):
    """Build the jitted DLRM train step over a (data, model) mesh.

    Factored out of ``SpmdDLRMTrainer`` so the billion-row feasibility path
    (VERDICT r4 #3) can AOT-compile the REAL step from ShapeDtypeStructs —
    a 2^30-row table is never materialized on a dev box, exactly like the
    8B body in ``parallel/feasibility.py``.

    Returns ``(jitted_step, shardings)`` where shardings carry the input
    layout: table row-sharded over ``model`` (the reference's key-range
    server partition), MLP replicated, batch over ``data``, unique slot
    ids replicated.
    """
    t_shard = mesh_lib.table_sharding(mesh)
    repl = mesh_lib.replicated(mesh)
    batch2 = mesh_lib.batch_sharding(mesh, 2)
    batch1 = mesh_lib.batch_sharding(mesh, 1)
    state_keys = sorted(optimizer.state_shapes())
    trash = table_cfg.rows  # trash row id (pads live past it)

    def step_fn(
        emb_value, emb_state, mlp_params, opt_state,
        ids, inverse, dense_feats, labels,
    ):
        batch = labels.shape[0]
        v_rows = scatter.gather_rows(emb_value, ids)
        s_rows = {k: scatter.gather_rows(v, ids) for k, v in emb_state.items()}
        w_rows = optimizer.pull_weights(v_rows, s_rows)

        def loss_fn(mlp_p, rows):
            emb = rows[inverse].reshape(batch, n_sparse, -1)
            logits = model.apply({"params": mlp_p}, dense_feats, emb)
            return logloss(logits, labels)

        l, (g_mlp, g_rows) = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            mlp_params, w_rows
        )
        updates, opt_state = tx.update(g_mlp, opt_state, mlp_params)
        mlp_params = optax.apply_updates(mlp_params, updates)
        new_v, new_s = optimizer.apply(v_rows, s_rows, g_rows)
        emb_value = scatter.scatter_update_rows_xla(emb_value, ids, new_v)
        emb_state = {
            k: scatter.scatter_update_rows_xla(emb_state[k], ids, new_s[k])
            for k in emb_state
        }
        # trash-row reset (PAD gradients)
        fills = optimizer.state_shapes()
        emb_value = emb_value.at[trash].set(0.0)
        emb_state = {k: emb_state[k].at[trash].set(fills[k]) for k in emb_state}
        return emb_value, emb_state, mlp_params, opt_state, l

    step = jax.jit(
        step_fn,
        in_shardings=(
            t_shard,
            {k: t_shard for k in state_keys},
            repl,
            repl,
            repl,  # ids: replicated unique slots
            repl,  # inverse
            batch2,
            batch1,
        ),
        out_shardings=(
            t_shard,
            {k: t_shard for k in state_keys},
            repl,
            repl,
            repl,
        ),
        donate_argnums=(0, 1, 2, 3),
    )
    shardings = {
        "table": t_shard, "replicated": repl,
        "batch2": batch2, "batch1": batch1,
    }
    return step, shardings


def init_sharded_table(
    table_cfg: TableConfig,
    mesh: Mesh,
    optimizer: ServerOptimizer,
    total_rows: int,
    key=None,
    kind: str = "normal",
):
    """Materialize (value, state) DIRECTLY into their row shards.

    ``jit`` with ``out_shardings`` makes GSPMD generate each device's rows
    in place (partitionable threefry), so peak per-device memory is the
    shard, never the full table — the only way a near-HBM-sized table can
    come up on real hardware, and what keeps the 2^28-row CPU-mesh proof
    inside host RAM.

    ``kind="zeros"`` skips the gaussian draw (memset-speed): cold-start
    embeddings at tens of GB, where RNG generation dominates bring-up —
    the row-sharded layout and the train step are identical either way.
    """
    if kind not in ("normal", "zeros"):
        raise ValueError(f"kind must be normal|zeros, got {kind!r}")
    if key is None:
        key = jax.random.PRNGKey(0)
    t_shard = mesh_lib.table_sharding(mesh)
    dim = table_cfg.dim
    fills = optimizer.state_shapes()

    @functools.partial(
        jax.jit,
        static_argnums=(1,),
        out_shardings=(t_shard, {k: t_shard for k in sorted(fills)}),
    )
    def build(key, kind_):
        if kind_ == "zeros":
            value = jnp.zeros((total_rows, dim), jnp.float32)
        else:
            value = (
                jax.random.normal(key, (total_rows, dim))
                * table_cfg.init_scale
            ).astype(jnp.float32)
            value = value.at[table_cfg.rows :].set(0.0)  # trash + pad rows
        state = {
            k: jnp.full((total_rows, dim), fill, jnp.float32)
            for k, fill in fills.items()
        }
        return value, state

    with mesh:
        return build(key, kind)


class SpmdDLRMTrainer:
    """DLRM over a (data, model) mesh: PS-sharded embeddings + DP dense part."""

    def __init__(
        self,
        table_cfg: TableConfig,
        mesh: Mesh,
        *,
        n_dense: int = 13,
        n_sparse: int = 26,
        bottom_mlp: Sequence[int] = (64, 32),
        top_mlp: Sequence[int] = (64, 32),
        learning_rate: float = 0.01,
        min_bucket: int = 1024,
        seed: int = 0,
        table_init: str = "normal",
        dashboard=None,
    ) -> None:
        from parameter_server_tpu.utils import metrics as metrics_lib

        self.cfg = table_cfg
        self.mesh = mesh
        self.n_sparse = n_sparse
        self.min_bucket = min_bucket
        self.dashboard = metrics_lib.trainer_dashboard(
            dashboard, mesh.devices.size
        )
        self.step_count = 0
        self._flops_shape = None  # (n_slots, batch) the cost analysis is for
        self.optimizer: ServerOptimizer = make_optimizer(table_cfg.optimizer)
        self.localizer = HashLocalizer(table_cfg.rows, seed=seed)
        self.model = DLRM(
            bottom_mlp=bottom_mlp, top_mlp=top_mlp, emb_dim=table_cfg.dim
        )
        self.tx = optax.adam(learning_rate)

        repl = mesh_lib.replicated(mesh)
        n_model = mesh.shape[mesh_lib.MODEL_AXIS]
        self.total_rows = ((table_cfg.rows + 1 + n_model - 1) // n_model) * n_model

        k_table, k_mlp = jax.random.split(jax.random.PRNGKey(seed))
        self.emb_value, self.emb_state = init_sharded_table(
            table_cfg, mesh, self.optimizer, self.total_rows, key=k_table,
            kind=table_init,
        )
        dense0 = jnp.zeros((1, n_dense), jnp.float32)
        emb0 = jnp.zeros((1, n_sparse, table_cfg.dim), jnp.float32)
        self.mlp_params = jax.device_put(
            self.model.init(k_mlp, dense0, emb0)["params"], repl
        )
        self.opt_state = jax.device_put(self.tx.init(self.mlp_params), repl)

        self._step, _shardings = make_dlrm_step(
            table_cfg, mesh, self.model, self.optimizer, self.tx, n_sparse,
        )

    def step(
        self,
        keys: np.ndarray,
        dense_feats: np.ndarray,
        labels: np.ndarray,
    ) -> float:
        slots, inverse, _n = localize_to_slots(
            keys, self.localizer, min_bucket=self.min_bucket
        )
        # MFU wiring (VERDICT r3 weak #4): DLRM has no clean FLOPs closed
        # form (MLPs + interactions + sparse gathers), so the numerator is
        # XLA's own count of the full step, refreshed when the bucketed
        # unique-slot count changes shape.
        shape_key = (slots.shape[0], labels.shape[0])
        if shape_key != self._flops_shape:
            from parameter_server_tpu.utils import metrics as metrics_lib

            step_flops = metrics_lib.lowered_flops(
                self._step,
                self.emb_value,
                self.emb_state,
                self.mlp_params,
                self.opt_state,
                jax.ShapeDtypeStruct(slots.shape, jnp.int32),
                jax.ShapeDtypeStruct(inverse.shape, jnp.int32),
                jax.ShapeDtypeStruct(np.asarray(dense_feats).shape, jnp.float32),
                jax.ShapeDtypeStruct(np.asarray(labels).shape, jnp.float32),
            )
            self.dashboard.flops_per_example = step_flops / max(
                labels.shape[0], 1
            )
            self._flops_shape = shape_key
        (
            self.emb_value,
            self.emb_state,
            self.mlp_params,
            self.opt_state,
            loss,
        ) = self._step(
            self.emb_value,
            self.emb_state,
            self.mlp_params,
            self.opt_state,
            jnp.asarray(slots),
            jnp.asarray(inverse),
            jnp.asarray(dense_feats),
            jnp.asarray(labels),
        )
        loss_f = float(loss)
        self.step_count += 1
        self.dashboard.record(
            self.step_count, loss_f, examples=int(labels.shape[0])
        )
        return loss_f
