"""Sparse logistic regression — the reference's flagship linear method.

(Reference: ``src/app/linear_method/`` — logit loss, L1/L2 penalties, AdaGrad
async SGD workers [U]; BASELINE config #1: Criteo sparse LR.)

Two execution paths over the same math:

- :func:`grad_rows` — the *Van path*: the worker pulls per-position weights,
  computes per-position gradient values, pushes them back (classic PS loop).
- :func:`fused_train_step` — the *single-device fast path*: pull (gather),
  loss/grad, duplicate pre-combine, optimizer apply, and scatter-back compiled
  into ONE XLA program over the HBM-resident table; buffers donated.  This is
  what the north-star examples/sec/chip metric measures, and the body that
  ``parallel/`` later wraps in shard_map (psum of combined grads over the DP
  axis before the apply == NCCL-pre-reduction replacement).

With one-hot categorical features the per-example logit is the sum of the
weights at the example's keys plus bias, and d(loss)/d(w_k) = (p - y) for
each position holding key k.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from parameter_server_tpu.kv.optim import ServerOptimizer
from parameter_server_tpu.ops import scatter


def predict_logits(w_pos: jax.Array, bias: jax.Array) -> jax.Array:
    """Per-example logits from per-position weights ``[B, nnz]``."""
    return jnp.sum(w_pos, axis=-1) + bias


def logloss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean binary cross-entropy from logits (numerically stable)."""
    return jnp.mean(
        jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )


def grad_rows(
    w_pos: jax.Array, labels: jax.Array
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Van-path worker compute: per-position gradient values.

    Returns ``(per_position_grads [B, nnz], bias_grad [], loss [])``.
    """
    with jax.named_scope("ps.model.linear"):
        logits = predict_logits(w_pos, 0.0)
        p = jax.nn.sigmoid(logits)
        residual = p - labels  # [B]
        g = jnp.broadcast_to(residual[:, None], w_pos.shape)
        return g, jnp.mean(residual), logloss(logits, labels)


@functools.partial(
    jax.jit,
    static_argnames=("optimizer", "num_rows"),
    donate_argnums=(0, 1, 2, 3),
)
def fused_train_step(
    value: jax.Array,
    state: Dict[str, jax.Array],
    bias: jax.Array,
    bias_state: Dict[str, jax.Array],
    ids: jax.Array,
    inverse: jax.Array,
    labels: jax.Array,
    optimizer: ServerOptimizer,
    num_rows: int,
):
    """One full LR step on the device-resident table.

    Args:
      value/state: the table planes, flat ``[rows + 1]`` (``KVTable``'s for
        dim 1) or ``[rows + 1, 1]`` (donated, updated in place).
      bias/bias_state: scalar bias row ``[1, 1]`` and its optimizer state.
      ids: unique row slots ``[num_rows]`` (bucket-padded, pads -> trash row).
      inverse: position -> slot-row map ``[B * nnz]``.
      labels: ``[B]``.

    Returns ``(value, state, bias, bias_state, loss)``.
    """
    batch = labels.shape[0]
    w_rows = optimizer.pull_weights(
        scatter.gather_rows(value, ids),
        {k: scatter.gather_rows(v, ids) for k, v in state.items()},
    )  # [num_rows, 1]
    w_pos = w_rows[inverse, 0].reshape(batch, -1)  # [B, nnz]
    # bias goes through the same lazy-weight transform (FTRL stores z here)
    bias_w = optimizer.pull_weights(bias, bias_state)
    logits = predict_logits(w_pos, bias_w[0, 0])
    loss = logloss(logits, labels)
    residual = (jax.nn.sigmoid(logits) - labels) / batch  # mean-loss scaling
    g_pos = jnp.broadcast_to(residual[:, None], w_pos.shape).reshape(-1, 1)
    combined = scatter.segment_combine(g_pos, inverse, num_rows)  # [num_rows, 1]
    # optimizer apply on touched rows, scatter back
    v_rows = scatter.gather_rows(value, ids)
    s_rows = {k: scatter.gather_rows(v, ids) for k, v in state.items()}
    new_v, new_s = optimizer.apply(v_rows, s_rows, combined)
    value = scatter.scatter_update_rows_xla(value, ids, new_v)
    state = {k: scatter.scatter_update_rows_xla(state[k], ids, new_s[k]) for k in state}
    # re-zero the trash row (last): PAD_KEY positions route gradients there
    fills = optimizer.state_shapes()
    value = value.at[-1].set(0.0)
    state = {k: state[k].at[-1].set(fills[k]) for k in state}
    # bias via the same optimizer rule on its 1x1 "table"
    g_bias = jnp.sum(residual)[None, None]
    new_b, new_bs = optimizer.apply(bias, bias_state, g_bias)
    return value, state, new_b, new_bs, loss


def dense_fused_impl(
    value: jax.Array,
    state: Dict[str, jax.Array],
    bias: jax.Array,
    bias_state: Dict[str, jax.Array],
    slots_pos: jax.Array,
    labels: jax.Array,
    optimizer: ServerOptimizer,
    trash_row: int = -1,
):
    """Dense-apply LR step: no host dedup, no row gather/scatter of updates.

    The TPU-native formulation of the server update: per-position hashed row
    slots ``[B, nnz]`` index the table directly; duplicate slots are combined
    by the scatter-add into a full-size gradient buffer, and the optimizer
    applies *elementwise over the whole table*.  For rows with zero gradient
    the update is exactly zero under SGD/AdaGrad/FTRL (their state updates
    are also zero at g=0), so this matches the sparse row-apply semantics
    while avoiding the per-batch ``np.unique`` host bottleneck entirely.

    Caveats (callers must enforce): requires ``l1 == l2 == 0`` — penalties
    make the update nonzero at g=0 rows (l2 decays every row; AdaGrad's prox
    with sum_sq=0 would zero untouched weights) — and an optimizer whose
    state update is zero at g=0 (true for SGD/AdaGrad/FTRL; NOT Adam, whose
    moments decay).  Otherwise use the row-apply :func:`fused_train_step`.

    HBM traffic per step is O(table size); right for tables up to a few GB
    (Criteo LR at 2^25 rows x 4B = 128 MB -> ~0.2 ms at v5e bandwidth).
    """
    batch = labels.shape[0]
    # a rank-1 plane is a dim-1 table (KVTable's); ``[N, 1]`` planes
    # (parallel/lr_spmd.py's own) index their one column
    pos = slots_pos.reshape(-1)
    idx = pos if value.ndim == 1 else (pos, 0)
    w_table = optimizer.pull_weights(value, state)  # elementwise transform
    w_pos = w_table[idx].reshape(batch, -1)
    bias_w = optimizer.pull_weights(bias, bias_state)
    logits = predict_logits(w_pos, bias_w[0, 0])
    loss = logloss(logits, labels)
    residual = (jax.nn.sigmoid(logits) - labels) / batch
    g_pos = jnp.broadcast_to(residual[:, None], w_pos.shape).reshape(-1)
    grad_buf = jnp.zeros_like(value).at[idx].add(g_pos)
    # drop PAD contributions; trash_row is the PAD slot of the localizer
    # (== capacity); -1 only coincides with it for unpadded [rows+1] tables
    grad_buf = grad_buf.at[trash_row].set(0.0)
    value, state = optimizer.apply(value, state, grad_buf)
    g_bias = jnp.sum(residual)[None, None]
    new_b, new_bs = optimizer.apply(bias, bias_state, g_bias)
    return value, state, new_b, new_bs, loss


dense_fused_train_step = functools.partial(
    jax.jit,
    static_argnames=("optimizer", "trash_row"),
    donate_argnums=(0, 1, 2, 3),
)(dense_fused_impl)


def mix32_jax(x: jax.Array, seed: int = 0) -> jax.Array:
    """murmur3 fmix32 on device (uint32) — twin of ``utils.keys.mix32``.

    TPUs have no native uint64, so device-side hashing uses the 32-bit
    avalanche; ``HashLocalizer(hash_bits=32)`` reproduces it on the host.
    The constants are shared with the host twin so they cannot diverge.
    """
    from parameter_server_tpu.utils.keys import MIX32_A, MIX32_B

    x = x.astype(jnp.uint32) ^ jnp.uint32(seed)
    x ^= x >> 16
    x = x * jnp.uint32(MIX32_A)
    x ^= x >> 13
    x = x * jnp.uint32(MIX32_B)
    x ^= x >> 16
    return x


@functools.partial(
    jax.jit,
    static_argnames=("optimizer", "num_rows", "seed"),
    donate_argnums=(0, 1, 2, 3),
)
def dense_scan_train_step(
    value: jax.Array,
    state: Dict[str, jax.Array],
    bias: jax.Array,
    bias_state: Dict[str, jax.Array],
    keys_block: jax.Array,
    labels_block: jax.Array,
    optimizer: ServerOptimizer,
    num_rows: int,
    seed: int = 0,
):
    """K dense-apply LR steps in ONE XLA program (``lax.scan`` over steps).

    The host-link-bound single-chip path: raw uint32 keys ``[K, B, nnz]``
    ship in one transfer (half the bytes of int32 slot ids computed on host,
    and K× fewer dispatches), the hashing trick runs on device via
    :func:`mix32_jax`, and each scan iteration is the ``dense_fused_impl``
    update.  PAD positions (key == ``0xFFFFFFFF``, the uint32 image of
    ``PAD_KEY``) route to the table's trash row like the host path; real keys
    must therefore be < 2**32 - 1.  Returns
    ``(value, state, bias, bias_state, losses [K])``.
    """

    def body(carry, xs):
        value, state, bias, bias_state = carry
        keys, labels = xs
        slots = jnp.where(
            keys == jnp.uint32(0xFFFF_FFFF),
            jnp.int32(num_rows),  # trash row of the [rows + 1] table
            (mix32_jax(keys, seed) % jnp.uint32(num_rows)).astype(jnp.int32),
        )
        value, state, bias, bias_state, loss = dense_fused_impl(
            value, state, bias, bias_state, slots, labels, optimizer
        )
        return (value, state, bias, bias_state), loss

    (value, state, bias, bias_state), losses = jax.lax.scan(
        body, (value, state, bias, bias_state), (keys_block, labels_block)
    )
    return value, state, bias, bias_state, losses


def eval_logits(
    value: jax.Array,
    state: Dict[str, jax.Array],
    bias: jax.Array,
    bias_state: Dict[str, jax.Array],
    ids: jax.Array,
    inverse: jax.Array,
    batch: int,
    optimizer: ServerOptimizer,
) -> jax.Array:
    """Forward-only logits for evaluation batches."""
    w_rows = optimizer.pull_weights(
        scatter.gather_rows(value, ids),
        {k: scatter.gather_rows(v, ids) for k, v in state.items()},
    )
    w_pos = w_rows[inverse, 0].reshape(batch, -1)
    bias_w = optimizer.pull_weights(bias, bias_state)
    return predict_logits(w_pos, bias_w[0, 0])
