"""Tensor-parallel sharding rules for the transformer family.

GSPMD replaces hand-written NCCL tensor-parallel collectives: annotate the
parameter tree with PartitionSpecs and XLA inserts the all-gathers /
reduce-scatters over the ICI ``model`` axis (PAPERS.md: GSPMD [V]).

Rules (matching ``models/transformer.py`` param naming):
- token embedding rows sharded over ``model`` — the PS table partition (the
  "PS-sharded embeddings" half of the Llama hybrid, BASELINE config #5);
- attention q/k/v sharded over heads; output projection over heads;
- MLP up/gate sharded over d_ff, down over d_ff (Megatron-style pairing:
  column- then row-parallel, one allreduce per block);
- norms, biases of row-parallel layers, and positional embeddings replicated.

The layer-pattern body (``models/kimi_linear.py``) adds: a held share of
routed experts ``[E, ...]`` sharded over ``model`` along the expert axis (the
router and the shared expert's norms stay whole; the shared expert and the
dense MLP pair as above); the delta-rule mixer's per-head parameters
(convolutions, the low-rank projections' second halves, decay rates and
biases, the ``b`` gate) over their head axis; latent attention's ``kv_b``
over its heads, ``kv_a`` and the latent's norm whole (every head reads the
whole latent).  The convolution-and-attention body (``models/lfm2_moe.py``)
adds the gated short convolution, split over its channels (a channel shares
nothing with another between the two products): ``in_proj`` ``[3, D, C]``
column-parallel, the taps ``[L, C]``, ``out_proj`` ``[C, D]`` row-parallel.
Its grouped-query attention takes the rules above (``k`` and ``v`` over their
own, fewer, heads; the per-head norms whole); the experts' selection bias
stays whole beside the router.  The window-and-full attention body
(``models/laguna.py``) adds the attention output's gate ``o_gate`` ``[D,
H]``, one value a head: column-parallel over the heads beside ``q``, whose
heads it gates.  A layer's head count is its own kernels' (48 and 64 in one
body): every rule here reads a leaf's shape and none a config's one count,
and each count splits where the key-value heads it is grouped over do.
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from parameter_server_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS


def _spec_for(path: tuple[str, ...], value: Any) -> P:
    names = [p for p in path]
    leaf = names[-1]
    parent = names[-2] if len(names) >= 2 else ""
    ndim = getattr(value, "ndim", 0)

    if leaf == "embedding":
        return P(MODEL_AXIS, None)  # vocab-row sharded (PS table scheme)
    if leaf == "pos_embedding":
        return P()
    if parent == "experts":  # [E, d_model, width] / [E, width, d_model]
        return P(MODEL_AXIS, None, None)
    if leaf.startswith("conv_"):  # depthwise [width, heads, head_dim]
        return P(None, MODEL_AXIS, None)
    if leaf == "taps":  # depthwise [taps, channels]
        return P(None, MODEL_AXIS)
    if parent == "in_proj":  # [3, d_model, channels]
        return P(None, None, MODEL_AXIS)
    if parent == "out_proj":  # [channels, d_model]
        return P(MODEL_AXIS, None)
    if leaf == "A_log":  # [heads]
        return P(MODEL_AXIS)
    if leaf == "dt_bias":  # [heads, head_dim]
        return P(MODEL_AXIS, None)
    if parent in ("f_b", "g_b", "kv_b"):
        if leaf == "kernel":  # [rank, heads, head_dim]
            return P(None, MODEL_AXIS, None)
        return P(MODEL_AXIS, None)  # bias [heads, head_dim]
    if parent in ("b", "o_gate"):  # [d_model, heads]
        return P(None, MODEL_AXIS)
    if parent in ("q", "k", "v"):
        if leaf == "kernel":  # [d_model, heads, head_dim]
            return P(None, MODEL_AXIS, None)
        return P(MODEL_AXIS, None)  # bias [heads, head_dim]
    if parent == "o":
        if leaf == "kernel":  # [heads, head_dim, d_model]
            return P(MODEL_AXIS, None, None)
        return P()  # row-parallel bias replicated
    if parent in ("gate", "up"):
        if leaf == "kernel":  # [d_model, d_ff]
            return P(None, MODEL_AXIS)
        return P(MODEL_AXIS)
    if parent == "down":
        if leaf == "kernel":  # [d_ff, d_model]
            return P(MODEL_AXIS, None)
        return P()
    if parent == "lm_head":
        return P(None, MODEL_AXIS) if ndim == 2 else P(MODEL_AXIS)
    return P()  # norms and everything else replicated


def _add_fsdp_axis(spec: P, shape, data_n: int, axis: str) -> P:
    """Extend a TP spec with ``data``-axis sharding on the first free dim.

    Fully-sharded data parallelism in GSPMD terms: params (and therefore
    optimizer moments, which inherit these shardings) are additionally
    split over the ``data`` axis instead of being replicated per data
    replica; XLA all-gathers them at use and reduce-scatters the gradient.
    The scaling-book recipe for fitting an 8B train state on a v5e-16 —
    TP-8 alone leaves params+moments+grads at ~15 GB/device (compiled:
    ``parallel/feasibility.py``), over the 16 GB HBM.
    """
    parts = list(spec) + [None] * (len(shape) - len(spec))
    for i, (p, s) in enumerate(zip(parts, shape)):
        if p is None and s % data_n == 0 and s >= data_n:
            parts[i] = axis
            break
    return P(*parts)


def transformer_param_shardings(
    params, mesh: Mesh, *, fsdp: bool = False, fsdp_axis: str = DATA_AXIS
):
    """Map a transformer param pytree to NamedShardings per the TP rules.

    ``fsdp=True`` additionally shards every param's first still-replicated
    (and evenly divisible) dimension over ``fsdp_axis`` (default ``data``;
    the SP x TP trainer passes ``sp`` — any non-``model`` axis works).
    """
    data_n = int(mesh.shape.get(fsdp_axis, 1)) if fsdp else 1

    def assign(path, value):
        names = tuple(
            p.key if hasattr(p, "key") else str(p) for p in path
        )
        if names and names[0] == "blocks":
            # scan_blocks layout: every block param carries a leading
            # n_layers axis; the per-layer rules apply to the tail dims.
            # Under FSDP that leading axis is the ideal data-axis shard:
            # the scan gathers exactly ONE layer's params per iteration.
            inner = P(*_spec_for(names, _TailView(value)))
            spec = P(None, *inner)
        else:
            spec = _spec_for(names, value)
        if data_n > 1:
            spec = _add_fsdp_axis(spec, value.shape, data_n, fsdp_axis)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(assign, params)


class _TailView:
    """Shape/ndim proxy dropping the leading (layer-stack) axis."""

    def __init__(self, value):
        self.shape = tuple(value.shape[1:])
        self.ndim = len(self.shape)


def place_params(params, mesh: Mesh):
    """Device-put a host param tree onto the mesh per the TP rules."""
    shardings = transformer_param_shardings(params, mesh)
    return jax.tree.map(jax.device_put, params, shardings)
