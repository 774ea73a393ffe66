"""Operations and bytes of a step of the block-diffusion mixture of experts
(``sdar_30b_a3b``), from the configuration's shapes alone: **the
algorithm's work, whatever implements it**.  A matrix product of ``[m, k] x
[k, n]`` is ``2 m k n`` operations; a training step is forward plus
backward, three times the forward's products; nothing recomputed is counted
(the blocked attention's second pass over its scores, the checkpoints'
second forward, the chunked loss's second head product), and nothing
masked.

The body runs on ``[xt ; x0]``: ``seq_len`` (``L``) clean positions a
sequence, ``2L`` body positions; the head and the loss read ``xt``'s ``L``.

- block-diffusion attention: a query of ``x0`` in block ``j`` against the
  ``x0`` keys of blocks ``<= j``, a query of ``xt`` against the ``x0`` keys
  of blocks ``< j`` and the ``b`` ``xt`` keys of its own block: ``L^2 + L
  b`` (query, key) pairs a head and sequence, half of a causal pass over
  ``2L``; bytes: ``q``, the output and their gradients per query head, ``k``,
  ``v`` and theirs per key-value head, once each, over ``2L`` positions;
- experts: the three matrices of an expert, over the token slots the held
  experts were sent (a count the step returns), not over the layout's rows.

``cfg`` is the configuration file's dict; the cut (``n_layers``,
``experts_held``, ``vocab_rows``) and the block length
(``generator_params``) are read beside the published keys."""

from __future__ import annotations

from typing import Optional

#: what ``harness/model_scopes.py`` reads of this body's trace (the driver
#: ``hybrid_sdar`` names this module): the device scope of the whole jitted
#: step, and each kernel's scopes
ROOT_SCOPE = "ps.model.sdar"
KERNELS = {
    "bd_attn": ("ps.model.attn.bd",),
    # the products with their gather and scatter (``flops_model.KERNELS``)
    "moe_experts": ("ps.model.moe.dispatch", "ps.model.moe.experts",
                    "ps.model.moe.combine"),
}
#: kernels read a layer: kernel -> the mixer whose layers share its time
PER_LAYER = {"bd_attn": "bd"}


def layer_kinds(cfg: dict) -> list:
    """Every layer held: block-diffusion attention and an expert MLP."""
    return [("bd", "experts")] * cfg["n_layers"]


def attn_params(cfg: dict) -> int:
    """Matrix parameters of one attention layer: ``q`` and ``o``, ``k`` and
    ``v``."""
    D, K = cfg["hidden_size"], cfg["head_dim"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * D * H * K + 2 * D * Hkv * K


def body_matrix_params(cfg: dict) -> float:
    """Matrix parameters one body position's forward multiplies with on
    this chip: attention, the router, and of the routed experts the share a
    slot lands on (top-k x held / routed)."""
    D = cfg["hidden_size"]
    expert = 3 * D * cfg["moe_intermediate_size"]
    per_layer = (
        attn_params(cfg) + D * cfg["num_experts"]
        + expert * cfg["num_experts_per_tok"] * cfg["experts_held"]
        / cfg["num_experts"]
    )
    return per_layer * cfg["n_layers"]


def pairs(seq_len: int, block_length: int) -> int:
    """(query, key) pairs a head and sequence: ``L (L + b) / 2`` for ``x0``
    (block-causal), ``L (L - b) / 2 + L b`` for ``xt``."""
    return seq_len * seq_len + seq_len * block_length


def bd_attn(cfg: dict, sequences: int, seq_len: int) -> dict:
    """Block-diffusion attention of every layer, forward and backward."""
    K, H, Hkv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    b = cfg["generator_params"]["block_length"]
    layers = cfg["n_layers"]
    flops = 3 * 2 * pairs(seq_len, b) * 2 * K * H * sequences * layers
    # q, o and their gradients a query head; k, v and theirs a key head
    floats = 2 * seq_len * K * (4 * H + 4 * Hkv) * sequences * layers
    return {"flops": flops, "bytes": 4 * floats}


def moe_experts(cfg: dict, held_slots: float) -> dict:
    """The held experts' three matrices over ``held_slots`` token slots (a
    step's count over all expert layers), forward and backward; bytes: each
    held expert's weights read forward and backward and its gradient
    written, a slot's row in and out both ways."""
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = 3 * D * F * cfg["experts_held"] * cfg["n_layers"]
    return {"flops": 3 * 2 * 3 * D * F * held_slots,
            "bytes": 4 * (3 * weights + 4 * D * held_slots)}


def work(cfg: dict, sequences: int, seq_len: int,
         held_slots: Optional[float] = None) -> dict:
    """Operations and bytes of each of ``KERNELS`` a step that the shapes
    count; the experts' only where ``held_slots`` (the step's count) is known."""
    out = {"bd_attn": bd_attn(cfg, sequences, seq_len)}
    if held_slots is not None:
        out["moe_experts"] = moe_experts(cfg, held_slots)
    return out


def step_flops(cfg: dict, sequences: int, seq_len: int) -> float:
    """Model operations of one training step: 6 x the body's active matrix
    parameters x the ``2L`` body positions, 6 x the head's over the ``L``
    positions it reads, and block-diffusion attention over the keys a query
    sees."""
    head = cfg["hidden_size"] * cfg["vocab_rows"]
    return (
        6.0 * body_matrix_params(cfg) * sequences * 2 * seq_len
        + 6.0 * head * sequences * seq_len
        + bd_attn(cfg, sequences, seq_len)["flops"]
    )
