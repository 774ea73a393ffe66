"""Operations and bytes of a step of the gated DeltaNet mixture of experts
(``qwen3_next_80b_a3b``), from the configuration's shapes alone: **the
algorithm's work, whatever implements it**.  A matrix product of ``[m, k] x
[k, n]`` is ``2 m k n`` operations; a training step is forward plus
backward, three times the forward's products; nothing recomputed is counted
(the checkpoints' second forward, the blocked attention's second pass over
its scores, the chunked loss's second head product), and nothing masked.

- the gated delta rule: the recurrence's own count, per token and value
  head ``7 d_k d_v`` forward (decay the state ``d_k d_v``, ``k^T S``, the
  rank-one update and ``q^T S`` at ``2 d_k d_v`` each), as
  ``flops_model.kda_scan`` counts KDA's, not the chunked form's larger one;
  bytes: ``q`` and ``k`` a key head, ``v``, ``g``, ``beta`` and the output
  a value head, read or written once each way;
- gated attention: the causal half of the scores and of the weighted sum
  at head size ``K`` over the query heads (the element-wise gate on the
  output is not counted: it fuses into its neighbours on the chip); bytes:
  ``q``, the output and their gradients a query head, ``k``, ``v`` and
  theirs a key head, once each;
- experts: the three matrices of an expert, over the token slots the held
  experts were sent (a count the step returns), not over the layout's rows.

``cfg`` is the configuration file's dict; the cut (``n_layers``,
``layers_first``, ``experts_held``, ``vocab_rows``) is read beside the
published keys."""

from __future__ import annotations

from typing import Optional

#: what ``harness/model_scopes.py`` reads of this body's trace (the driver
#: ``hybrid_qwen3_next`` names this module): the device scope of the whole
#: jitted step, and each kernel's scopes
ROOT_SCOPE = "ps.model.qwen3_next"
KERNELS = {
    "gdn_scan": ("ps.model.gdn.scan",),
    # the causal tiles of the gated layer (the element-wise gate on their
    # output fuses into its neighbours' programs, and its scope reads no
    # time of its own on the chip)
    "gated_attn": ("ps.model.attn.full",),
    # the products with their gather and scatter (``flops_model.KERNELS``)
    "moe_experts": ("ps.model.moe.dispatch", "ps.model.moe.experts",
                    "ps.model.moe.combine"),
}
#: kernels read a layer: kernel -> the mixer whose layers share its time
#: (the roofline is the kind's whole work over its whole time)
PER_LAYER = {"gdn_scan": "gdn", "gated_attn": "full"}


def layer_kinds(cfg: dict) -> list:
    """Layer ``i`` is ``full`` when ``(i + 1) % full_attention_interval ==
    0``, else ``gdn``; every one an expert layer."""
    first = cfg["layers_first"]
    return [
        ("full" if (i + 1) % cfg["full_attention_interval"] == 0 else "gdn",
         "experts")
        for i in range(first, first + cfg["n_layers"])
    ]


def _count(cfg: dict, what: str) -> int:
    return sum(what in kinds for kinds in layer_kinds(cfg))


def mixer_params(cfg: dict) -> dict:
    """Matrix parameters a token multiplies with, per mixer kind (the
    convolution's taps are element-wise, and not counted)."""
    D = cfg["hidden_size"]
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    H, Hkv, K = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    return {
        # W_qkvz, W_ba, W_o
        "gdn": D * (2 * Hk * dk + 2 * Hv * dv) + D * 2 * Hv + Hv * dv * D,
        # W_q with its gate, W_k, W_v, W_o
        "full": D * H * 2 * K + 2 * D * Hkv * K + H * K * D,
    }


def body_matrix_params(cfg: dict) -> float:
    """Matrix parameters one token's forward multiplies with on this chip:
    every mixer, the router, the shared expert and its gate, the head over
    the held vocabulary, and of the routed experts the share a slot lands on
    (top-k x held / routed)."""
    D = cfg["hidden_size"]
    mix = mixer_params(cfg)
    expert = 3 * D * cfg["moe_intermediate_size"]
    per_moe = (
        D * cfg["num_experts"]
        + 3 * D * cfg["shared_expert_intermediate_size"] + D
        + expert * cfg["num_experts_per_tok"] * cfg["experts_held"]
        / cfg["num_experts"]
    )
    total = D * cfg["vocab_rows"]
    for mixer, _mlp in layer_kinds(cfg):
        total += mix[mixer] + per_moe
    return total


def gdn_scan(cfg: dict, tokens: int) -> dict:
    """The gated delta rule of every DeltaNet layer, forward and backward."""
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    layers = _count(cfg, "gdn")
    inputs = 2 * Hk * dk + Hv * (dv + 2)  # q, k; v, g, beta
    # forward reads the inputs and writes o; backward reads them and dO
    # again and writes their gradients
    floats = (inputs + Hv * dv) + (inputs + Hv * dv) + inputs
    return {"flops": 3 * 7 * dk * dv * Hv * tokens * layers,
            "bytes": 4 * floats * tokens * layers}


def gated_attn(cfg: dict, sequences: int, seq_len: int) -> dict:
    """Causal softmax attention of every full layer, forward and backward
    (the gate on its output is element-wise and fuses elsewhere: not
    counted)."""
    K, H, Hkv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    pairs = seq_len * (seq_len + 1) // 2  # the causal half
    n = _count(cfg, "full") * sequences
    # q, o and their gradients a query head; k, v and theirs a key head
    floats = seq_len * K * (4 * H + 4 * Hkv)
    return {"flops": 3 * 2 * pairs * 2 * K * H * n, "bytes": 4 * floats * n}


def moe_experts(cfg: dict, held_slots: float) -> dict:
    """The held experts' three matrices over ``held_slots`` token slots (a
    step's count over all expert layers), forward and backward; bytes: each
    held expert's weights read forward and backward and its gradient
    written, a slot's row in and out both ways."""
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = 3 * D * F * cfg["experts_held"] * _count(cfg, "experts")
    return {"flops": 3 * 2 * 3 * D * F * held_slots,
            "bytes": 4 * (3 * weights + 4 * D * held_slots)}


def work(cfg: dict, sequences: int, seq_len: int,
         held_slots: Optional[float] = None) -> dict:
    """Operations and bytes of each of ``KERNELS`` a step that the shapes
    count; the experts' only where ``held_slots`` (the step's count) is known."""
    out = {"gdn_scan": gdn_scan(cfg, sequences * seq_len),
           "gated_attn": gated_attn(cfg, sequences, seq_len)}
    if held_slots is not None:
        out["moe_experts"] = moe_experts(cfg, held_slots)
    return out


def step_flops(cfg: dict, sequences: int, seq_len: int) -> float:
    """Model operations of one training step: 6 x active matrix parameters
    x tokens, plus attention's scores and the delta rule's recurrence."""
    tokens = sequences * seq_len
    return (
        6.0 * body_matrix_params(cfg) * tokens
        + gated_attn(cfg, sequences, seq_len)["flops"]
        + gdn_scan(cfg, tokens)["flops"]
    )
