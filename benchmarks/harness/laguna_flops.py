"""Operations and bytes of a step of the window-and-full-attention language
model (``laguna_xs2``), from the configuration's shapes alone: **the
algorithm's work, whatever implements it**.  A matrix product of ``[m, k] x
[k, n]`` is ``2 m k n`` operations; a training step is forward plus backward,
three times the forward's products; nothing recomputed is counted (the
blocked attention's second pass over its scores, the checkpoints' second
forward), and nothing masked: a full layer's query ``t`` is counted against
its ``t + 1`` keys (the causal half), a window layer's against ``min(t + 1,
window)``.

- attention of either kind: the scores and the weighted sum over the query
  heads of the layer (a layer's own count); bytes: ``q``, the output and
  their gradients per query head, ``k``, ``v`` and theirs per key-value
  head, once each;
- experts: the three matrices of an expert, over the token slots the held
  experts were sent (a count the step returns), not over the layout's rows.

``cfg`` is the configuration file's dict; the cut (``n_layers``,
``layers_first``, ``experts_held``, ``vocab_rows``) is read beside the
published keys."""

from __future__ import annotations

from typing import Optional

MIXERS = {"full_attention": "full", "sliding_attention": "window"}
MLPS = {"dense": "dense", "sparse": "experts"}

#: what ``harness/model_scopes.py`` reads of this body's trace (the driver
#: ``hybrid_laguna`` names this module): the device scope of the whole jitted
#: step, and each kernel's scopes
ROOT_SCOPE = "ps.model.laguna"
KERNELS = {
    "full_attn": ("ps.model.attn.full",),
    "window_attn": ("ps.model.attn.window",),
    # the products with their gather and scatter (``flops_model.KERNELS``)
    "moe_experts": ("ps.model.moe.dispatch", "ps.model.moe.experts",
                    "ps.model.moe.combine"),
}
#: kernels read a layer: kernel -> the mixer whose layers share its time
#: (the roofline is the kind's whole work over its whole time)
PER_LAYER = {"full_attn": "full", "window_attn": "window"}


def _held(cfg: dict) -> range:
    return range(cfg["layers_first"], cfg["layers_first"] + cfg["n_layers"])


def layer_kinds(cfg: dict) -> list:
    return [(MIXERS[cfg["layer_types"][i]], MLPS[cfg["mlp_layer_types"][i]])
            for i in _held(cfg)]


def layer_heads(cfg: dict) -> list:
    return [cfg["num_attention_heads_per_layer"][i] for i in _held(cfg)]


def _count(cfg: dict, what: str) -> int:
    return sum(what in kinds for kinds in layer_kinds(cfg))


def attn_params(cfg: dict, heads: int) -> int:
    """Matrix parameters of one attention layer of ``heads`` query heads:
    ``q`` and ``o``, ``k`` and ``v``, the gate a head."""
    D, K, Hkv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    return 2 * D * heads * K + 2 * D * Hkv * K + D * heads


def active_matrix_params(cfg: dict) -> float:
    """Matrix parameters one token's forward multiplies with on this chip:
    every layer's attention at its own head count, the dense MLP, the router
    and the shared expert of each expert layer, the head over the held
    vocabulary, and of the routed experts the share a slot lands on (top-k
    x held / routed)."""
    D = cfg["hidden_size"]
    expert = 3 * D * cfg["moe_intermediate_size"]
    per_moe = (
        D * cfg["num_experts"]
        + 3 * D * cfg["shared_expert_intermediate_size"]
        + expert * cfg["num_experts_per_tok"] * cfg["experts_held"]
        / cfg["num_experts"]
    )
    total = D * cfg["vocab_rows"]
    for (_mixer, mlp), heads in zip(layer_kinds(cfg), layer_heads(cfg)):
        total += attn_params(cfg, heads)
        total += 3 * D * cfg["intermediate_size"] if mlp == "dense" else per_moe
    return total


def _attn(cfg: dict, kind: str, sequences: int, seq_len: int, pairs: int) -> dict:
    """The layers of ``kind``, ``pairs`` (query, key) pairs a head and
    sequence, forward and backward."""
    K, Hkv = cfg["head_dim"], cfg["num_key_value_heads"]
    flops = floats = 0
    for (mixer, _mlp), H in zip(layer_kinds(cfg), layer_heads(cfg)):
        if mixer != kind:
            continue
        flops += 3 * 2 * pairs * 2 * K * H * sequences
        # q, o and their gradients a query head; k, v and theirs a key head
        floats += seq_len * K * (4 * H + 4 * Hkv) * sequences
    return {"flops": flops, "bytes": 4 * floats}


def full_attn(cfg: dict, sequences: int, seq_len: int) -> dict:
    """Causal softmax attention of every full layer: the causal half."""
    return _attn(cfg, "full", sequences, seq_len, seq_len * (seq_len + 1) // 2)


def window_pairs(seq_len: int, window: int) -> int:
    """``sum_t min(t + 1, window)`` over a sequence's queries."""
    w = min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def window_attn(cfg: dict, sequences: int, seq_len: int) -> dict:
    """Attention of every window layer: a query against the last
    ``sliding_window`` keys, its own included."""
    return _attn(cfg, "window", sequences, seq_len,
                 window_pairs(seq_len, cfg["sliding_window"]))


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
    out = {"full_attn": full_attn(cfg, sequences, seq_len),
           "window_attn": window_attn(cfg, sequences, seq_len)}
    if held_slots is not None:
        out["moe_experts"] = moe_experts(cfg, held_slots)
    return out


def step_flops(cfg: dict, sequences: int, seq_len: int) -> float:
    """Model operations of one training step: 6 x active matrix parameters
    x tokens, plus both kinds of attention over the keys a query sees."""
    tokens = sequences * seq_len
    return (
        6.0 * active_matrix_params(cfg) * tokens
        + full_attn(cfg, sequences, seq_len)["flops"]
        + window_attn(cfg, sequences, seq_len)["flops"]
    )
