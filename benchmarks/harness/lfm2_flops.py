"""Operations and bytes of a step of the convolution-and-attention language
model (``lfm2_8b_a1b``), from the configuration's shapes alone: **the
algorithm's work, whatever implements it**.  A matrix product of ``[m, k] x
[k, n]`` is ``2 m k n`` operations; a training step is forward plus backward,
three times the forward's products; nothing recomputed is counted (the
blocked attention's second pass over its scores, the checkpoints' second
forward).

- gated short convolution: the two products (``D -> 3 D`` and ``D -> D``)
  and, per token and channel, the two gates and the ``L`` taps (``2 L + 1``
  forward); bytes: the mixer's activations once each way (the input row, the
  three parts of ``W_in``'s output, the gated row, the output row; their
  gradients on the way back) and the weights read twice and their gradient
  written;
- grouped-query attention: the causal half of the scores and of the weighted
  sum, over the query heads; bytes: ``q``, the output and their gradients
  per query head, ``k``, ``v`` and theirs per key-value head, once each;
- experts: the three matrices of an expert, over the token slots the held
  experts were sent (a count the step returns), not over the layout's rows.

``cfg`` is the configuration file's dict; the cut (``n_layers``,
``layers_first``, ``experts_held``, ``vocab_rows``) is read beside the
published keys."""

from __future__ import annotations

from typing import Optional

MIXERS = {"conv": "conv", "full_attention": "gqa"}

#: what ``harness/model_scopes.py`` reads of this body's trace (the driver
#: ``hybrid_lfm2`` names this module): the device scope of the whole jitted
#: step, and each kernel's scopes
ROOT_SCOPE = "ps.model.lfm2"
KERNELS = {
    "short_conv": ("ps.model.conv.proj", "ps.model.conv.gate",
                   "ps.model.conv.out"),
    "gqa_attn": ("ps.model.gqa.attn",),
    # the products with their gather and scatter (``flops_model.KERNELS``)
    "moe_experts": ("ps.model.moe.dispatch", "ps.model.moe.experts",
                    "ps.model.moe.combine"),
}
#: kernels read a layer: kernel -> the mixer whose layers share its time
PER_LAYER: dict = {}


def layer_kinds(cfg: dict) -> list:
    first = cfg["layers_first"]
    return [
        (MIXERS[cfg["layer_types"][i]],
         "dense" if i < cfg["num_dense_layers"] else "experts")
        for i in range(first, first + cfg["n_layers"])
    ]


def _count(cfg: dict, what: str) -> int:
    return sum(what in kinds for kinds in layer_kinds(cfg))


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def mixer_params(cfg: dict) -> dict:
    """Matrix parameters a token multiplies with, per mixer kind."""
    D, K = cfg["hidden_size"], head_dim(cfg)
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"conv": 3 * D * D + D * D,
            "gqa": D * H * K + 2 * D * Hkv * K + H * K * D}


def active_matrix_params(cfg: dict) -> float:
    """Matrix parameters one token's forward multiplies with on this chip:
    every mixer, the dense MLP, the router of each expert layer, the head
    over the held vocabulary, and of the routed experts the share a slot
    lands on (top-k x held / routed)."""
    D = cfg["hidden_size"]
    mix = mixer_params(cfg)
    expert = 3 * D * cfg["moe_intermediate_size"]
    per_moe = (
        D * cfg["num_experts"]
        + expert * cfg["num_experts_per_tok"] * cfg["experts_held"]
        / cfg["num_experts"]
    )
    total = D * cfg["vocab_rows"]
    for mixer, mlp in layer_kinds(cfg):
        total += mix[mixer]
        total += 3 * D * cfg["intermediate_size"] if mlp == "dense" else per_moe
    return total


def short_conv(cfg: dict, tokens: int) -> dict:
    """The gated short convolution of every conv layer (both products, the
    gates and the taps), forward and backward, a step."""
    D, L = cfg["hidden_size"], cfg["conv_L_cache"]
    n = _count(cfg, "conv")
    weights = mixer_params(cfg)["conv"] + L * D
    # forward: x in, [B, C, u] out and in again, the gated row out and in,
    # the output row out (8 D floats a token); backward: the same rows'
    # gradients and the forward's rows read again (16 D)
    floats = 24 * D
    return {
        "flops": n * tokens * 3 * (2 * mixer_params(cfg)["conv"] + (2 * L + 1) * D),
        "bytes": 4 * n * (floats * tokens + 3 * weights),
    }


def gqa_attn(cfg: dict, sequences: int, seq_len: int) -> dict:
    """Causal softmax attention of every attention layer, forward and
    backward: the causal half, over the query heads."""
    H, Hkv, K = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim(cfg)
    pairs = seq_len * (seq_len + 1) // 2  # the causal half
    n = _count(cfg, "gqa") * sequences
    # q, o and their gradients a query head; k, v and theirs a key-value head
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
    out = {"short_conv": short_conv(cfg, sequences * seq_len),
           "gqa_attn": gqa_attn(cfg, sequences, seq_len)}
    if held_slots is not None:
        out["moe_experts"] = moe_experts(cfg, held_slots)
    return out


def step_flops(cfg: dict, sequences: int, seq_len: int) -> float:
    """Model operations of one training step: 6 x active matrix parameters
    x tokens, plus attention's scores over the causal half."""
    tokens = sequences * seq_len
    return (
        6.0 * active_matrix_params(cfg) * tokens
        + gqa_attn(cfg, sequences, seq_len)["flops"]
    )

