"""Operations and bytes of a step of the layer-pattern language model, from
the configuration's shapes alone: **the algorithm's work, whatever
implements it**.  A matrix product of ``[m, k] x [k, n]`` is ``2 m k n``
operations; a training step is forward plus backward, three times the
forward's products; nothing recomputed is counted (the blocked attention's
second pass over its scores, the checkpoints' second forward).

- delta rule: the recurrence's own count, per token and head ``7 d_k d_v``
  forward (decay the state ``d_k d_v``, ``k^T S``, the rank-one update and
  ``q^T S`` at ``2 d_k d_v`` each), not the chunked form's larger one;
- latent attention: the causal half of the scores and of the weighted sum;
- experts: the three matrices of an expert, over the token slots the held
  experts were sent (a count the step returns), not over the buffer's rows.

``cfg`` is the configuration file's dict; the cut (``n_layers``,
``experts_held``, ``vocab_rows``) is read beside the published keys."""

from __future__ import annotations

from typing import Optional


#: what ``harness/model_scopes.py`` reads of this body's trace (the driver
#: ``hybrid_lm`` names this module): the device scope of the whole jitted
#: step, and each kernel's scopes
ROOT_SCOPE = "ps.model.kimi"
KERNELS = {
    "kda_scan": ("ps.model.kda.scan",),
    "mla_attn": ("ps.model.mla.attn",),
    # the grouped product with the gather that feeds it and the scatter that
    # takes its rows back: ``moe_experts`` counts a slot's row in and out,
    # and the products alone read above their roofline (the compiler fuses
    # row traffic into ``.dispatch``; my chip run, PR 28)
    "moe_experts": ("ps.model.moe.dispatch", "ps.model.moe.experts",
                    "ps.model.moe.combine"),
}
#: kernels read a layer: kernel -> the mixer whose layers share its time
PER_LAYER: dict = {}


def layer_kinds(cfg: dict) -> list:
    lin = cfg["linear_attn_config"]
    return [
        ("kda" if i in lin["kda_layers"] else "mla",
         "dense" if i <= cfg["first_k_dense_replace"] else "experts")
        for i in range(1, cfg["n_layers"] + 1)
    ]


def _count(cfg: dict, what: str) -> int:
    return sum(what in kinds for kinds in layer_kinds(cfg))


def mixer_params(cfg: dict) -> dict:
    """Matrix parameters a token multiplies with, per mixer kind."""
    D = cfg["hidden_size"]
    lin = cfg["linear_attn_config"]
    HK = lin["num_heads"] * lin["head_dim"]
    R = cfg["model"]["low_rank_dim"]
    A = cfg["num_attention_heads"]
    dn, dr, dv, C = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"], cfg["kv_lora_rank"])
    return {
        "kda": 3 * D * HK + HK * D + 2 * (D * R + R * HK) + D * lin["num_heads"],
        "mla": D * A * (dn + dr) + D * (C + dr) + C * A * (dn + dv) + A * dv * D,
    }


def active_matrix_params(cfg: dict) -> float:
    """Matrix parameters one token's forward multiplies with on this chip:
    every mixer, the dense MLP, router and shared expert of each expert
    layer, the head over the held vocabulary, and of the routed experts the
    share a slot lands on (top-k x held / routed)."""
    D = cfg["hidden_size"]
    mix = mixer_params(cfg)
    expert = 3 * D * cfg["moe_intermediate_size"]
    per_moe = (
        D * cfg["num_experts"] + cfg["num_shared_experts"] * expert
        + expert * cfg["num_experts_per_token"] * cfg["experts_held"]
        / cfg["num_experts"]
    )
    total = D * cfg["vocab_rows"]
    for mixer, mlp in layer_kinds(cfg):
        total += mix[mixer]
        total += 3 * D * cfg["intermediate_size"] if mlp == "dense" else per_moe
    return total


def kda_scan(cfg: dict, tokens: int) -> dict:
    """The delta rule of every KDA layer, forward and backward, a step."""
    lin = cfg["linear_attn_config"]
    H, K = lin["num_heads"], lin["head_dim"]
    n = _count(cfg, "kda") * tokens * H
    # forward reads q, k, log a (K each), v (K), b and writes o (K);
    # backward reads them and dO again and writes five gradients
    floats = (5 * K + 1) + (6 * K + 1) + (4 * K + 1)
    return {"flops": 3 * 7 * K * K * n, "bytes": 4 * floats * n}


def mla_attn(cfg: dict, sequences: int, seq_len: int) -> dict:
    """Causal softmax attention of every MLA layer, forward and backward."""
    A = cfg["num_attention_heads"]
    dqk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    pairs = seq_len * (seq_len + 1) // 2  # the causal half
    n = _count(cfg, "mla") * sequences * A
    # q, k (nope part per head), v, o and their gradients, once each
    floats = seq_len * 2 * (dqk + cfg["qk_nope_head_dim"] + 2 * dv)
    return {"flops": 3 * 2 * pairs * (dqk + dv) * n, "bytes": 4 * floats * n}


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
    out = {"kda_scan": kda_scan(cfg, sequences * seq_len),
           "mla_attn": mla_attn(cfg, sequences, seq_len)}
    if held_slots is not None:
        out["moe_experts"] = moe_experts(cfg, held_slots)
    return out


def step_flops(cfg: dict, sequences: int, seq_len: int) -> float:
    """Model operations of one training step: 6 x active matrix parameters
    x tokens, plus attention's scores and the delta rule's recurrence."""
    tokens = sequences * seq_len
    return (
        6.0 * active_matrix_params(cfg) * tokens
        + mla_attn(cfg, sequences, seq_len)["flops"]
        + kda_scan(cfg, tokens)["flops"]
    )

