"""A hybrid body with a layer pattern: per layer a mixer (``kda`` gated
delta-rule linear attention, or ``mla`` latent attention without rotary) and
an MLP (``dense`` SwiGLU, or ``experts``: a sigmoid router over all routed
experts, of which this process holds a share, plus a shared expert).

The published model this serves is Kimi-Linear-48B-A3B-Instruct
(``config.json``: 27 layers, hidden 2304, 3 KDA : 1 MLA, 256 routed experts
of width 1024, top 8); :meth:`KimiLinearConfig.from_published` reads those
keys.  Like ``models/transformer.py::TransformerBody`` it takes input
embeddings, not tokens: the embedding table lives in a ``KVServer``
(``learner/hybrid.py``).

With ``x'`` the RMS-normed input of a sub-layer:

- **KDA**, per head, ``d_k = d_v``: ``q, k, v = SiLU(conv(x' W_q|k|v))``
  (causal depthwise convolution over time); ``q <- q / |q| / sqrt(d_k)``,
  ``k <- k / |k|``; ``log a_t = -exp(A_log) softplus(W_f2 (W_f1 x'_t) +
  dt_bias)`` per channel; ``b_t = sigmoid(w_b x'_t)``; the delta rule of
  ``ops/delta_rule.py``; ``y = W_o [RMSNorm_head(o_t) sigmoid(W_g2 (W_g1
  x'_t) + b_g)]``.
- **MLA**: ``[c, k_pe] = x' W_kva``; ``c <- RMSNorm(c)``; ``[k_nope_h, v_h]
  = c W_kvb``; ``q_h = x' W_q``; ``k_h = [k_nope_h, k_pe]`` with ``k_pe``
  shared by the heads and left unrotated; causal softmax at ``1 /
  sqrt(d_nope + d_pe)``; ``W_o``.  Blocked over queries
  (``ops/blocked_attention.py``).
- **Experts**: the held-share expert layer of ``models/moe.py``, which
  both layer-pattern bodies call: ``s = sigmoid(x' W_r)`` over all 256
  routed experts, ``sel = top_8(s)`` (the published selection bias is held
  at zero here: assumed, so this body passes none), ``w_i = scale s_i /
  sum_{j in sel} s_j``; ``y = sum_{i in sel, held} w_i E_i(x') +
  E_shared(x')`` over the experts ``[experts_first, experts_first +
  experts_held)`` this process holds.

**Precision**: parameters, residual stream, norms, router, decay, state,
softmax and loss are float32; matrix products run at jax's default precision
(on a TPU one bfloat16 pass with float32 accumulation), the router's at the
highest, so that its top-k is float32's.

Heterogeneous layers are unrolled; each mixer and each MLP is a
``jax.checkpoint`` of its own.  **How a step is cut to fit** follows from its
shapes (:func:`schedule`, one budget: ``live_elems``), not from options a
caller sets.  Device scopes (under the trainer's
``ps.model.kimi``, which holds the whole step, ``ps.model.optimizer``
included; each is written as a path under it, :func:`_scope`):
``ps.model.kda.proj`` / ``.conv`` / ``.scan`` / ``.out``,
``ps.model.mla.proj`` / ``.attn``, and ``models/moe.py``'s
(``ps.model.moe.*``, ``ps.model.mlp``, ``ps.model.head_loss``).
"""

from __future__ import annotations

import dataclasses
import functools
import sys
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_tpu.models import moe
from parameter_server_tpu.models.moe import (  # noqa: F401  (this body's API)
    COUNTERS, dispatch_layout, rms_norm,
)
from parameter_server_tpu.ops.blocked_attention import blocked_causal_attention
from parameter_server_tpu.ops.delta_rule import chunk_kda

#: the device scope the trainer puts round a step of this body
BODY_SCOPE = "ps.model.kimi"


#: device scope ``ps.model.<name>`` as a path under this body's
#: (``models/moe.py::scope`` says why)
_scope = functools.partial(moe.scope, BODY_SCOPE)


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int  # rows of the head held here
    n_layers: int
    #: 1-based layer numbers, as published
    kda_layers: Tuple[int, ...]
    full_attn_layers: Tuple[int, ...]
    hidden_size: int = 2304
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    first_k_dense_replace: int = 1
    n_routed_experts: int = 256
    #: the share held here: experts [experts_first, experts_first + held)
    experts_held: int = 256
    experts_first: int = 0
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    moe_renormalize: bool = True
    linear_num_heads: int = 32
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rms_norm_eps: float = 1e-5
    #: rank of KDA's two low-rank projections (not in config.json: assumed)
    low_rank_dim: int = 128
    init_scale: float = 0.02
    kda_chunk: int = 64
    attn_block: int = 256
    moe_block: int = 512
    #: elements of one of a mixer's float32 activations (``[B, S, heads, head
    #: size]``) that may be live at once; :func:`schedule` cuts a step to it
    live_elems: int = 1 << 23
    tie_embeddings: bool = False  # the head is dense, the table PS-served

    @property
    def d_model(self) -> int:
        return self.hidden_size

    def hybrid_body(self, seed: int, loss_chunk: int):
        """What ``learner/hybrid.py::HybridLMTrainer`` trains
        (``models/moe.py::hybrid_body``)."""
        return moe.hybrid_body(sys.modules[__name__], self, seed, loss_chunk)

    @classmethod
    def from_published(cls, pub: dict, **over) -> "KimiLinearConfig":
        """From ``config.json``'s keys; ``over`` holds the cut (``n_layers``,
        ``experts_held``, ``vocab_size``) and anything assumed."""
        lin = pub["linear_attn_config"]
        kw = dict(
            vocab_size=pub["vocab_size"], n_layers=pub["num_hidden_layers"],
            kda_layers=tuple(lin["kda_layers"]),
            full_attn_layers=tuple(lin["full_attn_layers"]),
            hidden_size=pub["hidden_size"],
            intermediate_size=pub["intermediate_size"],
            moe_intermediate_size=pub["moe_intermediate_size"],
            first_k_dense_replace=pub["first_k_dense_replace"],
            n_routed_experts=pub["num_experts"],
            experts_held=pub["num_experts"],
            num_experts_per_token=pub["num_experts_per_token"],
            num_shared_experts=pub["num_shared_experts"],
            routed_scaling_factor=pub["routed_scaling_factor"],
            moe_renormalize=pub["moe_renormalize"],
            linear_num_heads=lin["num_heads"], linear_head_dim=lin["head_dim"],
            short_conv_kernel_size=lin["short_conv_kernel_size"],
            num_attention_heads=pub["num_attention_heads"],
            kv_lora_rank=pub["kv_lora_rank"],
            qk_nope_head_dim=pub["qk_nope_head_dim"],
            qk_rope_head_dim=pub["qk_rope_head_dim"],
            v_head_dim=pub["v_head_dim"], rms_norm_eps=pub["rms_norm_eps"],
        )
        if pub.get("hidden_act", "silu") != "silu" or pub.get(
            "moe_router_activation_func", "sigmoid"
        ) != "sigmoid" or not pub.get("mla_use_nope", True) or pub.get(
            "q_lora_rank"
        ) is not None or pub.get("num_expert_group", 1) != 1:
            raise ValueError("a published key this body has no code for")
        kw.update(over)
        return cls(**kw)

    def layer_kinds(self):
        """``[(mixer, mlp), ...]`` of layers ``1 .. n_layers``."""
        kinds = []
        for i in range(1, self.n_layers + 1):
            if i in self.kda_layers:
                mixer = "kda"
            elif i in self.full_attn_layers:
                mixer = "mla"
            else:
                raise ValueError(f"layer {i} is in neither layer list")
            kinds.append(
                (mixer, "dense" if i <= self.first_k_dense_replace else "experts")
            )
        return kinds


def tiny_config(**kw) -> KimiLinearConfig:
    """Small config for tests and ``app.create``: same code paths (K K M K
    behind nothing dense would hide the dense MLP, so K M with layer 1 dense),
    toy sizes."""
    defaults = dict(
        vocab_size=256, n_layers=2, kda_layers=(1,), full_attn_layers=(2,),
        hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
        n_routed_experts=8, experts_held=2, num_experts_per_token=2,
        linear_num_heads=2, linear_head_dim=16, num_attention_heads=2,
        kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, low_rank_dim=8, kda_chunk=16, attn_block=16,
        moe_block=8,
    )
    defaults.update(kw)
    return KimiLinearConfig(**defaults)


def schedule(cfg: KimiLinearConfig, batch: int, seq: int):
    """``(by_sequence, kda_head_groups)`` of a step of ``batch``
    sequences of ``seq`` tokens: how it is cut so that one of a KDA mixer's
    float32 activations (``[batch, seq, heads, head size]``: it keeps a dozen
    of them) stays within ``cfg.live_elems``.  None changes a result.

    - ``by_sequence``: the mixers and the dense MLP run one sequence at a
      time (``lax.map`` over the batch; over a mesh whose ``data`` axis splits
      the batch this serialises its devices: give such a mesh the budget its
      per-device batch needs);
    - ``kda_head_groups``: KDA runs its heads in that many groups, one after
      the other (a head shares nothing with another but the input and the sum
      into ``W_o``): the smallest divisor of the heads that fits the budget.

    At the published widths and 2 x 8,192 tokens: one sequence at a time, 4
    groups of 8 heads (2^23 elements, 32 MiB an activation)."""
    H, K = cfg.linear_num_heads, cfg.linear_head_dim
    by_sequence = batch > 1 and batch * seq * H * K > cfg.live_elems
    rows = seq if by_sequence else batch * seq
    groups = next(
        (g for g in range(1, H + 1)
         if H % g == 0 and rows * (H // g) * K <= cfg.live_elems), H
    )
    return by_sequence, groups


# -- parameters ---------------------------------------------------------------
def param_shapes(cfg: KimiLinearConfig) -> dict:
    """The parameter tree as ``{name: ... shape}``; kernels under a
    ``kernel`` leaf so that ``parallel/tp.py`` reads them by name."""
    D = cfg.hidden_size
    H, K, R = cfg.linear_num_heads, cfg.linear_head_dim, cfg.low_rank_dim
    A, dn, dr, dv = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                     cfg.qk_rope_head_dim, cfg.v_head_dim)
    C, F, Fd = cfg.kv_lora_rank, cfg.moe_intermediate_size, cfg.intermediate_size
    W = cfg.short_conv_kernel_size

    swiglu = functools.partial(moe.swiglu_shapes, D)
    kda = {
        "q": {"kernel": (D, H, K)}, "k": {"kernel": (D, H, K)},
        "v": {"kernel": (D, H, K)},
        "conv_q": (W, H, K), "conv_k": (W, H, K), "conv_v": (W, H, K),
        "f_a": {"kernel": (D, R)}, "f_b": {"kernel": (R, H, K)},
        "A_log": (H,), "dt_bias": (H, K),
        "b": {"kernel": (D, H)},
        "g_a": {"kernel": (D, R)}, "g_b": {"kernel": (R, H, K), "bias": (H, K)},
        "o_norm": {"scale": (K,)}, "o": {"kernel": (H, K, D)},
    }
    mla = {
        "q": {"kernel": (D, A, dn + dr)}, "kv_a": {"kernel": (D, C + dr)},
        "kv_norm": {"scale": (C,)}, "kv_b": {"kernel": (C, A, dn + dv)},
        "o": {"kernel": (A, dv, D)},
    }
    experts = {
        "router": {"kernel": (D, cfg.n_routed_experts)},
        "experts": {k: v["kernel"] for k, v in
                    swiglu(F, (cfg.experts_held,)).items()},
        "shared": swiglu(F * cfg.num_shared_experts),
    }
    tree = {}
    for i, (mixer, mlp) in enumerate(cfg.layer_kinds()):
        tree[f"layer_{i}"] = {
            "mixer_norm": {"scale": (D,)},
            mixer: kda if mixer == "kda" else mla,
            "mlp_norm": {"scale": (D,)},
            **({"mlp": swiglu(Fd)} if mlp == "dense" else {"moe": experts}),
        }
    tree["final_norm"] = {"scale": (D,)}
    tree["lm_head"] = {"kernel": (D, cfg.vocab_size)}
    return tree


def count_params(cfg: KimiLinearConfig) -> dict:
    """``held`` and ``active`` parameters of this body
    (``models/moe.py::count_params``)."""
    return moe.count_params(param_shapes(cfg), expert_layer(cfg))


def init_params(cfg: KimiLinearConfig, key) -> dict:
    """Seeded float32 parameters (initial scales: assumed; the file of the
    benchmark's configuration lists them)."""
    def special(leaf, k, shape):
        if leaf == "A_log":  # decay rates exp(A_log) in [1, 16)
            return jnp.log(jax.random.uniform(k, shape, minval=1.0, maxval=16.0))
        if leaf == "dt_bias":  # softplus^-1 of a step in [1e-3, 1e-1), log-uniform
            dt = jnp.exp(jax.random.uniform(
                k, shape, minval=np.log(1e-3), maxval=np.log(1e-1)
            ))
            return dt + jnp.log(-jnp.expm1(-dt))
        if leaf.startswith("conv_"):  # as a depthwise conv's default: 1 / sqrt(width)
            bound = 1.0 / np.sqrt(shape[0])
            return jax.random.uniform(k, shape, minval=-bound, maxval=bound)
        return None

    return moe.init_tree(param_shapes(cfg), key, cfg.init_scale, special)


# -- layers ---------------------------------------------------------------------
def kda_mixer(cfg: KimiLinearConfig, p, x):
    K = cfg.linear_head_dim
    with _scope("kda.proj"):
        q, k, v = (
            jnp.einsum("bsd,dhk->bshk", x, p[n]["kernel"]) for n in "qkv"
        )
        f = jnp.einsum(
            "bsr,rhk->bshk", x @ p["f_a"]["kernel"], p["f_b"]["kernel"]
        )
        gate = jnp.einsum(
            "bsr,rhk->bshk", x @ p["g_a"]["kernel"], p["g_b"]["kernel"]
        ) + p["g_b"]["bias"]
        beta = jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", x, p["b"]["kernel"]))
    with _scope("kda.conv"):
        q, k, v = (
            jax.nn.silu(moe.causal_conv(a, p[f"conv_{n}"]))
            for a, n in ((q, "q"), (k, "k"), (v, "v"))
        )
        q, k = moe.l2_normalize(q) / np.sqrt(K), moe.l2_normalize(k)
        log_a = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(f + p["dt_bias"])
    with _scope("kda.scan"):
        o, _state = chunk_kda(q, k, v, log_a, beta, chunk=cfg.kda_chunk)
    with _scope("kda.out"):
        o = rms_norm(o, p["o_norm"]["scale"], cfg.rms_norm_eps)
        return jnp.einsum(
            "bshk,hkd->bsd", o * jax.nn.sigmoid(gate), p["o"]["kernel"]
        )


#: where KDA's parameters carry their head axis (none: shared by the heads)
_KDA_HEAD_AXIS = {
    ("q", "kernel"): 1, ("k", "kernel"): 1, ("v", "kernel"): 1,
    ("conv_q",): 1, ("conv_k",): 1, ("conv_v",): 1,
    ("f_b", "kernel"): 1, ("g_b", "kernel"): 1, ("g_b", "bias"): 0,
    ("A_log",): 0, ("dt_bias",): 0, ("b", "kernel"): 1, ("o", "kernel"): 0,
}


def kda_mixer_grouped(cfg: KimiLinearConfig, G: int, p, x):
    """:func:`kda_mixer` over ``G`` groups of heads in turn, their outputs
    summed: the same sum ``W_o`` takes over all heads."""
    if G == 1:
        return kda_mixer(cfg, p, x)

    def split(path, a):
        axis = _KDA_HEAD_AXIS.get(tuple(k.key for k in path))
        if axis is None:
            return jnp.broadcast_to(a, (G, *a.shape))
        shape = (*a.shape[:axis], G, a.shape[axis] // G, *a.shape[axis + 1:])
        return jnp.moveaxis(a.reshape(shape), axis, 0)

    groups = jax.tree_util.tree_map_with_path(split, p)
    one = jax.checkpoint(functools.partial(kda_mixer, cfg))
    y, _ = jax.lax.scan(
        lambda acc, pg: (acc + one(pg, x), None),
        jnp.zeros(x.shape, jnp.float32), groups,
    )
    return y


def mla_mixer(cfg: KimiLinearConfig, p, x):
    dn, dr, C = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    with _scope("mla.proj"):
        q = jnp.einsum("bsd,dhk->bshk", x, p["q"]["kernel"])
        kva = x @ p["kv_a"]["kernel"]
        c = rms_norm(kva[..., :C], p["kv_norm"]["scale"], cfg.rms_norm_eps)
        kv = jnp.einsum("bsr,rhk->bshk", c, p["kv_b"]["kernel"])
    with _scope("mla.attn"):
        # k_h = [k_nope_h, k_pe], k_pe shared by the heads and not rotated
        o = blocked_causal_attention(
            q[..., :dn], kv[..., :dn], kv[..., dn:], block=cfg.attn_block,
            scale=1.0 / np.sqrt(dn + dr),
            q_shared=q[..., dn:], k_shared=kva[..., C:],
        )
    with _scope("mla.proj"):
        return jnp.einsum("bshk,hkd->bsd", o, p["o"]["kernel"])


def expert_layer(cfg: KimiLinearConfig) -> moe.ExpertLayer:
    """What ``models/moe.py``'s expert layer is told by this body: the top 8
    of 256 sigmoid scores, renormalised and scaled, a shared expert in the
    parameters and no selection bias (a buffer held at zero and not trained:
    assumed, so the parameters hold none and selection is by the scores
    alone)."""
    return moe.ExpertLayer(
        root=BODY_SCOPE, n_routed=cfg.n_routed_experts,
        held=cfg.experts_held, first=cfg.experts_first,
        top_k=cfg.num_experts_per_token, scale=cfg.routed_scaling_factor,
        renormalize=cfg.moe_renormalize, block=cfg.moe_block,
    )


def moe_capacity(cfg: KimiLinearConfig, tokens: int) -> int:
    return moe.moe_capacity(expert_layer(cfg), tokens)


def route(cfg: KimiLinearConfig, router_kernel, xt):
    """``xt [N, D]`` -> ``(idx [N, k] of all routed experts, w [N, k])``:
    ``models/moe.py::route`` without a selection bias (this body's is held
    at zero: assumed), so selection and weights are both the scores'."""
    return moe.route(expert_layer(cfg), router_kernel, xt)


def moe_layer(cfg: KimiLinearConfig, p, x):
    """``x [B, S, D]`` (normed) -> ``(y, counters)``: the held experts' part
    of the routed sum plus the shared expert (``models/moe.py``)."""
    return moe.moe_layer(expert_layer(cfg), p, x)


def _mixer_block(cfg, kind, groups, p, x):
    """``groups``: KDA's head groups (:func:`schedule`)."""
    h = rms_norm(x, p["mixer_norm"]["scale"], cfg.rms_norm_eps)
    if kind == "kda":
        return x + kda_mixer_grouped(cfg, groups, p[kind], h)
    return x + mla_mixer(cfg, p[kind], h)


def trunk(cfg: KimiLinearConfig, params, x):
    """``x [B, S, D]`` input embeddings -> ``(hidden [B, S, D], counters)``."""
    by_sequence, groups = schedule(cfg, x.shape[0], x.shape[1])
    return moe.trunk(
        expert_layer(cfg), cfg.layer_kinds(),
        lambda mixer: functools.partial(_mixer_block, cfg, mixer, groups),
        cfg.rms_norm_eps, by_sequence, params, x,
    )


def loss_fn(cfg: KimiLinearConfig, params, emb_in, targets, loss_chunk: int = 0):
    """Next-token loss over the held vocabulary -> ``(loss, counters)``.
    ``loss_chunk > 0`` fuses the head into the chunked loss."""
    hidden, counters = trunk(cfg, params, emb_in)
    return moe.head_loss(
        BODY_SCOPE, hidden, params["lm_head"]["kernel"], targets, loss_chunk
    ), counters


def logits(cfg: KimiLinearConfig, params, emb_in):
    hidden, _ = trunk(cfg, params, emb_in)
    return moe.head_logits(hidden, params["lm_head"]["kernel"])
