"""A hybrid body of gated short convolutions and grouped-query attention:
per layer a mixer (``conv``: a gated depthwise convolution of a few taps, or
``gqa``: causal softmax attention whose key and value heads each serve a
group of query heads, with per-head norms and rotary) and an MLP (``dense``
SwiGLU, or ``experts``: the held-share expert layer of ``models/moe.py``,
selected through a bias, no shared expert).

The published model this serves is LFM2-8B-A1B (``config.json``,
``model_type`` ``lfm2_moe``: 24 layers, hidden 2048, 18 ``conv`` : 6
``full_attention`` by ``layer_types``, two leading dense layers of width
7168, then 32 experts of width 1792, top 4);
:meth:`Lfm2MoeConfig.from_published` reads those keys.  Like
``models/kimi_linear.py`` it takes input embeddings, not tokens: the
embedding table lives in a ``KVServer`` (``learner/hybrid.py``).

Layer ``i``, pre-norm, ``x'`` the RMS-normed input of a sub-layer: ``h = x +
Op_i(x')``, ``y = h + FF_i(h')``; after the last layer one more RMSNorm, then
the head.

- **conv**: ``[B, C, u] = x' W_in`` (``D -> 3 D``, no bias); ``z = B * u``;
  ``c_t = sum_j w_j z_{t - (L - 1) + j}`` (depthwise, causal, ``L =
  conv_L_cache`` taps a channel, no bias, no activation); ``Op = (C * c)
  W_out``.
- **gqa**: ``q = RMSNorm_head(x' W_q)``, ``k = RMSNorm_head(x' W_k)`` (a
  learned scale of the head size each), ``v = x' W_v``; rotary at
  ``rope_theta`` on ``q`` and ``k`` (halves convention:
  ``models/transformer.py::_rotary``); causal softmax at ``1 / sqrt(head
  size)``, key-value head ``g`` serving query heads ``g G .. g G + G - 1``
  (``ops/blocked_attention.py``: ``k`` and ``v`` are never copied out per
  query head); ``W_o``.  No biases.
- **experts**: ``s = sigmoid(x' W_r)`` over all routed experts, ``sel =
  top_k(s + b)`` with ``b`` the ``expert_bias`` of ``use_expert_bias``, ``w_i
  = s_i / sum_{j in sel} s_j`` (``norm_topk_prob``) ``x
  routed_scaling_factor``; ``FF = sum_{i in sel, held} w_i E_i(x')`` over the
  experts ``[experts_first, experts_first + experts_held)`` this process
  holds.  **``expert_bias`` is a buffer**: float32, zero at start, it takes
  part in the selection only, takes no gradient, and the trainer gives it no
  update (``buffers`` names it: weight decay would otherwise move a non-zero
  one).  How training moves it is not in ``config.json``, so it is not moved
  (assumed).

**The cut**: this process holds the published layers ``[layers_first,
layers_first + n_layers)`` (0-based), ``experts_held`` experts a layer from
``experts_first``, ``vocab_size`` rows of the head.

**Precision**: parameters, residual stream, norms, router, softmax and loss
are float32; matrix products run at jax's default precision (on a TPU one
bfloat16 pass with float32 accumulation), the router's at the highest, so
that its top-k is float32's.

Heterogeneous layers are unrolled; each mixer and each MLP is a
``jax.checkpoint`` of its own (``models/moe.py::trunk``).  **How a step is
cut to fit** follows from its shapes (:func:`schedule`, one budget:
``live_elems``), not from options a caller sets.  Device scopes (under the
trainer's ``ps.model.lfm2``, which holds the whole step,
``ps.model.optimizer`` included; each is written as a path under it):
``ps.model.conv.proj`` / ``.gate`` (both gates and the taps) / ``.out``,
``ps.model.gqa.proj`` (with the head norms and rotary) / ``.attn``, and
``models/moe.py``'s (``ps.model.moe.*``, ``ps.model.mlp``,
``ps.model.head_loss``).
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
from parameter_server_tpu.models import transformer as tfm
from parameter_server_tpu.models.moe import rms_norm
from parameter_server_tpu.ops.blocked_attention import blocked_causal_attention

#: the device scope the trainer puts round a step of this body
BODY_SCOPE = "ps.model.lfm2"
#: ``layer_types`` -> this body's mixers
MIXERS = {"conv": "conv", "full_attention": "gqa"}

#: device scope ``ps.model.<name>`` as a path under this body's
_scope = functools.partial(moe.scope, BODY_SCOPE)


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    vocab_size: int  # rows of the head held here
    #: every published layer's type, ``conv`` or ``full_attention``
    layer_types: Tuple[str, ...]
    #: the layers held here: published layers [layers_first, + n_layers)
    n_layers: int
    layers_first: int = 0
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_dense_layers: int = 2
    n_routed_experts: int = 32
    #: the share held here: experts [experts_first, experts_first + held)
    experts_held: int = 32
    experts_first: int = 0
    num_experts_per_token: int = 4
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    conv_L_cache: int = 3
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    #: not in config.json (``head_dim`` null): hidden / heads, assumed
    head_dim: int = 64
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    init_scale: float = 0.02
    attn_block: int = 256
    moe_block: int = 512
    #: elements of the conv mixer's widest float32 activation (``[B, S, 3
    #: D]``) that may be live at once; :func:`schedule` cuts a step to it
    live_elems: int = 1 << 26
    tie_embeddings: bool = False  # the head is dense, the table PS-served

    #: leaves of the parameter tree that are buffers: they take no update
    #: (``learner/hybrid.py`` masks them by name)
    buffers = ("expert_bias",)

    @property
    def d_model(self) -> int:
        return self.hidden_size

    def hybrid_body(self, seed: int, loss_chunk: int):
        """What ``learner/hybrid.py::HybridLMTrainer`` trains
        (``models/moe.py::hybrid_body``)."""
        return moe.hybrid_body(sys.modules[__name__], self, seed, loss_chunk)

    @classmethod
    def from_published(cls, pub: dict, **cut) -> "Lfm2MoeConfig":
        """From ``config.json``'s keys; ``cut`` holds the cut (``n_layers``,
        ``layers_first``, ``experts_held``, ``experts_first``,
        ``vocab_size``) and anything assumed."""
        types = tuple(pub["layer_types"])
        if (
            pub.get("model_type", "lfm2_moe") != "lfm2_moe"
            or pub["conv_bias"]
            or set(types) - set(MIXERS)
            or len(types) != pub["num_hidden_layers"]
            or pub["hidden_size"] % pub["num_attention_heads"]
            or pub["num_attention_heads"] % pub["num_key_value_heads"]
        ):
            raise ValueError("a published key this body has no code for")
        kw = dict(
            vocab_size=pub["vocab_size"], layer_types=types,
            n_layers=pub["num_hidden_layers"],
            hidden_size=pub["hidden_size"],
            intermediate_size=pub["intermediate_size"],
            moe_intermediate_size=pub["moe_intermediate_size"],
            num_dense_layers=pub["num_dense_layers"],
            n_routed_experts=pub["num_experts"],
            experts_held=pub["num_experts"],
            num_experts_per_token=pub["num_experts_per_tok"],
            routed_scaling_factor=float(pub["routed_scaling_factor"]),
            norm_topk_prob=pub["norm_topk_prob"],
            use_expert_bias=pub["use_expert_bias"],
            conv_L_cache=pub["conv_L_cache"],
            num_attention_heads=pub["num_attention_heads"],
            num_key_value_heads=pub["num_key_value_heads"],
            head_dim=pub.get("head_dim")
            or pub["hidden_size"] // pub["num_attention_heads"],
            rope_theta=float(pub["rope_theta"]), norm_eps=pub["norm_eps"],
        )
        kw.update(cut)
        return cls(**kw)

    def layer_kinds(self):
        """``[(mixer, mlp), ...]`` of the layers held here."""
        held = range(self.layers_first, self.layers_first + self.n_layers)
        if held.stop > len(self.layer_types):
            raise ValueError(f"layers {held} of {len(self.layer_types)}")
        return [
            (MIXERS[self.layer_types[i]],
             "dense" if i < self.num_dense_layers else "experts")
            for i in held
        ]


def tiny_config(**kw) -> Lfm2MoeConfig:
    """Small config for tests and ``app.create``: same code paths (a dense
    conv layer, then an attention and a conv layer with experts), toy
    sizes."""
    defaults = dict(
        vocab_size=256, layer_types=("conv", "full_attention", "conv"),
        n_layers=3, num_dense_layers=1, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, n_routed_experts=8, experts_held=2,
        num_experts_per_token=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, attn_block=16, moe_block=8,
    )
    defaults.update(kw)
    return Lfm2MoeConfig(**defaults)


def expert_layer(cfg: Lfm2MoeConfig) -> moe.ExpertLayer:
    """What ``models/moe.py``'s expert layer is told by this body."""
    return moe.ExpertLayer(
        root=BODY_SCOPE, n_routed=cfg.n_routed_experts,
        held=cfg.experts_held, first=cfg.experts_first,
        top_k=cfg.num_experts_per_token, scale=cfg.routed_scaling_factor,
        renormalize=cfg.norm_topk_prob, block=cfg.moe_block,
    )


def schedule(cfg: Lfm2MoeConfig, batch: int, seq: int) -> bool:
    """``by_sequence`` of a step of ``batch`` sequences of ``seq`` tokens:
    whether the mixers and the dense MLP run one sequence at a time
    (``models/moe.py::by_sequence``) so that the conv mixer's widest float32
    activation (``[batch, seq, 3 x hidden]``; the dense MLP's ``[batch, seq,
    intermediate]`` is of its order) stays within ``cfg.live_elems``.  It
    changes no result.

    At the published widths and 2 x 8,192 tokens: one sequence at a time
    (2^26 elements, 256 MiB an activation)."""
    return batch > 1 and batch * seq * 3 * cfg.hidden_size > cfg.live_elems


# -- parameters ---------------------------------------------------------------
def param_shapes(cfg: Lfm2MoeConfig) -> dict:
    """The parameter tree as ``{name: ... shape}``; kernels under a
    ``kernel`` leaf so that ``parallel/tp.py`` reads them by name."""
    D, F = cfg.hidden_size, cfg.moe_intermediate_size
    H, Hkv, K = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    swiglu = functools.partial(moe.swiglu_shapes, D)
    conv = {
        # [B, C, u] = x' W_in: one product, the three parts on a leading axis
        "in_proj": {"kernel": (3, D, D)}, "taps": (cfg.conv_L_cache, D),
        "out_proj": {"kernel": (D, D)},
    }
    gqa = {
        "q": {"kernel": (D, H, K)}, "k": {"kernel": (D, Hkv, K)},
        "v": {"kernel": (D, Hkv, K)},
        "q_norm": {"scale": (K,)}, "k_norm": {"scale": (K,)},
        "o": {"kernel": (H, K, D)},
    }
    experts = {
        "router": {"kernel": (D, cfg.n_routed_experts)},
        "experts": {k: v["kernel"] for k, v in
                    swiglu(F, (cfg.experts_held,)).items()},
    }
    if cfg.use_expert_bias:
        experts["expert_bias"] = (cfg.n_routed_experts,)
    tree = {}
    for i, (mixer, mlp) in enumerate(cfg.layer_kinds()):
        tree[f"layer_{i}"] = {
            "mixer_norm": {"scale": (D,)},
            mixer: conv if mixer == "conv" else gqa,
            "mlp_norm": {"scale": (D,)},
            **({"mlp": swiglu(cfg.intermediate_size)} if mlp == "dense"
               else {"moe": experts}),
        }
    tree["final_norm"] = {"scale": (D,)}
    tree["lm_head"] = {"kernel": (D, cfg.vocab_size)}
    return tree


def count_params(cfg: Lfm2MoeConfig) -> dict:
    """``held`` and ``active`` parameters of this body
    (``models/moe.py::count_params``); the selection bias counts as held."""
    return moe.count_params(param_shapes(cfg), expert_layer(cfg))


def init_params(cfg: Lfm2MoeConfig, key) -> dict:
    """Seeded float32 parameters (initial scales: assumed; the file of the
    benchmark's configuration lists them)."""
    def special(leaf, k, shape):
        if leaf == "expert_bias":  # a buffer, zero at start
            return jnp.zeros(shape, jnp.float32)
        if leaf == "taps":  # as a depthwise conv's default: 1 / sqrt(taps)
            bound = 1.0 / np.sqrt(shape[0])
            return jax.random.uniform(k, shape, minval=-bound, maxval=bound)
        return None

    return moe.init_tree(param_shapes(cfg), key, cfg.init_scale, special)


# -- layers ---------------------------------------------------------------------
def conv_mixer(cfg: Lfm2MoeConfig, p, x):
    with _scope("conv.proj"):
        b, c, u = jnp.einsum("bsd,gdc->gbsc", x, p["in_proj"]["kernel"])
    with _scope("conv.gate"):
        y = c * moe.causal_conv(b * u, p["taps"])
    with _scope("conv.out"):
        return y @ p["out_proj"]["kernel"]


def gqa_mixer(cfg: Lfm2MoeConfig, p, x):
    B, S, _ = x.shape
    with _scope("gqa.proj"):
        q, k, v = (
            jnp.einsum("bsd,dhk->bshk", x, p[n]["kernel"]) for n in "qkv"
        )
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        q, k = (
            tfm._rotary(
                rms_norm(a, p[f"{n}_norm"]["scale"], cfg.norm_eps),
                positions, cfg.rope_theta, halves=True,
            )
            for a, n in ((q, "q"), (k, "k"))
        )
    with _scope("gqa.attn"):
        o = blocked_causal_attention(
            q, k, v, block=cfg.attn_block,
            scale=1.0 / np.sqrt(cfg.head_dim),
        )
    with _scope("gqa.proj"):
        return jnp.einsum("bshk,hkd->bsd", o, p["o"]["kernel"])


def _mixer_block(cfg, kind, p, x):
    h = rms_norm(x, p["mixer_norm"]["scale"], cfg.norm_eps)
    if kind == "conv":
        return x + conv_mixer(cfg, p["conv"], h)
    return x + gqa_mixer(cfg, p["gqa"], h)


def trunk(cfg: Lfm2MoeConfig, params, x):
    """``x [B, S, D]`` input embeddings -> ``(hidden [B, S, D], counters)``."""
    return moe.trunk(
        expert_layer(cfg), cfg.layer_kinds(),
        lambda mixer: functools.partial(_mixer_block, cfg, mixer),
        cfg.norm_eps, schedule(cfg, x.shape[0], x.shape[1]), params, x,
    )


def loss_fn(cfg: Lfm2MoeConfig, params, emb_in, targets, loss_chunk: int = 0):
    """Next-token loss over the held vocabulary -> ``(loss, counters)``.
    ``loss_chunk > 0`` fuses the head into the chunked loss."""
    hidden, counters = trunk(cfg, params, emb_in)
    return moe.head_loss(
        BODY_SCOPE, hidden, params["lm_head"]["kernel"], targets, loss_chunk
    ), counters


def logits(cfg: Lfm2MoeConfig, params, emb_in):
    hidden, _ = trunk(cfg, params, emb_in)
    return moe.head_logits(hidden, params["lm_head"]["kernel"])
