"""A hybrid body of gated DeltaNet and gated attention layers, every layer
an expert layer with a gated shared expert: per layer a mixer (``gdn``: the
delta rule under a scalar decay a head, over grouped key heads; or
``full``: causal softmax attention whose output is gated element by
element) and the held-share expert layer of ``models/moe.py``.

The published model this serves is Qwen3-Next-80B-A3B-Instruct
(``config.json``, ``model_type`` ``qwen3_next``: 48 layers, hidden 2048,
``full_attention_interval`` 4 so that layer ``i`` is full attention when
``(i + 1) % 4 == 0`` and gated DeltaNet otherwise, 16 query heads over 2
key-value heads of 256, ``partial_rotary_factor`` 0.25 at ``rope_theta``
1e7, gated DeltaNet with 16 key heads and 32 value heads of 128 and a
convolution of width 4, ``decoder_sparse_step`` 1 and ``mlp_only_layers``
[] so that every layer is an expert layer, 512 experts of width 512, top
10, ``norm_topk_prob`` true, a shared expert of 512, an untied head,
vocabulary 151,936, ``rms_norm_eps`` 1e-6); :meth:`Qwen3NextConfig.
from_published` reads those keys by name.  Like its siblings it takes
input embeddings, not tokens: the embedding table lives in a ``KVServer``
(``learner/hybrid.py``).

Layer ``i``, pre-norm, ``x'`` the normed input of a sub-layer: ``h = x +
Mix_i(x')``, ``y = h + MoE(h')``; after the last layer one more norm, then
the head.  **Every norm but the DeltaNet output's is zero-centred**:
``x^ (1 + w)``, ``x^ = x / rms(x)``, ``w`` initialised at 0
(``models/moe.py::norm``, an ``offset`` leaf).

- **gated DeltaNet** (``Hk`` key heads, ``Hv`` value heads, ``r = Hv /
  Hk``, sizes ``d_k``, ``d_v``): ``x' W_qkvz``, ``W_qkvz [D, Hk, 2 d_k + 2
  r d_v]``, gives per key head ``q``, ``k`` and its ``r`` value heads'
  ``v`` and ``z`` (the published layout, grouped by key head); ``x' W_ba``,
  ``[D, Hk, 2 r]``, gives ``b`` and ``a`` of those value heads; a causal
  depthwise convolution of width 4 without bias over the channels of ``[q ;
  k ; v]``, then SiLU (its taps ``[W, Hk, 2 d_k + r d_v]`` grouped by key
  head as ``W_qkvz`` is: the published channel order permuted, so that
  they split with the key heads); ``q <- q / |q| / sqrt(d_k)``, ``k <- k / |k|`` (eps
  1e-6 under the root); ``beta = sigmoid(b)``, ``g = -exp(A_log)
  softplus(a + dt_bias)``, one value a value head and position; the delta
  rule ``S_t = e^(g_t) S_{t-1} + beta_t k_t (v_t - e^(g_t) S_{t-1}^T
  k_t)^T``, ``o_t = S_t^T q_t``, value head ``j`` reading key head ``j //
  r`` (``ops/delta_rule.py::chunk_gated_delta``); ``RMSNorm(o) w *
  silu(z)`` over each value head's ``d_v`` in float32, ``w`` initialised at
  ones; ``W_o [Hv, d_v, D]``.
- **gated attention**: ``x' W_q``, ``W_q [D, H, 2 K]``, gives per head the
  query (its first ``K``) and a gate (its last ``K``); ``k = x' W_k``, ``v
  = x' W_v`` ``[.., Hkv, K]``, no biases; a zero-centred RMSNorm over every
  head's ``K`` of ``q`` and of ``k``; rotary over the first
  ``partial_rotary_factor x K`` dimensions (the halves convention, ``theta``
  ``rope_theta``), the rest passed through; causal softmax at ``1 /
  sqrt(K)``, key head ``g`` serving query heads ``g G .. g G + G - 1``
  (``ops/blocked_attention.py``); ``o <- o * sigmoid(gate)`` element by
  element; ``Attn = concat_h(o_h) W_o``.
- **experts**: ``p = softmax(x' W_r)`` over all ``num_experts`` in float32,
  the top ``k`` renormalised, scale 1; ``MoE = sum_sel w_i SwiGLU_i(x') +
  sigmoid(x' W_sg) SwiGLU_shared(x')``, ``W_sg [D, 1]``, of which this
  process computes its held experts' part (``models/moe.py``).

**Left out**: the multi-token-prediction module (``num_nextn_predict_layers``
of the released checkpoint), which ``transformers``' ``qwen3_next`` does not
run either.  **Initialisation** (published): ``A_log = log U(0, 16]``,
``dt_bias`` 1, the DeltaNet output norm's ``w`` 1, the zero-centred norms'
``w`` 0, every matrix and the convolution's taps normal 0.02.

**The cut**: this process holds the published layers ``[layers_first,
layers_first + n_layers)``, ``experts_held`` experts a layer from
``experts_first``, ``vocab_size`` rows of the head.

**Precision**: parameters, residual stream, norms, router, decay, state,
rotary, softmax and loss are float32; matrix products run at jax's default
precision (on a TPU one bfloat16 pass with float32 accumulation), the
router's at the highest, so that its top-k is float32's.

Heterogeneous layers are unrolled; each mixer and each MLP is a
``jax.checkpoint`` of its own (``models/moe.py::trunk``).  **How a step is
cut to fit** follows from its shapes (:func:`schedule`, one budget:
``live_elems``), and no option chooses a path.  Device scopes (under the
trainer's ``ps.model.qwen3_next``, which holds the whole step,
``ps.model.optimizer`` included; each written as a path under it):
``ps.model.gdn.proj`` (``W_qkvz``, ``W_ba``, ``W_o``), ``.conv`` (the
convolution, the norms of ``q`` and ``k``, ``beta`` and ``g``), ``.scan``
(the delta rule), ``.norm`` (the gated output norm); ``ps.model.attn.proj``
(q with its gate, k, v, the head norms, rotary, ``W_o``), ``.full`` (the
causal tiles), ``.gate`` (the sigmoid and the product with the heads'
outputs); and ``models/moe.py``'s (``ps.model.moe.*``, ``.shared`` with its
gate, ``ps.model.head_loss``).
"""

from __future__ import annotations

import dataclasses
import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_tpu.models import moe
from parameter_server_tpu.models import transformer as tfm
from parameter_server_tpu.models.moe import rms_norm
from parameter_server_tpu.ops.blocked_attention import blocked_causal_attention
from parameter_server_tpu.ops.delta_rule import chunk_gated_delta

#: the device scope the trainer puts round a step of this body
BODY_SCOPE = "ps.model.qwen3_next"

#: device scope ``ps.model.<name>`` as a path under this body's
_scope = functools.partial(moe.scope, BODY_SCOPE)


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int  # rows of the head held here
    #: the layers held here: published layers [layers_first, + n_layers)
    n_layers: int
    layers_first: int = 0
    num_hidden_layers: int = 48  # published
    full_attention_interval: int = 4
    hidden_size: int = 2048
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    n_routed_experts: int = 512
    #: the share held here: experts [experts_first, experts_first + held)
    experts_held: int = 512
    experts_first: int = 0
    num_experts_per_token: int = 10
    norm_topk_prob: bool = True
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10_000_000.0
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    norm_eps: float = 1e-6
    init_scale: float = 0.02
    gdn_chunk: int = 64
    attn_block: int = 256
    moe_block: int = 512
    #: elements of a mixer's widest float32 activation (the DeltaNet's
    #: ``[B, S, Hk (2 d_k + 2 r d_v)]``) that may be live at once;
    #: :func:`schedule` cuts a step to it
    live_elems: int = 1 << 26
    tie_embeddings: bool = False  # the head is dense, the table PS-served

    @property
    def d_model(self) -> int:
        return self.hidden_size

    def hybrid_body(self, seed: int, loss_chunk: int):
        """What ``learner/hybrid.py::HybridLMTrainer`` trains
        (``models/moe.py::hybrid_body``)."""
        return moe.hybrid_body(sys.modules[__name__], self, seed, loss_chunk)

    @classmethod
    def from_published(cls, pub: dict, **cut) -> "Qwen3NextConfig":
        """From ``config.json``'s keys; ``cut`` holds the cut (``n_layers``,
        ``layers_first``, ``experts_held``, ``experts_first``,
        ``vocab_size``) and anything assumed.  A key whose value this body
        has no code for is refused."""
        n = pub["num_hidden_layers"]
        if (
            pub.get("model_type", "qwen3_next") != "qwen3_next"
            or pub.get("attention_bias")
            or pub.get("tie_word_embeddings")
            or pub.get("use_sliding_window")
            or pub.get("mlp_only_layers")
            or pub.get("rope_scaling") is not None
            or pub.get("decoder_sparse_step", 1) != 1
            or pub.get("hidden_act", "silu") != "silu"
            or pub["num_attention_heads"] % pub["num_key_value_heads"]
            or pub["linear_num_value_heads"] % pub["linear_num_key_heads"]
        ):
            raise ValueError("a published key this body has no code for")
        kw = dict(
            vocab_size=pub["vocab_size"], n_layers=n, num_hidden_layers=n,
            full_attention_interval=pub["full_attention_interval"],
            hidden_size=pub["hidden_size"],
            moe_intermediate_size=pub["moe_intermediate_size"],
            shared_expert_intermediate_size=pub[
                "shared_expert_intermediate_size"],
            n_routed_experts=pub["num_experts"],
            experts_held=pub["num_experts"],
            num_experts_per_token=pub["num_experts_per_tok"],
            norm_topk_prob=pub["norm_topk_prob"],
            num_attention_heads=pub["num_attention_heads"],
            num_key_value_heads=pub["num_key_value_heads"],
            head_dim=pub["head_dim"],
            partial_rotary_factor=float(pub["partial_rotary_factor"]),
            rope_theta=float(pub["rope_theta"]),
            linear_num_key_heads=pub["linear_num_key_heads"],
            linear_num_value_heads=pub["linear_num_value_heads"],
            linear_key_head_dim=pub["linear_key_head_dim"],
            linear_value_head_dim=pub["linear_value_head_dim"],
            linear_conv_kernel_dim=pub["linear_conv_kernel_dim"],
            norm_eps=pub["rms_norm_eps"],
        )
        kw.update(cut)
        return cls(**kw)

    def layer_kinds(self):
        """``[(mixer, mlp), ...]`` of the layers held here: layer ``i`` is
        ``full`` when ``(i + 1) % full_attention_interval == 0``, else
        ``gdn``; every one an expert layer."""
        held = range(self.layers_first, self.layers_first + self.n_layers)
        if held.stop > self.num_hidden_layers:
            raise ValueError(f"layers {held} of {self.num_hidden_layers}")
        return [
            ("full" if (i + 1) % self.full_attention_interval == 0 else "gdn",
             "experts")
            for i in held
        ]


def tiny_config(**kw) -> Qwen3NextConfig:
    """Small config for tests and ``app.create``: the same code paths at toy
    sizes (one period G G G F, a held quarter of the experts, two value
    heads a key head, key and value heads of different sizes, grouped
    queries, a partial rotary)."""
    defaults = dict(
        vocab_size=256, n_layers=4, num_hidden_layers=4, hidden_size=64,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        n_routed_experts=8, experts_held=2, num_experts_per_token=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        partial_rotary_factor=0.5, rope_theta=10_000.0,
        linear_num_key_heads=2, linear_num_value_heads=4,
        linear_key_head_dim=16, linear_value_head_dim=8, gdn_chunk=8,
        attn_block=8, moe_block=8,
    )
    defaults.update(kw)
    return Qwen3NextConfig(**defaults)


def expert_layer(cfg: Qwen3NextConfig) -> moe.ExpertLayer:
    """What ``models/moe.py``'s expert layer is told by this body: a
    softmax router renormalised over its top k, scale 1, no selection bias;
    the shared expert and its gate are in the parameters."""
    return moe.ExpertLayer(
        root=BODY_SCOPE, n_routed=cfg.n_routed_experts,
        held=cfg.experts_held, first=cfg.experts_first,
        top_k=cfg.num_experts_per_token, scale=1.0,
        renormalize=cfg.norm_topk_prob, block=cfg.moe_block,
        scoring="softmax",
    )


def _qkvz_width(cfg: Qwen3NextConfig) -> int:
    """A key head's share of ``W_qkvz``: ``q``, ``k``, and its value heads'
    ``v`` and ``z``."""
    r = cfg.linear_num_value_heads // cfg.linear_num_key_heads
    return 2 * cfg.linear_key_head_dim + 2 * r * cfg.linear_value_head_dim


def schedule(cfg: Qwen3NextConfig, batch: int, seq: int) -> bool:
    """``by_sequence`` of a step of ``batch`` sequences of ``seq`` tokens:
    the mixers run one sequence at a time where the widest of their
    activations (``[batch, seq, Hk (2 d_k + 2 r d_v)]``, the DeltaNet's
    projection; attention's queries with their gates are ``H 2 K`` a
    position) would pass ``cfg.live_elems`` (``models/moe.py::by_sequence``).
    It changes no result.  At the published widths and 2 x 8,192 tokens:
    one sequence at a time (12,288 a position: 1.5 x 2^27 elements)."""
    widest = max(cfg.linear_num_key_heads * _qkvz_width(cfg),
                 2 * cfg.num_attention_heads * cfg.head_dim)
    return batch > 1 and batch * seq * widest > cfg.live_elems


# -- parameters ---------------------------------------------------------------
def param_shapes(cfg: Qwen3NextConfig) -> dict:
    """The parameter tree as ``{name: ... shape}``; kernels under a
    ``kernel`` leaf so that ``parallel/tp.py`` reads them by name."""
    D, H, Hkv, K = (cfg.hidden_size, cfg.num_attention_heads,
                    cfg.num_key_value_heads, cfg.head_dim)
    Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    mixers = {
        "gdn": {
            "qkvz": {"kernel": (D, Hk, _qkvz_width(cfg))},
            "ba": {"kernel": (D, Hk, 2 * (Hv // Hk))},
            # depthwise taps over a key head's q, k and value heads' v
            "conv": (cfg.linear_conv_kernel_dim, Hk, 2 * dk + (Hv // Hk) * dv),
            "A_log": (Hv,), "dt_bias": (Hv,),
            "norm": {"scale": (dv,)},
            "o": {"kernel": (Hv, dv, D)},
        },
        "full": {
            "q": {"kernel": (D, H, 2 * K)},  # a head's query, then its gate
            "k": {"kernel": (D, Hkv, K)}, "v": {"kernel": (D, Hkv, K)},
            "q_norm": {"offset": (K,)}, "k_norm": {"offset": (K,)},
            "o": {"kernel": (H, K, D)},
        },
    }
    swiglu = functools.partial(moe.swiglu_shapes, D)
    experts = {
        "router": {"kernel": (D, cfg.n_routed_experts)},
        "experts": {k: v["kernel"] for k, v in swiglu(
            cfg.moe_intermediate_size, (cfg.experts_held,)
        ).items()},
        "shared": swiglu(cfg.shared_expert_intermediate_size),
        "shared_gate": {"kernel": (D, 1)},
    }
    tree = {
        f"layer_{i}": {
            "mixer_norm": {"offset": (D,)},
            mixer: mixers[mixer],
            "mlp_norm": {"offset": (D,)},
            "moe": experts,
        }
        for i, (mixer, _mlp) in enumerate(cfg.layer_kinds())
    }
    tree["final_norm"] = {"offset": (D,)}
    tree["lm_head"] = {"kernel": (D, cfg.vocab_size)}
    return tree


def count_params(cfg: Qwen3NextConfig) -> dict:
    """``held`` and ``active`` parameters of this body
    (``models/moe.py::count_params``)."""
    return moe.count_params(param_shapes(cfg), expert_layer(cfg))


def init_params(cfg: Qwen3NextConfig, key) -> dict:
    """Seeded float32 parameters, initialised as published (the module's
    docstring)."""
    def special(leaf, k, shape):
        if leaf == "A_log":  # log U(0, 16]: decay rates exp(A_log) up to 16
            return jnp.log(16.0 * (1.0 - jax.random.uniform(k, shape)))
        if leaf == "dt_bias":
            return jnp.ones(shape, jnp.float32)
        return None

    return moe.init_tree(param_shapes(cfg), key, cfg.init_scale, special)


# -- layers ---------------------------------------------------------------------
def gdn_mixer(cfg: Qwen3NextConfig, p, x):
    """Gated DeltaNet over ``x [B, S, D]`` (normed)."""
    B, S, _ = x.shape
    Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    r = Hv // Hk
    with _scope("gdn.proj"):
        qkvz = jnp.einsum("bsd,dhn->bshn", x, p["qkvz"]["kernel"])
        ba = jnp.einsum("bsd,dhn->bshn", x, p["ba"]["kernel"])
        # per key head: q, k, then its r value heads' v and z; the
        # convolution takes q, k and v
        z = qkvz[..., 2 * dk + r * dv:]
        b, a = (t.reshape(B, S, Hv) for t in jnp.split(ba, 2, axis=-1))
    with _scope("gdn.conv"):
        # taps grouped by key head as qkvz is: the shards keep their heads
        mixed = jax.nn.silu(moe.causal_conv(qkvz[..., :2 * dk + r * dv], p["conv"]))
        q, k, v = jnp.split(mixed, [dk, 2 * dk], axis=-1)
        q = moe.l2_normalize(q) / np.sqrt(dk)
        k = moe.l2_normalize(k)
        beta = jax.nn.sigmoid(b)
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    with _scope("gdn.scan"):
        o, _state = chunk_gated_delta(
            q, k, v.reshape(B, S, Hv, dv), g, beta, chunk=cfg.gdn_chunk
        )
    with _scope("gdn.norm"):
        o = rms_norm(o, p["norm"]["scale"], cfg.norm_eps) * jax.nn.silu(
            z.reshape(B, S, Hv, dv)
        )
    with _scope("gdn.proj"):
        return jnp.einsum("bshv,hvd->bsd", o, p["o"]["kernel"])


def attn_mixer(cfg: Qwen3NextConfig, p, x):
    """Gated attention over ``x [B, S, D]`` (normed)."""
    B, S, _ = x.shape
    K = cfg.head_dim
    with _scope("attn.proj"):
        q, gate = jnp.split(
            jnp.einsum("bsd,dhk->bshk", x, p["q"]["kernel"]), 2, axis=-1
        )
        k, v = (jnp.einsum("bsd,dhk->bshk", x, p[n]["kernel"]) for n in "kv")
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        q, k = (
            tfm._rotary(
                moe.norm(p[f"{n}_norm"], t, cfg.norm_eps), positions,
                cfg.rope_theta, halves=True,
                rotary_dim=int(K * cfg.partial_rotary_factor),
            )
            for t, n in ((q, "q"), (k, "k"))
        )
    with _scope("attn.full"):
        o = blocked_causal_attention(
            q, k, v, block=cfg.attn_block, scale=1.0 / np.sqrt(K)
        )
    with _scope("attn.gate"):
        o = o * jax.nn.sigmoid(gate)
    with _scope("attn.proj"):
        return jnp.einsum("bshk,hkd->bsd", o, p["o"]["kernel"])


_MIXERS = {"gdn": gdn_mixer, "full": attn_mixer}


def _mixer_block(cfg, kind, p, x):
    h = moe.norm(p["mixer_norm"], x, cfg.norm_eps)
    return x + _MIXERS[kind](cfg, p[kind], h)


def trunk(cfg: Qwen3NextConfig, params, x):
    """``x [B, S, D]`` input embeddings -> ``(hidden [B, S, D], counters)``."""
    return moe.trunk(
        expert_layer(cfg), cfg.layer_kinds(),
        lambda mixer: functools.partial(_mixer_block, cfg, mixer),
        cfg.norm_eps, schedule(cfg, x.shape[0], x.shape[1]), params, x,
    )


def loss_fn(cfg: Qwen3NextConfig, params, emb_in, targets, loss_chunk: int = 0):
    """Next-token loss over the held vocabulary -> ``(loss, counters)``.
    ``loss_chunk > 0`` fuses the head into the chunked loss."""
    hidden, counters = trunk(cfg, params, emb_in)
    return moe.head_loss(
        BODY_SCOPE, hidden, params["lm_head"]["kernel"], targets, loss_chunk
    ), counters


def logits(cfg: Qwen3NextConfig, params, emb_in):
    hidden, _ = trunk(cfg, params, emb_in)
    return moe.head_logits(hidden, params["lm_head"]["kernel"])
