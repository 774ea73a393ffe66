"""A hybrid body of window and full attention layers, each kind with its own
head count, rotary table and key range, every attention output gated per
head: per layer a mixer (``full``: causal softmax attention over every key
up to the query's own, or ``window``: over the last ``sliding_window`` keys,
the query's own included) and an MLP (``dense`` SwiGLU, or ``experts``: the
held-share expert layer of ``models/moe.py`` with a shared expert).

The published model this serves is Laguna-XS.2 (``config.json``,
``model_type`` ``laguna``: 40 layers, hidden 2048, 8 key-value heads of 128,
``layer_types`` one ``full_attention`` then three ``sliding_attention``,
``num_attention_heads_per_layer`` 48 on a full and 64 on a window layer,
``sliding_window`` 512, ``rope_parameters`` by layer kind, one leading dense
layer of width 8192, then 256 experts of width 512, top 8, and a shared
expert of 512); :meth:`LagunaConfig.from_published` reads those keys by
name.  Like its siblings it takes input embeddings, not tokens: the
embedding table lives in a ``KVServer`` (``learner/hybrid.py``).

Layer ``i``, pre-norm, ``x'`` the RMS-normed input of a sub-layer: ``h = x +
Attn_i(x')``, ``y = h + FF_i(h')``; after the last layer one more RMSNorm,
then the head.

- **attention**, ``H_i = num_attention_heads_per_layer[i]`` query heads over
  ``Hkv`` key-value heads of size ``K``, no biases: ``q = x' W_q [.., H_i,
  K]``, ``k = x' W_k``, ``v = x' W_v [.., Hkv, K]``; rotary on ``q`` and
  ``k`` by the layer kind's table (below); causal softmax at ``1 /
  sqrt(K)``, key head ``g`` serving query heads ``g G .. g G + G - 1``
  (``ops/blocked_attention.py``: ``k`` and ``v`` are never copied out per
  query head); on a ``window`` layer query ``t`` sees the keys ``t -
  (sliding_window - 1) .. t`` and the keys outside are not computed; **gate**
  ``a = sigmoid(x' W_g)``, ``W_g [D, H_i]``, ``o_h <- a_h o_h`` for every
  head ``h``; ``Attn = concat_h(o_h) W_o``.
- **rotary** (:class:`Rotary`, one a layer kind, the halves convention of
  ``models/transformer.py::_rotary``): over the first ``share x K``
  dimensions of the head (``partial_rotary_factor``; the rest pass
  through); frequencies ``theta^(-2j / dim)``, or under ``rope_type``
  ``yarn`` over ``dim`` and base ``theta``: ``ext_j = base^(-2j / dim)``,
  ``int_j = ext_j / factor``, ``c(r) = dim ln(original_max / (2 pi r)) / (2
  ln base)``, ``low = max(floor(c(beta_fast)), 0)``, ``high =
  min(ceil(c(beta_slow)), dim - 1)``, ``ramp_j = clip((j - low) / (high -
  low), 0, 1)``, ``inv_freq_j = int_j ramp_j + ext_j (1 - ramp_j)``; ``cos``
  and ``sin`` of ``t inv_freq_j`` times ``attention_factor``.
- **experts**: ``models/moe.py::moe_layer``: ``s = sigmoid(x' W_r)`` over
  all routed experts, top k, ``w_i = scale s_i / sum_sel s_j``, the held
  experts' part of ``sum w_i E_i(x')`` plus the shared SwiGLU, unweighted
  (the router's weight is on an expert's output:
  ``moe_apply_router_weight_on_input`` false).

**Assumed** (``config.json`` leaves these open; each by its family's
convention; the benchmark's configuration file repeats the list):

1. the gate is one value a head (``gating: true`` here; the sibling
   Laguna-S-2.1 of the same ``model_type`` spells it ``"gating":
   "per-head"``, ``gating_types`` ``per_head`` in every layer), a sigmoid of
   a product of the layer's normed input, applied to the head's attention
   output before ``W_o`` (the head-wise gate of "Gated Attention for Large
   Language Models", arXiv:2505.06708).  The same paper's other variant, one
   value an element (``W_g [D, H_i x K]``), would add 0.62 B parameters to
   the whole model: 34.07 B against the 33.44 B that a value a head gives
   by the shapes (:func:`count_params`, with one copy of the embedding),
   and the catalog's ``described_as`` says 33.4 B: ``config`` does not say,
   the sibling's spelling and the count decide alike;
2. the router scores by a sigmoid and the selected scores are renormalised
   (the sibling's ``norm_topk_prob: true``; a scaling factor of 2.5 is a
   sigmoid router's), and nothing but the scores takes part in the
   selection (no selection bias is named);
3. no per-head norm on ``q`` or ``k`` (the config names none);
4. SwiGLU in the dense MLP, the experts and the shared expert;
5. YaRN's ``attention_factor`` multiplies ``cos`` and ``sin`` (Hugging Face's
   rotary convention), and its correction range is truncated to whole
   dimensions (``floor`` / ``ceil``, that code's default);
6. a head matrix of its own (``tie_word_embeddings`` false).

**The cut**: this process holds the published layers ``[layers_first,
layers_first + n_layers)`` (0-based), ``experts_held`` experts a layer from
``experts_first``, ``vocab_size`` rows of the head.

**Precision**: parameters, residual stream, norms, router, gates, rotary,
softmax and loss are float32; matrix products run at jax's default precision
(on a TPU one bfloat16 pass with float32 accumulation), the router's at the
highest, so that its top-k is float32's.

Heterogeneous layers are unrolled; each mixer and each MLP is a
``jax.checkpoint`` of its own (``models/moe.py::trunk``).  **How a step is
cut to fit** follows from its shapes (:func:`schedule`, one budget:
``live_elems``), not from options a caller sets, and **no option chooses a
path**: a layer's kind does.  Device scopes (under the trainer's
``ps.model.laguna``, which holds the whole step, ``ps.model.optimizer``
included; each is written as a path under it): ``ps.model.attn.proj`` (q, k,
v, the gate's product, rotary, ``W_o``), ``ps.model.attn.full`` /
``ps.model.attn.window`` (the two kinds of attention apart),
``ps.model.attn.gate`` (the sigmoid and the product with the heads'
outputs), and ``models/moe.py``'s (``ps.model.moe.*``, ``ps.model.mlp``,
``ps.model.head_loss``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
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
BODY_SCOPE = "ps.model.laguna"
#: ``layer_types`` -> this body's mixers
MIXERS = {"full_attention": "full", "sliding_attention": "window"}
#: ``mlp_layer_types`` -> ``models/moe.py``'s MLP kinds
MLPS = {"dense": "dense", "sparse": "experts"}
#: full attention runs its query blocks in at most this many bands
ATTN_BANDS = 4

#: device scope ``ps.model.<name>`` as a path under this body's
_scope = functools.partial(moe.scope, BODY_SCOPE)


@dataclasses.dataclass(frozen=True)
class Rotary:
    """One layer kind's ``rope_parameters``: the default table at ``theta``
    over ``share`` of the head, or (``factor`` above 1) YaRN's."""

    theta: float
    share: float = 1.0  # partial_rotary_factor
    factor: float = 1.0  # YaRN's; 1: the default table
    original_max: int = 0
    beta_fast: float = 0.0  # YaRN's two; read only under a factor above 1
    beta_slow: float = 0.0
    amplitude: float = 1.0  # attention_factor, on cos and sin

    @classmethod
    def from_published(cls, rp: dict) -> "Rotary":
        kind = rp.get("rope_type", "default")
        if kind not in ("default", "yarn"):
            raise ValueError(f"a published key this body has no code for: "
                             f"rope_type {kind!r}")
        kw = dict(theta=float(rp["rope_theta"]),
                  share=float(rp.get("partial_rotary_factor", 1.0)))
        if kind == "yarn":
            # every key read by name: a config that leaves one out is refused
            missing = {"factor", "original_max_position_embeddings", "beta_fast",
                       "beta_slow", "attention_factor"} - set(rp)
            if missing:
                raise ValueError(f"a yarn table without {sorted(missing)}")
            kw.update(
                factor=float(rp["factor"]),
                original_max=rp["original_max_position_embeddings"],
                beta_fast=float(rp["beta_fast"]),
                beta_slow=float(rp["beta_slow"]),
                amplitude=float(rp["attention_factor"]),
            )
        return cls(**kw)

    def dim(self, head_dim: int) -> int:
        """The dimensions of a head that are turned."""
        return int(head_dim * self.share)

    def inv_freq(self, head_dim: int) -> np.ndarray:
        """``[dim / 2]`` float32 frequencies (the formulas of the module's
        docstring, in float64 and rounded once)."""
        dim = self.dim(head_dim)
        j = np.arange(dim // 2, dtype=np.float64)
        ext = self.theta ** (-2.0 * j / dim)
        if self.factor == 1.0:
            return ext.astype(np.float32)

        def c(r):
            return dim * math.log(self.original_max / (2 * math.pi * r)) / (
                2 * math.log(self.theta)
            )

        low = max(math.floor(c(self.beta_fast)), 0)
        high = min(math.ceil(c(self.beta_slow)), dim - 1)
        ramp = np.clip((j - low) / ((high - low) or 1e-3), 0.0, 1.0)
        return (ext / self.factor * ramp + ext * (1.0 - ramp)).astype(np.float32)


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    vocab_size: int  # rows of the head held here
    #: every published layer's mixer, ``full_attention`` or
    #: ``sliding_attention``, its MLP, ``dense`` or ``sparse``, and its heads
    layer_types: Tuple[str, ...]
    mlp_layer_types: Tuple[str, ...]
    heads_per_layer: Tuple[int, ...]
    #: ``((layer type, Rotary), ...)``: a rotary table a layer kind
    rotary: Tuple[Tuple[str, Rotary], ...]
    #: the layers held here: published layers [layers_first, + n_layers)
    n_layers: int
    layers_first: int = 0
    hidden_size: int = 2048
    intermediate_size: int = 8192
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    n_routed_experts: int = 256
    #: the share held here: experts [experts_first, experts_first + held)
    experts_held: int = 256
    experts_first: int = 0
    num_experts_per_token: int = 8
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True  # assumed (2)
    num_key_value_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    norm_eps: float = 1e-6
    init_scale: float = 0.02
    attn_block: int = 256
    moe_block: int = 512
    #: elements of a mixer's widest float32 activation (a window layer's
    #: ``[B, S, heads x head_dim]``) that may be live at once;
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
    def from_published(cls, pub: dict, **cut) -> "LagunaConfig":
        """From ``config.json``'s keys; ``cut`` holds the cut (``n_layers``,
        ``layers_first``, ``experts_held``, ``experts_first``,
        ``vocab_size``) and anything assumed."""
        types, mlps = tuple(pub["layer_types"]), tuple(pub["mlp_layer_types"])
        heads = tuple(pub["num_attention_heads_per_layer"])
        n = pub["num_hidden_layers"]
        if (
            pub.get("model_type", "laguna") != "laguna"
            or pub.get("attention_bias")
            or pub.get("tie_word_embeddings")
            or pub.get("moe_apply_router_weight_on_input")
            or pub.get("gating") not in (True, "per-head")
            or set(types) - set(MIXERS) or set(mlps) - set(MLPS)
            or {len(types), len(mlps), len(heads)} != {n}
            or any(h % pub["num_key_value_heads"] for h in heads)
            or set(types) - set(pub["rope_parameters"])
        ):
            raise ValueError("a published key this body has no code for")
        kw = dict(
            vocab_size=pub["vocab_size"], layer_types=types,
            mlp_layer_types=mlps, heads_per_layer=heads,
            rotary=tuple(
                (t, Rotary.from_published(pub["rope_parameters"][t]))
                for t in sorted(set(types))
            ),
            n_layers=n, hidden_size=pub["hidden_size"],
            intermediate_size=pub["intermediate_size"],
            moe_intermediate_size=pub["moe_intermediate_size"],
            shared_expert_intermediate_size=pub[
                "shared_expert_intermediate_size"],
            n_routed_experts=pub["num_experts"],
            experts_held=pub["num_experts"],
            num_experts_per_token=pub["num_experts_per_tok"],
            routed_scaling_factor=float(pub["moe_routed_scaling_factor"]),
            norm_topk_prob=pub.get("norm_topk_prob", True),
            num_key_value_heads=pub["num_key_value_heads"],
            head_dim=pub["head_dim"], sliding_window=pub["sliding_window"],
            norm_eps=pub["rms_norm_eps"],
        )
        kw.update(cut)
        return cls(**kw)

    def layer_kinds(self):
        """``[(mixer, mlp), ...]`` of the layers held here."""
        return [
            (MIXERS[self.layer_types[i]], MLPS[self.mlp_layer_types[i]])
            for i in self._held()
        ]

    def layer_heads(self):
        """Query heads of each layer held here: a layer's own count."""
        return [self.heads_per_layer[i] for i in self._held()]

    def rotary_of(self, mixer: str) -> Rotary:
        """The rotary table of the layers whose mixer is ``mixer``."""
        tables = dict(self.rotary)
        return next(tables[t] for t, m in MIXERS.items() if m == mixer)

    def _held(self) -> range:
        held = range(self.layers_first, self.layers_first + self.n_layers)
        if held.stop > len(self.layer_types):
            raise ValueError(f"layers {held} of {len(self.layer_types)}")
        return held


def tiny_config(**kw) -> LagunaConfig:
    """Small config for tests and ``app.create``: same code paths (a full
    layer with the dense MLP, then two window layers and a full one with
    experts; the kinds' own head counts and rotary tables, YaRN and a
    partial rotary factor on the full layers), toy sizes."""
    defaults = dict(
        vocab_size=256,
        layer_types=("full_attention", "sliding_attention",
                     "sliding_attention", "full_attention"),
        mlp_layer_types=("dense", "sparse", "sparse", "sparse"),
        heads_per_layer=(4, 6, 6, 4),
        rotary=(
            ("full_attention", Rotary(
                theta=500_000.0, share=0.5, factor=8.0, original_max=16,
                beta_fast=4.0, beta_slow=1.0, amplitude=1.2079441541679836,
            )),
            ("sliding_attention", Rotary(theta=10_000.0)),
        ),
        n_layers=4, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        n_routed_experts=8, experts_held=2, num_experts_per_token=2,
        num_key_value_heads=2, head_dim=16, sliding_window=12,
        attn_block=8, moe_block=8,
    )
    defaults.update(kw)
    return LagunaConfig(**defaults)


def expert_layer(cfg: LagunaConfig) -> moe.ExpertLayer:
    """What ``models/moe.py``'s expert layer is told by this body; the
    shared expert is in the parameters, and there is no selection bias."""
    return moe.ExpertLayer(
        root=BODY_SCOPE, n_routed=cfg.n_routed_experts,
        held=cfg.experts_held, first=cfg.experts_first,
        top_k=cfg.num_experts_per_token, scale=cfg.routed_scaling_factor,
        renormalize=cfg.norm_topk_prob, block=cfg.moe_block,
    )


def schedule(cfg: LagunaConfig, batch: int, seq: int):
    """``(by_sequence, attn_band)`` of a step of ``batch`` sequences of
    ``seq`` tokens: how it is cut so that a mixer's widest float32
    activation (the queries of the layer with the most heads, ``[batch, seq,
    heads x head_dim]``; the dense MLP's ``[batch, seq, intermediate]`` is
    of its order) stays within ``cfg.live_elems``.  Neither changes a
    result.

    - ``by_sequence``: the mixers and the dense MLP run one sequence at a
      time (``models/moe.py::by_sequence``);
    - ``attn_band``: blocks of queries a band of a full layer, for
      ``ATTN_BANDS`` bands (a window layer has no bands: one loop).

    At the published widths and 2 x 8,192 tokens: one sequence at a time
    (a window layer's 64 x 128 = 8,192 a token: 2^26 elements, 256 MiB an
    activation), bands of 8."""
    widest = max(cfg.layer_heads()) * cfg.head_dim
    by_sequence = batch > 1 and batch * seq * widest > cfg.live_elems
    blocks = -(-seq // cfg.attn_block)
    return by_sequence, -(-blocks // ATTN_BANDS)


# -- parameters ---------------------------------------------------------------
def param_shapes(cfg: LagunaConfig) -> dict:
    """The parameter tree as ``{name: ... shape}``; kernels under a
    ``kernel`` leaf so that ``parallel/tp.py`` reads them by name."""
    D, Hkv, K = cfg.hidden_size, cfg.num_key_value_heads, cfg.head_dim
    swiglu = functools.partial(moe.swiglu_shapes, D)
    experts = {
        "router": {"kernel": (D, cfg.n_routed_experts)},
        "experts": {k: v["kernel"] for k, v in
                    swiglu(cfg.moe_intermediate_size,
                           (cfg.experts_held,)).items()},
        "shared": swiglu(cfg.shared_expert_intermediate_size),
    }
    tree = {}
    for i, ((_mixer, mlp), H) in enumerate(
        zip(cfg.layer_kinds(), cfg.layer_heads())
    ):
        tree[f"layer_{i}"] = {
            "mixer_norm": {"scale": (D,)},
            "attn": {
                "q": {"kernel": (D, H, K)}, "k": {"kernel": (D, Hkv, K)},
                "v": {"kernel": (D, Hkv, K)},
                "o_gate": {"kernel": (D, H)},  # assumed (1): a value a head
                "o": {"kernel": (H, K, D)},
            },
            "mlp_norm": {"scale": (D,)},
            **({"mlp": swiglu(cfg.intermediate_size)} if mlp == "dense"
               else {"moe": experts}),
        }
    tree["final_norm"] = {"scale": (D,)}
    tree["lm_head"] = {"kernel": (D, cfg.vocab_size)}
    return tree


def count_params(cfg: LagunaConfig) -> dict:
    """``held`` and ``active`` parameters of this body
    (``models/moe.py::count_params``), every layer at its own head count."""
    return moe.count_params(param_shapes(cfg), expert_layer(cfg))


def init_params(cfg: LagunaConfig, key) -> dict:
    """Seeded float32 parameters (initial scales: assumed; the file of the
    benchmark's configuration lists them)."""
    return moe.init_tree(
        param_shapes(cfg), key, cfg.init_scale, lambda leaf, k, shape: None
    )


# -- layers ---------------------------------------------------------------------
def _gated(o, gate_in):
    """The heads' outputs ``o [B, S, H, K]`` under their gates: a sigmoid a
    head (assumed (1))."""
    with _scope("attn.gate"):
        return o * jax.nn.sigmoid(gate_in)[..., None]


def attn_mixer(cfg: LagunaConfig, kind: str, band: int, p, x):
    """A layer's attention, ``kind`` ``full`` or ``window``: its head count
    is its ``q`` kernel's, its rotary table and key range its kind's."""
    B, S, _ = x.shape
    rot = cfg.rotary_of(kind)
    with _scope("attn.proj"):
        q, k, v = (
            jnp.einsum("bsd,dhk->bshk", x, p[n]["kernel"]) for n in "qkv"
        )
        gate_in = x @ p["o_gate"]["kernel"]  # [B, S, H]
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        q, k = (
            tfm._rotary(
                a, positions, rot.theta, halves=True,
                inv_freq=rot.inv_freq(cfg.head_dim),
                rotary_dim=rot.dim(cfg.head_dim), amplitude=rot.amplitude,
            )
            for a in (q, k)
        )
    with _scope(f"attn.{kind}"):
        # no shared key part: empty slices, as the kernel's docstring asks
        o = blocked_causal_attention(
            q, k, v, block=cfg.attn_block, band=band,
            scale=1.0 / np.sqrt(cfg.head_dim),
            q_shared=q[..., :0], k_shared=k[:, :, 0, :0],
            window=cfg.sliding_window if kind == "window" else None,
        )
    o = _gated(o, gate_in)
    with _scope("attn.proj"):
        return jnp.einsum("bshk,hkd->bsd", o, p["o"]["kernel"])


def _mixer_block(cfg, kind, band, p, x):
    h = rms_norm(x, p["mixer_norm"]["scale"], cfg.norm_eps)
    return x + attn_mixer(cfg, kind, band, p["attn"], h)


def trunk(cfg: LagunaConfig, params, x):
    """``x [B, S, D]`` input embeddings -> ``(hidden [B, S, D], counters)``."""
    by_sequence, band = schedule(cfg, x.shape[0], x.shape[1])
    return moe.trunk(
        expert_layer(cfg), cfg.layer_kinds(),
        lambda mixer: functools.partial(_mixer_block, cfg, mixer, band),
        cfg.norm_eps, by_sequence, params, x,
    )


def loss_fn(cfg: LagunaConfig, params, emb_in, targets, loss_chunk: int = 0):
    """Next-token loss over the held vocabulary -> ``(loss, counters)``.
    ``loss_chunk > 0`` fuses the head into the chunked loss."""
    hidden, counters = trunk(cfg, params, emb_in)
    return moe.head_loss(
        BODY_SCOPE, hidden, params["lm_head"]["kernel"], targets, loss_chunk
    ), counters


def logits(cfg: LagunaConfig, params, emb_in):
    hidden, _ = trunk(cfg, params, emb_in)
    return moe.head_logits(hidden, params["lm_head"]["kernel"])
