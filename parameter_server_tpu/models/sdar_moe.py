"""A mixture-of-experts body trained by block diffusion: every layer is
grouped-query attention with per-head ``q`` / ``k`` norms and an expert
layer of softmax-routed experts (``models/moe.py``); the body runs on a
masked copy of every sequence beside its clean one and attends over blocks
(``ops/blocked_attention.py::block_diffusion_attention``).

The published model this serves is SDAR-30B-A3B-Chat (``config.json``,
``model_type`` ``sdar_moe``: 48 layers, hidden 2048, 32 query heads over 4
key-value heads of 128, ``rope_theta`` 1e6 with no scaling, no sliding
window, ``decoder_sparse_step`` 1 and ``mlp_only_layers`` [] so that every
layer is an expert layer, 128 experts of width 768, top 8,
``norm_topk_prob`` true, no shared expert, an untied head, vocabulary
151,936, ``rms_norm_eps`` 1e-6); :meth:`SdarMoeConfig.from_published`
reads those keys by name.  Like its siblings it takes input embeddings, not
tokens: the embedding table lives in a ``KVServer`` (``learner/hybrid.py``).

Layer ``i``, pre-norm, ``x'`` the RMS-normed input of a sub-layer: ``h = x +
Attn(x')``, ``y = h + MoE(h')``; after the last layer one more RMSNorm, then
the head.

- **attention**: ``q = x' W_q [.., H, K]``, ``k = x' W_k``, ``v = x' W_v
  [.., Hkv, K]``, no biases; an RMSNorm over the ``K`` dimensions of every
  head of ``q`` and of ``k`` (``q_norm``, ``k_norm``: one scale of ``K``
  each), then rotary (the halves convention, ``theta``, all ``K``
  dimensions); softmax at ``1 / sqrt(K)``, key head ``g`` serving query
  heads ``g G .. g G + G - 1``, over the keys block diffusion lets a query
  see (below); ``Attn = concat_h(o_h) W_o``.
- **experts**: ``p = softmax(x' W_r)`` over all ``num_experts`` in float32;
  the top ``k``, renormalised (``w_i = p_i / sum_sel p_j``), scale 1;
  ``MoE = sum_sel w_i SwiGLU_i(x')`` of which this process computes its
  held experts' part (``models/moe.py``).
- **block diffusion** (the training objective of SDAR, "A Synergistic
  Diffusion-AutoRegression Paradigm for Scalable Sequence Generation",
  after "Block Diffusion", Arriola et al., ICLR 2025): a clean sequence
  ``x0`` of ``L`` tokens is cut into blocks of ``b``; in block ``j``,
  ``m_j`` uniform on ``1..b`` positions, chosen uniformly, are replaced by
  the mask token: ``xt`` (:func:`noise`).  The body runs on ``[xt ; x0]``,
  ``2L`` positions, both halves at rotary positions ``0..L-1``; a query of
  ``xt`` in block ``j`` sees the ``xt`` keys of block ``j`` in both
  directions and the ``x0`` keys of blocks ``< j``; a query of ``x0`` in
  block ``j`` sees the ``x0`` keys of blocks ``<= j``.  **Loss**: ``(1 / (B
  L)) sum_seq sum_j (b / m_j) sum_{i masked in j} -log p(x0_i | ...)``,
  read at the masked position itself (no shift) over the held vocabulary
  (:func:`loss_fn`, :func:`diffusion_targets`).  The trainer hands the body
  the tokens ``[B, 2L] = [xt ; x0]`` as its targets, and the masked
  positions and ``m_j`` follow from the mask id, so
  ``learner/hybrid.py`` needs no branch for this body.

**Assumed** (``config.json`` leaves these open; the benchmark's
configuration file repeats the list with its reasons):

1. the block length ``b`` is 4, that of SDAR's released chat models;
2. ``m_j`` uniform on ``1..b`` with the weight ``b / m_j``: the count form
   of the linear schedule's ``1 / t``, so the loss is a function of the
   tokens alone;
3. the loss on ``xt``'s masked positions only, read unshifted (BD3-LM's
   convention; shifted logits, as Dream reads them, are the other reading);
   the mask token's own output is not set aside;
4. both halves at rotary positions ``0..L-1``;
5. per-head ``q`` / ``k`` RMSNorm, from the ``qwen3_moe`` layer that
   ``sdar_moe`` derives from;
6. SwiGLU experts, the router's weight on an expert's output, no shared
   expert, a head of its own;
7. the mask token is the last row of the held vocabulary (``mask_id``).

**The cut**: this process holds the published layers ``[layers_first,
layers_first + n_layers)``, ``experts_held`` experts a layer from
``experts_first``, ``vocab_size`` rows of the head (the mask row the last).

**Precision**: parameters, residual stream, norms, router, rotary, softmax
and loss are float32; matrix products run at jax's default precision (on a
TPU one bfloat16 pass with float32 accumulation), the router's at the
highest, so that its top-k is float32's.

Every layer is the same; each mixer and each MLP is a ``jax.checkpoint`` of
its own (``models/moe.py::trunk``).  **How a step is cut to fit** follows
from its shapes (:func:`schedule`, one budget: ``live_elems``), and no
option chooses a path.  Device scopes (under the trainer's
``ps.model.sdar``, which holds the whole step, ``ps.model.optimizer``
included; each written as a path under it): ``ps.model.attn.proj`` (q, k,
v, the head norms, rotary, ``W_o``), ``ps.model.attn.bd`` (block-diffusion
attention, both halves), and ``models/moe.py``'s (``ps.model.moe.*``,
``ps.model.head_loss``).  A step counts ``bd_masked_positions`` beside the
expert layer's counters: the masked positions of its batch.
"""

from __future__ import annotations

import dataclasses
import functools
import sys

import jax.numpy as jnp
import numpy as np

from parameter_server_tpu.models import moe
from parameter_server_tpu.models import transformer as tfm
from parameter_server_tpu.models.moe import rms_norm
from parameter_server_tpu.ops.blocked_attention import block_diffusion_attention

#: the device scope the trainer puts round a step of this body
BODY_SCOPE = "ps.model.sdar"

#: device scope ``ps.model.<name>`` as a path under this body's
_scope = functools.partial(moe.scope, BODY_SCOPE)


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    vocab_size: int  # rows of the head held here, the mask row the last
    #: the layers held here: published layers [layers_first, + n_layers)
    n_layers: int
    layers_first: int = 0
    num_hidden_layers: int = 48  # published
    hidden_size: int = 2048
    moe_intermediate_size: int = 768
    n_routed_experts: int = 128
    #: the share held here: experts [experts_first, experts_first + held)
    experts_held: int = 128
    experts_first: int = 0
    num_experts_per_token: int = 8
    norm_topk_prob: bool = True
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-6
    block_length: int = 4  # assumed (1)
    init_scale: float = 0.02
    attn_block: int = 256
    moe_block: int = 1024
    #: the held share's static capacity, a multiple of its expected slots
    #: (``models/moe.py::capacity_blocks``; 0: none); it changes no result
    moe_capacity_factor: float = 0.0
    #: elements of a mixer's widest float32 activation (the queries, ``[B,
    #: 2L, heads x head_dim]``) that may be live at once; :func:`schedule`
    #: cuts a step to it
    live_elems: int = 1 << 26
    tie_embeddings: bool = False  # the head is dense, the table PS-served

    @property
    def d_model(self) -> int:
        return self.hidden_size

    @property
    def mask_id(self) -> int:
        """The mask token: the held vocabulary's last row (assumed (7))."""
        return self.vocab_size - 1

    def hybrid_body(self, seed: int, loss_chunk: int):
        """What ``learner/hybrid.py::HybridLMTrainer`` trains
        (``models/moe.py::hybrid_body``)."""
        return moe.hybrid_body(sys.modules[__name__], self, seed, loss_chunk)

    @classmethod
    def from_published(cls, pub: dict, **cut) -> "SdarMoeConfig":
        """From ``config.json``'s keys; ``cut`` holds the cut (``n_layers``,
        ``layers_first``, ``experts_held``, ``experts_first``,
        ``vocab_size``) and anything assumed.  A key whose value this body
        has no code for is refused."""
        n = pub["num_hidden_layers"]
        if (
            pub.get("model_type", "sdar_moe") != "sdar_moe"
            or pub.get("attention_bias")
            or pub.get("tie_word_embeddings")
            or pub.get("use_sliding_window")
            or pub.get("mlp_only_layers")
            or pub.get("rope_scaling") is not None
            or pub.get("decoder_sparse_step", 1) != 1
            or pub.get("hidden_act", "silu") != "silu"
            or pub["num_attention_heads"] % pub["num_key_value_heads"]
        ):
            raise ValueError("a published key this body has no code for")
        kw = dict(
            vocab_size=pub["vocab_size"], n_layers=n, num_hidden_layers=n,
            hidden_size=pub["hidden_size"],
            moe_intermediate_size=pub["moe_intermediate_size"],
            n_routed_experts=pub["num_experts"],
            experts_held=pub["num_experts"],
            num_experts_per_token=pub["num_experts_per_tok"],
            norm_topk_prob=pub["norm_topk_prob"],
            num_attention_heads=pub["num_attention_heads"],
            num_key_value_heads=pub["num_key_value_heads"],
            head_dim=pub["head_dim"], rope_theta=float(pub["rope_theta"]),
            norm_eps=pub["rms_norm_eps"],
        )
        kw.update(cut)
        return cls(**kw)

    def layer_kinds(self):
        """``[(mixer, mlp), ...]`` of the layers held here: all alike."""
        if self.layers_first + self.n_layers > self.num_hidden_layers:
            raise ValueError(
                f"layers {self.layers_first} + {self.n_layers} of "
                f"{self.num_hidden_layers}"
            )
        return [("bd", "experts")] * self.n_layers


def tiny_config(**kw) -> SdarMoeConfig:
    """Small config for tests and ``app.create``: the same code paths at toy
    sizes (a held quarter of the experts, grouped queries, blocks of 4)."""
    defaults = dict(
        vocab_size=256, n_layers=2, num_hidden_layers=4, hidden_size=64,
        moe_intermediate_size=32, n_routed_experts=8, experts_held=2,
        num_experts_per_token=2, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, rope_theta=10_000.0, attn_block=8, moe_block=8,
    )
    defaults.update(kw)
    return SdarMoeConfig(**defaults)


def expert_layer(cfg: SdarMoeConfig) -> moe.ExpertLayer:
    """What ``models/moe.py``'s expert layer is told by this body: a
    softmax router, scale 1, no selection bias, no shared expert, the
    configuration's static capacity."""
    return moe.ExpertLayer(
        root=BODY_SCOPE, n_routed=cfg.n_routed_experts,
        held=cfg.experts_held, first=cfg.experts_first,
        top_k=cfg.num_experts_per_token, scale=1.0,
        renormalize=cfg.norm_topk_prob, block=cfg.moe_block,
        scoring="softmax", capacity_factor=cfg.moe_capacity_factor,
    )


def schedule(cfg: SdarMoeConfig, batch: int, positions: int) -> bool:
    """``by_sequence`` of a step of ``batch`` sequences of ``positions``
    body positions (``2L``): the mixers run one sequence at a time where the
    queries ``[batch, positions, heads x head_dim]`` would pass
    ``cfg.live_elems`` (``models/moe.py::by_sequence``).  It changes no
    result.  At the published widths and 2 x 8,192 positions: both
    sequences at once (32 x 128 = 4,096 a position: 2^26 elements)."""
    widest = cfg.num_attention_heads * cfg.head_dim
    return batch > 1 and batch * positions * widest > cfg.live_elems


# -- parameters ---------------------------------------------------------------
def param_shapes(cfg: SdarMoeConfig) -> dict:
    """The parameter tree as ``{name: ... shape}``; kernels under a
    ``kernel`` leaf so that ``parallel/tp.py`` reads them by name."""
    D, H, Hkv, K = (cfg.hidden_size, cfg.num_attention_heads,
                    cfg.num_key_value_heads, cfg.head_dim)
    layer = {
        "mixer_norm": {"scale": (D,)},
        "attn": {
            "q": {"kernel": (D, H, K)}, "k": {"kernel": (D, Hkv, K)},
            "v": {"kernel": (D, Hkv, K)},
            "q_norm": {"scale": (K,)}, "k_norm": {"scale": (K,)},
            "o": {"kernel": (H, K, D)},
        },
        "mlp_norm": {"scale": (D,)},
        "moe": {
            "router": {"kernel": (D, cfg.n_routed_experts)},
            "experts": {k: v["kernel"] for k, v in moe.swiglu_shapes(
                D, cfg.moe_intermediate_size, (cfg.experts_held,)
            ).items()},
        },
    }
    tree = {f"layer_{i}": layer for i in range(len(cfg.layer_kinds()))}
    tree["final_norm"] = {"scale": (D,)}
    tree["lm_head"] = {"kernel": (D, cfg.vocab_size)}
    return tree


def count_params(cfg: SdarMoeConfig) -> dict:
    """``held`` and ``active`` parameters of this body
    (``models/moe.py::count_params``)."""
    return moe.count_params(param_shapes(cfg), expert_layer(cfg))


def init_params(cfg: SdarMoeConfig, key) -> dict:
    """Seeded float32 parameters (initial scales: assumed; the file of the
    benchmark's configuration lists them)."""
    return moe.init_tree(
        param_shapes(cfg), key, cfg.init_scale, lambda leaf, k, shape: None
    )


# -- the noise ------------------------------------------------------------------
def noise(tokens, rng, cfg: SdarMoeConfig) -> np.ndarray:
    """Clean ``tokens [B, L]`` (ids below ``mask_id``, ``L`` a whole number
    of blocks) -> ``[xt ; x0]`` ``[B, 2L]`` int32: in every block of ``b``,
    ``m`` uniform on ``1..b`` positions chosen uniformly by the NumPy
    generator ``rng`` are the mask token."""
    x0 = np.asarray(tokens)
    B, L = x0.shape
    b = cfg.block_length
    if L % b:
        raise ValueError(f"{L} tokens are not whole blocks of {b}")
    m = rng.integers(1, b + 1, size=(B, L // b, 1))
    # a uniform permutation of each block's positions; its first m masked
    ranks = rng.random((B, L // b, b)).argsort(-1).argsort(-1)
    xt = np.where((ranks < m).reshape(B, L), cfg.mask_id, x0)
    return np.concatenate([xt, x0], axis=1).astype(np.int32)


def diffusion_targets(cfg: SdarMoeConfig, targets):
    """``targets [B, 2L] = [xt ; x0]`` -> ``(x0 [B, L], weight [B, L],
    masked [B, L])``: ``weight`` is ``b / m_j / (B L)`` at a masked position
    of block ``j`` (``m_j`` its masked count) and 0 elsewhere."""
    B, L = targets.shape[0], targets.shape[1] // 2
    b = cfg.block_length
    xt, x0 = targets[:, :L], targets[:, L:]
    masked = xt == cfg.mask_id
    per_block = masked.reshape(B, L // b, b)
    m = jnp.sum(per_block, axis=-1, keepdims=True)
    w = jnp.where(per_block, b / jnp.maximum(m, 1), 0.0).reshape(B, L)
    return x0, w.astype(jnp.float32) / (B * L), masked


# -- layers ---------------------------------------------------------------------
def attn_mixer(cfg: SdarMoeConfig, p, x):
    """Attention over ``[xt ; x0]`` ``x [B, 2L, D]``: both halves at rotary
    positions ``0..L-1``, the keys block diffusion lets a query see."""
    B, S, _ = x.shape
    with _scope("attn.proj"):
        q, k, v = (
            jnp.einsum("bsd,dhk->bshk", x, p[n]["kernel"]) for n in "qkv"
        )
        positions = jnp.broadcast_to(jnp.tile(jnp.arange(S // 2), 2), (B, S))
        q, k = (
            tfm._rotary(
                rms_norm(a, p[f"{n}_norm"]["scale"], cfg.norm_eps),
                positions, cfg.rope_theta, halves=True,
            )
            for a, n in ((q, "q"), (k, "k"))
        )
    with _scope("attn.bd"):
        o = block_diffusion_attention(
            q, k, v, diffusion_block=cfg.block_length, block=cfg.attn_block,
            scale=1.0 / np.sqrt(cfg.head_dim),
        )
    with _scope("attn.proj"):
        return jnp.einsum("bshk,hkd->bsd", o, p["o"]["kernel"])


def _mixer_block(cfg, p, x):
    h = rms_norm(x, p["mixer_norm"]["scale"], cfg.norm_eps)
    return x + attn_mixer(cfg, p["attn"], h)


def trunk(cfg: SdarMoeConfig, params, x):
    """``x [B, 2L, D]`` input embeddings of ``[xt ; x0]`` -> ``(hidden [B,
    2L, D], counters)``."""
    return moe.trunk(
        expert_layer(cfg), cfg.layer_kinds(),
        lambda mixer: functools.partial(_mixer_block, cfg),
        cfg.norm_eps, schedule(cfg, x.shape[0], x.shape[1]), params, x,
    )


def loss_fn(cfg: SdarMoeConfig, params, emb_in, targets, loss_chunk: int = 0):
    """The block-diffusion loss -> ``(loss, counters)``: ``emb_in [B, 2L,
    D]`` the rows of ``targets [B, 2L] = [xt ; x0]``.  ``loss_chunk > 0``
    fuses the head into the chunked loss."""
    hidden, counters = trunk(cfg, params, emb_in)
    x0, weight, masked = diffusion_targets(cfg, targets)
    loss = moe.weighted_loss(
        BODY_SCOPE, hidden[:, : x0.shape[1]], params["lm_head"]["kernel"],
        x0, weight, loss_chunk,
    )
    return loss, dict(counters, bd_masked_positions=jnp.sum(masked, dtype=jnp.int32))


def logits(cfg: SdarMoeConfig, params, emb_in):
    """``emb_in [B, 2L, D]`` -> the noisy half's logits ``[B, L, vocab]``."""
    hidden, _ = trunk(cfg, params, emb_in)
    return moe.head_logits(
        hidden[:, : hidden.shape[1] // 2], params["lm_head"]["kernel"]
    )
