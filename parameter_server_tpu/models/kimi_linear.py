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
- **Experts**: ``s = sigmoid(x' W_r)`` over all routed experts, ``sel =
  top_k(s + bias)``, ``w_i = scale s_i / sum_{j in sel} s_j``; ``y = sum_{i
  in sel, held} w_i E_i(x') + E_shared(x')``.  **The held share**: this
  process holds experts ``[experts_first, experts_first + experts_held)``;
  the router keeps every output; what the absent experts would add is left
  out, here and in the reference alike, and nothing stands in for them.
  Token slots that select a held expert are laid out by expert in rows whose
  groups are padded to whole blocks; a loop over the blocks that hold rows
  gathers each block's rows, runs them through its expert's three matrices
  (a grouped product), weights them and adds them back to their tokens
  (:func:`grouped_experts`), so the cost follows the load while the layout
  has room for the worst case (every slot of every token held here: index
  arrays only, 1 MB at the published widths).  No slot may be dropped:
  ``moe_dropped_slots`` counts the held slots the layout gave no row
  (:func:`dispatch_layout`), and a caller must find it 0.

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
``ps.model.mla.proj`` / ``.attn``, ``ps.model.moe.router`` / ``.dispatch`` /
``.experts`` / ``.combine`` / ``.shared``, ``ps.model.mlp``,
``ps.model.head_loss``.
"""

from __future__ import annotations

import dataclasses
import functools
import zlib
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from parameter_server_tpu.models import transformer as tfm
from parameter_server_tpu.ops.blocked_attention import blocked_causal_attention
from parameter_server_tpu.ops.delta_rule import chunk_kda

HIGHEST = jax.lax.Precision.HIGHEST
#: the device scope the trainer puts round a step of this body
BODY_SCOPE = "ps.model.kimi"


def _scope(name: str):
    """Device scope ``ps.model.<name>``, written as a path under the body's
    own (``ps.model.kimi/ps.model.<name>``).  An operation of a transposed
    checkpoint or of a ``custom_vjp``'s rule loses the name stack around it,
    and the profiler leaves a ``while``'s own name out of the trace: a
    reader that splits the busy time by outermost scope
    (``scoped_device_pct``) gives such a ``while`` its program's scope only
    if every scoped operation of the program starts with that one (my chip
    run, PR 28: 11 % scoped without this, the step's ``lax.map`` loops
    unscoped)."""
    return jax.named_scope(f"{BODY_SCOPE}/ps.model.{name}")


#: what a step returns beside the loss, summed (``max``: largest) over the
#: expert layers
COUNTERS = ("moe_held_slots", "moe_dropped_slots", "moe_max_expert_slots")


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
        """What ``learner/hybrid.py::HybridLMTrainer`` trains: see
        :func:`hybrid_body`."""
        return hybrid_body(self, seed, loss_chunk)

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


#: attention runs its query blocks in at most this many bands (a band's
#: blocks share one key prefix and one piece of code: ``ops/blocked_attention``)
ATTN_BANDS = 4


def schedule(cfg: KimiLinearConfig, batch: int, seq: int):
    """``(by_sequence, kda_head_groups, attn_band)`` of a step of ``batch``
    sequences of ``seq`` tokens: how it is cut so that one of a KDA mixer's
    float32 activations (``[batch, seq, heads, head size]``: it keeps a dozen
    of them) stays within ``cfg.live_elems``.  None changes a result.

    - ``by_sequence``: the mixers and the dense MLP run one sequence at a
      time (``lax.map`` over the batch; over a mesh whose ``data`` axis splits
      the batch this serialises its devices: give such a mesh the budget its
      per-device batch needs);
    - ``kda_head_groups``: KDA runs its heads in that many groups, one after
      the other (a head shares nothing with another but the input and the sum
      into ``W_o``): the smallest divisor of the heads that fits the budget;
    - ``attn_band``: blocks of queries a band, for ``ATTN_BANDS`` bands.

    At the published widths and 2 x 8,192 tokens: one sequence at a time, 4
    groups of 8 heads (2^23 elements, 32 MiB an activation), bands of 8."""
    H, K = cfg.linear_num_heads, cfg.linear_head_dim
    by_sequence = batch > 1 and batch * seq * H * K > cfg.live_elems
    rows = seq if by_sequence else batch * seq
    groups = next(
        (g for g in range(1, H + 1)
         if H % g == 0 and rows * (H // g) * K <= cfg.live_elems), H
    )
    blocks = -(-seq // cfg.attn_block)
    return by_sequence, groups, -(-blocks // ATTN_BANDS)


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

    def swiglu(width, lead=()):
        return {"gate": {"kernel": (*lead, D, width)},
                "up": {"kernel": (*lead, D, width)},
                "down": {"kernel": (*lead, width, D)}}

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
    moe = {
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
            **({"mlp": swiglu(Fd)} if mlp == "dense" else {"moe": moe}),
        }
    tree["final_norm"] = {"scale": (D,)}
    tree["lm_head"] = {"kernel": (D, cfg.vocab_size)}
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def count_params(cfg: KimiLinearConfig) -> dict:
    """``held``: parameters of this body; ``active``: those a token's
    forward multiplies with here, a routed expert counted by the chance that
    a slot picks it (top-k x held / routed experts a layer).  What the 6ND
    rule takes for a body with experts."""
    shapes = param_shapes(cfg)
    held = sum(
        int(np.prod(s)) for s in jax.tree.leaves(shapes, is_leaf=_is_shape)
    )
    per_expert = 3 * cfg.hidden_size * cfg.moe_intermediate_size
    n_moe = sum(mlp == "experts" for _, mlp in cfg.layer_kinds())
    routed = n_moe * cfg.experts_held * per_expert
    share = cfg.num_experts_per_token / cfg.n_routed_experts
    return {"held": held, "active": held - routed + int(routed * share)}


def init_params(cfg: KimiLinearConfig, key) -> dict:
    """Seeded float32 parameters (initial scales: assumed; the file of the
    benchmark's configuration lists them)."""
    def make(path, shape):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        leaf = name.rsplit("/", 1)[-1]
        if leaf == "scale":
            return jnp.ones(shape, jnp.float32)
        if leaf == "bias":
            return jnp.zeros(shape, jnp.float32)
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
        return cfg.init_scale * jax.random.normal(k, shape, jnp.float32)

    return jax.tree_util.tree_map_with_path(
        make, param_shapes(cfg), is_leaf=_is_shape
    )


# -- layers ---------------------------------------------------------------------
def rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _swiglu(p, x):
    h = jax.nn.silu(x @ p["gate"]["kernel"]) * (x @ p["up"]["kernel"])
    return h @ p["down"]["kernel"]


def _causal_conv(x, w):
    """Depthwise causal convolution over time: ``x [B, S, H, K]``, ``w [W, H,
    K]``; ``y_t = sum_i w_i x_{t - (W - 1) + i}``."""
    width, S = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0), (0, 0)))
    return sum(xp[:, i:i + S] * w[i] for i in range(width))


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


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
            jax.nn.silu(_causal_conv(a, p[f"conv_{n}"]))
            for a, n in ((q, "q"), (k, "k"), (v, "v"))
        )
        q, k = _l2(q) / np.sqrt(K), _l2(k)
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


def mla_mixer(cfg: KimiLinearConfig, band: int, p, x):
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
            band=band,
            scale=1.0 / np.sqrt(dn + dr),
            q_shared=q[..., dn:], k_shared=kva[..., C:],
        )
    with _scope("mla.proj"):
        return jnp.einsum("bshk,hkd->bsd", o, p["o"]["kernel"])


def _expert_rows(xz, ex, rows, e):
    """A block's rows through expert ``e``: ``(x, silu'(a) parts, h, out)``."""
    with _scope("moe.dispatch"):
        xb = xz[rows]
    with _scope("moe.experts"):
        a, b = xb @ ex["gate"][e], xb @ ex["up"][e]
        h = jax.nn.silu(a) * b
        return xb, a, b, h, h @ ex["down"][e]


@jax.custom_vjp
def grouped_experts(xz, ex, weight, rows, block_expert, n_live):
    """The held experts' weighted outputs added back to their tokens.

    ``xz [N + 1, D]``: the tokens and a zero row; ``rows [nb, bm]``: the
    token of every row of every block (``N``: none); ``weight [nb, bm]``;
    ``block_expert [nb]``; the first ``n_live`` blocks hold rows, the rest
    none.  A ``fori_loop`` over the live blocks only: the layout has room
    for every slot of every token, the cost is the load's.  Its backward is
    written out below (a loop with a traced trip count has no derivative of
    jax's own, and under ``lax.scan`` + ``lax.cond`` every block's residuals
    are stacked for all ``nb`` blocks, 20 GB at the published widths: found
    by compiling for the chip, PR 28): it recomputes a block's hidden
    activations and keeps nothing per block."""

    def block(i, y):
        _xb, _a, _b, _h, out = _expert_rows(xz, ex, rows[i], block_expert[i])
        with _scope("moe.combine"):
            return y.at[rows[i]].add(out * weight[i][:, None])

    return jax.lax.fori_loop(
        0, n_live, block, jnp.zeros(xz.shape, jnp.float32)
    )


def _grouped_fwd(xz, ex, weight, rows, block_expert, n_live):
    y = grouped_experts(xz, ex, weight, rows, block_expert, n_live)
    return y, (xz, ex, weight, rows, block_expert, n_live)


def _grouped_bwd(res, dy):
    xz, ex, weight, rows, block_expert, n_live = res

    def block(i, carry):
        dxz, d_ex, d_weight = carry
        e, r = block_expert[i], rows[i]
        xb, a, b, h, out = _expert_rows(xz, ex, r, e)
        with _scope("moe.combine"):
            dyb = dy[r]
            d_weight = d_weight.at[i].set(jnp.sum(out * dyb, axis=-1))
            d_out = dyb * weight[i][:, None]
        with _scope("moe.experts"):
            dh = d_out @ ex["down"][e].T
            sig = jax.nn.sigmoid(a)
            da = dh * b * sig * (1.0 + a * (1.0 - sig))  # silu'(a)
            db = dh * a * sig
            d_ex = {
                "gate": d_ex["gate"].at[e].add(xb.T @ da),
                "up": d_ex["up"].at[e].add(xb.T @ db),
                "down": d_ex["down"].at[e].add(h.T @ d_out),
            }
            dxb = da @ ex["gate"][e].T + db @ ex["up"][e].T
        with _scope("moe.dispatch"):
            return dxz.at[r].add(dxb), d_ex, d_weight

    dxz, d_ex, d_weight = jax.lax.fori_loop(0, n_live, block, (
        jnp.zeros(xz.shape, jnp.float32),
        jax.tree.map(jnp.zeros_like, ex),
        jnp.zeros(weight.shape, jnp.float32),
    ))
    # the zero row takes no gradient; rows, experts and the count are integers
    return dxz.at[-1].set(0.0), d_ex, d_weight, None, None, None


grouped_experts.defvjp(_grouped_fwd, _grouped_bwd)


def moe_capacity(cfg: KimiLinearConfig, tokens: int) -> int:
    """Rows of the dispatch layout, a whole number of blocks: room for the
    worst case, every slot of every token held here and every expert's last
    block all but empty."""
    bm = cfg.moe_block
    rows = (
        tokens * min(cfg.num_experts_per_token, cfg.experts_held)
        + cfg.experts_held * (bm - 1)
    )
    return -(-rows // bm) * bm


def dispatch_layout(group, held: int, block: int, rows: int):
    """Where every token slot goes.  ``group [slots]``: the held expert a slot
    selected (``held``: none of them); ``rows``: rows of the layout, a whole
    number of ``block``s.  The slots are laid out by expert, each expert's
    group padded to whole blocks.  Returns ``(slot [rows], filled [rows],
    block_expert [rows / block], live blocks, counters)``: the slot that fills
    a row, whether one does, a block's expert, how many leading blocks hold
    rows.  ``moe_dropped_slots`` counts the held slots that found no row:
    0 whenever ``rows`` is :func:`moe_capacity`'s, which a caller checks."""
    nb = rows // block
    order = jnp.argsort(group, stable=True)  # slots by expert, absent last
    # where each held expert's slots start among the sorted ones
    first_slot = jnp.searchsorted(group[order], jnp.arange(held + 1))
    counts = first_slot[1:] - first_slot[:-1]
    padded = -(-counts // block) * block  # every group a whole number of blocks
    ends = jnp.cumsum(padded)
    first_row = ends - padded
    # layout row -> its block's expert -> the sorted slot that fills it
    block_expert = jnp.minimum(
        jnp.searchsorted(ends, jnp.arange(nb) * block, side="right"), held - 1
    )
    row_expert = jnp.repeat(block_expert, block)
    offset = jnp.arange(rows) - first_row[row_expert]
    filled = (offset >= 0) & (offset < counts[row_expert])
    slot = order[jnp.where(filled, first_slot[row_expert] + offset, 0)]
    counters = {
        "moe_held_slots": jnp.sum(counts),
        "moe_dropped_slots": jnp.sum(counts) - jnp.sum(filled),
        "moe_max_expert_slots": jnp.max(counts),
    }
    # the blocks past the last group hold no row: they are not run
    live = jnp.sum((jnp.arange(nb) * block < ends[-1]).astype(jnp.int32))
    return slot, filled, block_expert, live, counters


def route(cfg: KimiLinearConfig, router_kernel, xt):
    """``xt [N, D]`` -> ``(idx [N, k] of all routed experts, w [N, k])``, in
    float32 at the highest matrix precision.  The selection bias is a buffer
    held at zero and not trained (assumed), so it is left out."""
    s = jax.nn.sigmoid(jnp.dot(xt, router_kernel, precision=HIGHEST))
    _top, idx = jax.lax.top_k(s, cfg.num_experts_per_token)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg.moe_renormalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg.routed_scaling_factor


def moe_layer(cfg: KimiLinearConfig, p, x):
    """``x [B, S, D]`` (normed) -> ``(y, counters)``: the held experts' part
    of the routed sum plus the shared expert."""
    B, S, D = x.shape
    N, k, Eh, bm = B * S, cfg.num_experts_per_token, cfg.experts_held, cfg.moe_block
    nb = moe_capacity(cfg, N) // bm
    xt = x.reshape(N, D)
    with _scope("moe.router"):
        idx, w = route(cfg, p["router"]["kernel"], xt)
    with _scope("moe.dispatch"):
        local = idx - cfg.experts_first
        group = jnp.where((local >= 0) & (local < Eh), local, Eh).reshape(-1)
        slot, filled, block_expert, live, counters = dispatch_layout(
            group, Eh, bm, nb * bm
        )
        # a row's token (N: none, the zero row) and its weight
        token = jnp.where(filled, slot // k, N).astype(jnp.int32)
        weight = jnp.where(filled, w.reshape(-1)[slot], 0.0)
        xz = jnp.concatenate([xt, jnp.zeros((1, D), xt.dtype)])
    y = grouped_experts(
        xz, p["experts"], weight.reshape(nb, bm), token.reshape(nb, bm),
        block_expert, live,
    )
    y = y[:N]
    with _scope("moe.shared"):
        y = y + _swiglu(p["shared"], xt)
    return y.reshape(B, S, D), counters


def _mixer_block(cfg, kind, cut, p, x):
    """``cut``: KDA's head groups, or attention's band (:func:`schedule`)."""
    h = rms_norm(x, p["mixer_norm"]["scale"], cfg.rms_norm_eps)
    return x + (kda_mixer_grouped if kind == "kda" else mla_mixer)(
        cfg, cut, p[kind], h
    )


def _mlp_block(cfg, kind, p, x):
    h = rms_norm(x, p["mlp_norm"]["scale"], cfg.rms_norm_eps)
    if kind == "dense":
        with _scope("mlp"):
            return x + _swiglu(p["mlp"], h), {}
    y, counters = moe_layer(cfg, p["moe"], h)
    return x + y, counters


def _by_sequence(f, p, x):
    """``f(p, x)`` one sequence at a time (``lax.map`` over the batch): the
    mixers and the dense MLP treat sequences independently, so this changes
    no result and divides their live activations by the batch."""
    return jax.lax.map(lambda row: f(p, row[None])[0], x)


def trunk(cfg: KimiLinearConfig, params, x):
    """``x [B, S, D]`` input embeddings -> ``(hidden [B, S, D], counters)``."""
    x = x.astype(jnp.float32)
    zero = jnp.zeros((), jnp.int32)
    held = dropped = most = zero
    by_sequence, groups, band = schedule(cfg, x.shape[0], x.shape[1])
    for i, (mixer, mlp) in enumerate(cfg.layer_kinds()):
        p = params[f"layer_{i}"]
        mix = jax.checkpoint(functools.partial(
            _mixer_block, cfg, mixer, groups if mixer == "kda" else band
        ))
        ffn = jax.checkpoint(functools.partial(_mlp_block, cfg, mlp))
        if by_sequence:
            mix = functools.partial(_by_sequence, mix)
            if mlp == "dense":
                dense = ffn
                ffn = lambda p, x: (  # noqa: E731
                    _by_sequence(lambda p, x: dense(p, x)[0], p, x), {}
                )
        x = mix(p, x)
        x, c = ffn(p, x)
        if c:
            held = held + c["moe_held_slots"]
            dropped = dropped + c["moe_dropped_slots"]
            most = jnp.maximum(most, c["moe_max_expert_slots"])
    hidden = rms_norm(x, params["final_norm"]["scale"], cfg.rms_norm_eps)
    return hidden, dict(zip(COUNTERS, (held, dropped, most)))


def loss_fn(cfg: KimiLinearConfig, params, emb_in, targets, loss_chunk: int = 0):
    """Next-token loss over the held vocabulary -> ``(loss, counters)``.
    ``loss_chunk > 0`` fuses the head into the chunked loss."""
    hidden, counters = trunk(cfg, params, emb_in)
    with _scope("head_loss"):
        head = params["lm_head"]["kernel"]
        if loss_chunk > 0:
            loss = tfm.chunked_causal_lm_loss(hidden, head, targets, loss_chunk)
        else:
            loss = tfm.causal_lm_loss(
                jnp.einsum("bsd,dv->bsv", hidden, head,
                           preferred_element_type=jnp.float32),
                targets,
            )
    return loss, counters


def logits(cfg: KimiLinearConfig, params, emb_in):
    hidden, _ = trunk(cfg, params, emb_in)
    return jnp.einsum(
        "bsd,dv->bsv", hidden, params["lm_head"]["kernel"],
        preferred_element_type=jnp.float32,
    )


def hybrid_body(cfg: KimiLinearConfig, seed: int, loss_chunk: int):
    """This body as the hybrid trainer takes one (the five of
    ``models/transformer.py::hybrid_body``); a routed expert counts among the
    active parameters by the chance that a slot picks it."""
    params = jax.jit(lambda key: init_params(cfg, key))(jax.random.PRNGKey(seed))

    def body_loss(params, emb_in, targets):
        return loss_fn(cfg, params, emb_in, targets, loss_chunk)

    return (params, body_loss, lambda p, e: logits(cfg, p, e),
            count_params(cfg)["active"], BODY_SCOPE)
