"""Transformer family: one configurable module covering BERT and Llama.

BASELINE configs #4 (BERT-base MLM) and #5 (Llama-3-8B hybrid).  The
reference predates transformers; the north star adds them, with the Llama
hybrid defined as "PS-sharded embeddings + XLA allreduce for transformer
blocks": here the embedding table is row-sharded over the ``model`` mesh axis
(exactly the KV table partition scheme) while attention/MLP weights use
tensor-parallel sharding rules (``parallel/tp.py``) whose collectives XLA
emits over ICI.

Implementation notes (TPU-first):
- all projections keep explicit head axes so GSPMD can shard heads;
- rotary embeddings computed in f32 regardless of activation dtype;
- GQA: n_kv_heads <= n_heads with head-group repetition;
- no data-dependent control flow; causal masking via static tril.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int
    n_layers: int
    n_heads: int
    d_model: int
    d_ff: int
    n_kv_heads: Optional[int] = None  # None -> == n_heads (MHA)
    max_seq: int = 2048
    causal: bool = True
    positional: str = "rotary"  # "rotary" | "learned"
    norm: str = "rms"  # "rms" | "ln"
    activation: str = "swiglu"  # "swiglu" | "gelu"
    tie_embeddings: bool = False
    dtype: Any = jnp.float32
    rope_theta: float = 500_000.0
    #: rematerialize each block on backward (jax.checkpoint): the bwd pass
    #: then saves only the O(B*S*d) block inputs instead of every attention
    #: score / d_ff intermediate — the HBM-for-FLOPs trade that makes the
    #: 8B config fit a v5e-16 (SURVEY §7 step 7).
    remat: bool = False
    #: run the block stack as ONE lax.scan over stacked per-layer params
    #: instead of a Python-unrolled loop.  Param tree changes shape: all
    #: blocks live under ``blocks/block/...`` with a leading layer axis.
    #: This is the at-scale layout: compile time is O(1) in depth, and
    #: XLA's buffer liveness (and therefore remat's memory win) is explicit
    #: — measured on the 8B feasibility path, unrolled remat saves ~nothing
    #: while scan+remat cuts temp memory several-fold.
    scan_blocks: bool = False
    #: attention implementation: "dense" (full scores matrix), "ring"
    #: (sequence-parallel exact attention via ppermute over the ``sp_axis``
    #: mesh axis — ONLY valid inside a shard_map that carries that axis;
    #: ``parallel/sp_lm.py`` is the trainer that sets this up), "ulysses"
    #: (same contract as "ring"), or "ring_spmd" (the ring wrapped in a
    #: PARTIAL shard_map — callable from ordinary GSPMD code on global
    #: views, composing with TP/FSDP shardings on the other mesh axes;
    #: requires ``spmd_mesh``; ``parallel/sp_fsdp.py`` is the trainer).
    #: The param tree is identical in every case, so dense-initialized
    #: checkpoints load into ring models and vice versa.
    attn_impl: str = "dense"
    sp_axis: str = "sp"
    #: concrete mesh for "ring_spmd" (the partial shard_map must name it)
    spmd_mesh: Any = None

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def hybrid_body(self, seed: int, loss_chunk: int):
        """What ``learner/hybrid.py::HybridLMTrainer`` trains: see
        :func:`hybrid_body`."""
        return hybrid_body(self, seed, loss_chunk)

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads


def bert_base(vocab_size: int = 30522, **kw) -> "TransformerConfig":
    """BERT-base: 12L, 12H, 768d, bidirectional, learned pos, LN, GELU."""
    return TransformerConfig(
        vocab_size=vocab_size, n_layers=12, n_heads=12, d_model=768,
        d_ff=3072, max_seq=512, causal=False, positional="learned",
        norm="ln", activation="gelu", tie_embeddings=True, **kw,
    )


def llama3_8b(vocab_size: int = 128_256, **kw) -> "TransformerConfig":
    """Llama-3-8B: 32L, 32H/8KV, 4096d, 14336ff, rotary, RMS, SwiGLU."""
    return TransformerConfig(
        vocab_size=vocab_size, n_layers=32, n_heads=32, n_kv_heads=8,
        d_model=4096, d_ff=14336, max_seq=8192, **kw,
    )


def tiny_config(causal: bool = True, **kw) -> TransformerConfig:
    """Small config for tests: same code paths, toy sizes."""
    defaults = dict(
        vocab_size=256, n_layers=2, n_heads=4, n_kv_heads=2, d_model=64,
        d_ff=128, max_seq=64, causal=causal,
    )
    if not causal:
        defaults.update(positional="learned", norm="ln", activation="gelu",
                        n_kv_heads=4, tie_embeddings=True)
    defaults.update(kw)
    return TransformerConfig(**defaults)


def _rotary(x: jax.Array, positions: jax.Array, theta: float,
            halves: bool = False, *, inv_freq=None, rotary_dim=None,
            amplitude: float = 1.0) -> jax.Array:
    """Apply rotary embedding over the last (head_dim) axis. x: [B,S,H,D].
    Frequency ``i`` turns the pair ``(x[2i], x[2i + 1])``, or with
    ``halves`` the pair ``(x[i], x[i + D/2])`` (the ``rotate_half``
    convention).

    What a body with more than one table gives (``models/laguna.py``; with
    none of them given this builds the program it built before they
    existed): ``rotary_dim``: only the first ``rotary_dim`` dimensions of
    the head are turned (``D`` above is then ``rotary_dim``) and the rest
    pass through untouched (a partial rotary factor); ``inv_freq
    [rotary_dim / 2]``: the frequencies, where they are not ``theta^(-2i /
    D)`` (a scaled table such as YaRN's; ``theta`` is then not read);
    ``amplitude``: a factor on ``cos`` and ``sin`` (YaRN's attention factor,
    by Hugging Face's convention)."""
    d = x.shape[-1] if rotary_dim is None else rotary_dim
    if inv_freq is None:
        freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    else:
        freq = jnp.asarray(inv_freq, jnp.float32)
    angles = positions[:, :, None].astype(jnp.float32) * freq  # [B,S,D/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if amplitude != 1.0:
        cos, sin = cos * amplitude, sin * amplitude
    rest = None
    if d < x.shape[-1]:
        x, rest = x[..., :d], x[..., d:]
    if halves:
        x1, x2 = x[..., : d // 2], x[..., d // 2:]
    else:
        x1, x2 = x[..., 0::2], x[..., 1::2]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    if halves:
        out = jnp.concatenate([out1, out2], axis=-1)
    else:
        out = jnp.stack([out1, out2], axis=-1).reshape(x.shape)
    out = out.astype(x.dtype)
    return out if rest is None else jnp.concatenate([out, rest], axis=-1)


class Norm(nn.Module):
    kind: str
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        if self.kind == "rms":
            scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
            var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
            return (x * jax.lax.rsqrt(var + 1e-6)).astype(self.dtype) * scale
        return nn.LayerNorm(dtype=self.dtype)(x)


class Attention(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, attn_mask=None):
        cfg = self.cfg
        B, S, _ = x.shape
        H, KV, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        dense = lambda heads, name: nn.DenseGeneral(  # noqa: E731
            (heads, D), axis=-1, use_bias=cfg.norm == "ln", name=name,
            dtype=cfg.dtype,
        )
        q = dense(H, "q")(x)  # [B,S,H,D]
        k = dense(KV, "k")(x)
        v = dense(KV, "v")(x)
        if cfg.positional == "rotary":
            q = _rotary(q, positions, cfg.rope_theta)
            k = _rotary(k, positions, cfg.rope_theta)
        if KV != H:
            rep = H // KV
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        if cfg.attn_impl in ("ring", "ulysses", "ring_spmd"):
            if attn_mask is not None:
                raise ValueError(
                    "sequence-parallel attention does not support attn_mask "
                    "(padding masks are a dense-impl feature)"
                )
            if cfg.attn_impl == "ring_spmd":
                from parameter_server_tpu.ops.ring_attention import (
                    ring_attention_spmd,
                )

                if cfg.spmd_mesh is None:
                    raise ValueError(
                        "attn_impl='ring_spmd' needs cfg.spmd_mesh (the "
                        "partial shard_map must name a concrete mesh)"
                    )
                out = ring_attention_spmd(
                    q, k, v, mesh=cfg.spmd_mesh, sp_axis=cfg.sp_axis,
                    causal=cfg.causal,
                ).astype(cfg.dtype)
            elif cfg.attn_impl == "ring":
                from parameter_server_tpu.ops.ring_attention import (
                    ring_attention,
                )

                out = ring_attention(
                    q, k, v, axis_name=cfg.sp_axis, causal=cfg.causal
                ).astype(cfg.dtype)
            else:
                from parameter_server_tpu.ops.ulysses import ulysses_attention

                out = ulysses_attention(
                    q, k, v, axis_name=cfg.sp_axis, causal=cfg.causal
                ).astype(cfg.dtype)
        else:
            scores = jnp.einsum(
                "bshd,bthd->bhst", q, k, preferred_element_type=jnp.float32
            ) / np.sqrt(D)
            if cfg.causal:
                causal = jnp.tril(jnp.ones((S, S), bool))
                scores = jnp.where(causal[None, None], scores, -1e30)
            if attn_mask is not None:  # [B, S] True = attend
                scores = jnp.where(attn_mask[:, None, None, :], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1).astype(cfg.dtype)
            out = jnp.einsum(
                "bhst,bthd->bshd", probs, v,
                preferred_element_type=jnp.float32,
            ).astype(cfg.dtype)
        return nn.DenseGeneral(
            cfg.d_model, axis=(-2, -1), use_bias=cfg.norm == "ln", name="o",
            dtype=cfg.dtype,
        )(out)


class MLPBlock(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        bias = cfg.norm == "ln"
        if cfg.activation == "swiglu":
            gate = nn.Dense(cfg.d_ff, use_bias=bias, name="gate", dtype=cfg.dtype)(x)
            up = nn.Dense(cfg.d_ff, use_bias=bias, name="up", dtype=cfg.dtype)(x)
            h = nn.silu(gate) * up
        else:
            h = nn.gelu(
                nn.Dense(cfg.d_ff, use_bias=bias, name="up", dtype=cfg.dtype)(x)
            )
        return nn.Dense(cfg.d_model, use_bias=bias, name="down", dtype=cfg.dtype)(h)


class Block(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, attn_mask=None):
        cfg = self.cfg
        h = Norm(cfg.norm, cfg.dtype, name="attn_norm")(x)
        x = x + Attention(cfg, name="attn")(h, positions, attn_mask)
        h = Norm(cfg.norm, cfg.dtype, name="mlp_norm")(x)
        return x + MLPBlock(cfg, name="mlp")(h)


class _ScanBlock(nn.Module):
    """Scan-body adapter: Block with the (carry, ys) return nn.scan wants."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, positions, attn_mask=None):
        return Block(self.cfg, name="block")(x, positions, attn_mask), ()


def _apply_body(mod: nn.Module, cfg: TransformerConfig, x, attn_mask,
                positions=None):
    """Shared block stack: pos-emb + layers + final norm (no head).

    Called from inside a module's ``@nn.compact`` ``__call__``; submodules
    and params attach to the CALLER's scope with identical names, so
    :class:`Transformer` and :class:`TransformerBody` stay one
    implementation with interchangeable param trees.

    ``positions``: GLOBAL token positions ``[B, S]`` — pass them when ``x``
    is a sequence SHARD (SP: rotary phases and learned pos-emb rows must
    use global offsets, not the local 0..S_local range).
    """
    B, S, _ = x.shape
    x = x.astype(cfg.dtype)
    if positions is None:
        if cfg.positional == "learned" and S > cfg.max_seq:
            # the old slice failed loudly here; the gather below would
            # silently clamp out-of-range rows instead — keep it loud
            raise ValueError(
                f"sequence {S} exceeds learned-positional max_seq "
                f"{cfg.max_seq}"
            )
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    if cfg.positional == "learned":
        pos_emb = mod.param(
            "pos_embedding",
            nn.initializers.normal(0.02),
            (cfg.max_seq, cfg.d_model),
        )
        x = x + jnp.take(pos_emb, positions, axis=0).astype(cfg.dtype)
    if cfg.scan_blocks:
        body_cls = nn.remat(_ScanBlock) if cfg.remat else _ScanBlock
        scanned = nn.scan(
            body_cls,
            variable_axes={"params": 0},
            split_rngs={"params": True},
            length=cfg.n_layers,
            in_axes=(nn.broadcast, nn.broadcast),
        )
        x, _ = scanned(cfg, name="blocks")(x, positions, attn_mask)
    else:
        block_cls = nn.remat(Block) if cfg.remat else Block
        for i in range(cfg.n_layers):
            x = block_cls(cfg, name=f"layer_{i}")(x, positions, attn_mask)
    return Norm(cfg.norm, cfg.dtype, name="final_norm")(x)


class Transformer(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens, attn_mask=None):
        """tokens [B, S] int32 -> logits [B, S, vocab]."""
        cfg = self.cfg
        emb = self.param(
            "embedding",
            nn.initializers.normal(0.02),
            (cfg.vocab_size, cfg.d_model),
        )
        x = _apply_body(self, cfg, emb[tokens], attn_mask)
        if cfg.tie_embeddings:
            logits = jnp.einsum(
                "bsd,vd->bsv", x, emb.astype(cfg.dtype),
                preferred_element_type=jnp.float32,
            )
        else:
            logits = nn.Dense(
                cfg.vocab_size, use_bias=False, name="lm_head",
                dtype=cfg.dtype,
            )(x)
        return logits.astype(jnp.float32)


class TransformerTrunk(nn.Module):
    """Block stack + final norm WITHOUT the lm_head: hidden states out.

    Param names match :class:`TransformerBody` minus ``lm_head`` (both call
    :func:`_apply_body` in their own scope), so a body param tree minus its
    ``lm_head`` entry applies directly — the seam the memory-bounded chunked
    loss needs (head matmul fused into the loss, logits never materialized).
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, attn_mask=None, positions=None):
        return _apply_body(self, self.cfg, x, attn_mask, positions)


class TransformerBody(nn.Module):
    """The dense half of the PS hybrid (BASELINE config #5): blocks + final
    norm + untied lm_head, taking PRE-COMPUTED input embeddings.

    The embedding table itself lives in a KVServer (async Push/Pull over the
    Van, row-partitioned by token id — the reference's key-range scheme),
    while this body trains synchronously under GSPMD: batch sharded over
    ``data``, params TP-sharded per ``parallel/tp.py``, XLA emitting the
    allreduce.  ``learner/hybrid.py`` glues the two halves.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x, attn_mask=None):
        """x [B, S, d_model] input embeddings -> logits [B, S, vocab]."""
        cfg = self.cfg
        x = _apply_body(self, cfg, x, attn_mask)
        logits = nn.Dense(
            cfg.vocab_size, use_bias=False, name="lm_head", dtype=cfg.dtype
        )(x)
        return logits.astype(jnp.float32)


# -- losses -----------------------------------------------------------------


def causal_lm_loss(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Next-token CE: predict tokens[:, 1:] from logits[:, :-1]."""
    logp = jax.nn.log_softmax(logits[:, :-1])
    tgt = tokens[:, 1:]
    nll = -jnp.take_along_axis(logp, tgt[..., None], axis=-1)[..., 0]
    return jnp.mean(nll)


def chunked_causal_lm_loss(
    hidden: jax.Array,
    head_kernel: jax.Array,
    tokens: jax.Array,
    chunk: int = 1024,
) -> jax.Array:
    """Next-token CE with the head matmul fused into the loss, by chunks.

    ``causal_lm_loss`` needs the full f32 ``[B, S, vocab]`` logits live (and
    AD saves more copies for backward) — at Llama-3-8B scale (vocab 128k)
    that one tensor dominates the step's memory.  Here the lm_head matmul
    runs per sequence-chunk inside a rematerialized scan body: only one
    ``[B, chunk, vocab]`` slab exists at a time and backward recomputes it,
    so peak memory is O(S/chunk smaller) for ~one extra head matmul of
    FLOPs.  Numerically identical to
    ``causal_lm_loss(hidden @ head_kernel, tokens)`` up to summation order.
    """
    B, S, _d = hidden.shape
    n = S - 1
    xs = hidden[:, :-1]
    tg = tokens[:, 1:]
    chunk = min(chunk, n)
    pad = (-n) % chunk
    if pad:
        xs = jnp.pad(xs, ((0, 0), (0, pad), (0, 0)))
        tg = jnp.pad(tg, ((0, 0), (0, pad)))
    valid = (jnp.arange(n + pad) < n)[None, :]
    n_chunks = (n + pad) // chunk
    xs = xs.reshape(B, n_chunks, chunk, -1).transpose(1, 0, 2, 3)
    tg = tg.reshape(B, n_chunks, chunk).transpose(1, 0, 2)
    mk = (
        jnp.broadcast_to(valid, (B, n + pad))
        .reshape(B, n_chunks, chunk)
        .transpose(1, 0, 2)
    )

    @jax.checkpoint
    def chunk_nll(xc, tc, mc):
        logits = jnp.einsum(
            "bcd,dv->bcv", xc, head_kernel,
            preferred_element_type=jnp.float32,
        )
        logp = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logp, tc[..., None], axis=-1)[..., 0]
        return jnp.sum(nll * mc)

    def body(acc, args):
        xc, tc, mc = args
        return acc + chunk_nll(xc, tc, mc), None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (xs, tg, mk))
    return total / (B * n)


def mlm_loss(logits: jax.Array, targets: jax.Array, mask: jax.Array) -> jax.Array:
    """Masked-LM CE over masked positions only (mask True = predict)."""
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(nll * mask) / denom


def hybrid_body(cfg: TransformerConfig, seed: int, loss_chunk: int):
    """The one-kind block stack as the hybrid trainer takes a body:
    ``(params, loss_fn(params, emb_in, targets) -> (loss, counters),
    logits_fn(params, emb_in), active parameter count, device scope of the
    step)``.  ``loss_chunk > 0`` fuses the head into the chunked loss."""
    body = TransformerBody(cfg)
    x0 = jnp.zeros((1, 8, cfg.d_model), jnp.float32)
    params = body.init(jax.random.PRNGKey(seed), x0)["params"]
    if loss_chunk > 0:
        trunk = TransformerTrunk(cfg)

        def loss_fn(params, emb_in, targets):
            hidden = trunk.apply(
                {"params": {k: v for k, v in params.items() if k != "lm_head"}},
                emb_in,
            )
            return chunked_causal_lm_loss(
                hidden, params["lm_head"]["kernel"], targets, loss_chunk
            ), {}

    else:

        def loss_fn(params, emb_in, targets):
            logits = body.apply({"params": params}, emb_in)
            return causal_lm_loss(logits, targets), {}

    n = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))
    return (params, loss_fn, lambda p, e: body.apply({"params": p}, e), n,
            "ps.model.transformer")
