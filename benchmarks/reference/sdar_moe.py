"""Plain reference of the block-diffusion body of ``models/sdar_moe.py``
(SDAR-30B-A3B's layers): forward, loss and gradients in ``jax.numpy``
float32 under ``jax.default_matmul_precision("highest")``.  No blocks of
keys, no bands, no dispatch, no kernels: attention is a dense softmax per
head over the ``2L`` positions of ``[xt ; x0]`` under an explicit ``[2L,
2L]`` boolean mask built from the rules of block diffusion (:func:`mask`),
with ``k`` and ``v`` repeated for every query head of their group; every
held expert is a plain SwiGLU over every position with a mask for the
positions that selected it; the loss is the weighted masked cross-entropy,
read at the masked position itself.  It imports nothing of the package and
takes the parameter tree by its names.

``sizes`` is a plain dict: ``layers`` (how many), ``kv_heads``,
``head_dim``, ``theta``, ``routed``, ``top_k``, ``renormalize``, ``held``,
``first``, ``eps``, ``block`` (the diffusion block ``b``), ``mask_id``.

Departures from the paper and the published code, each at its line below:
(1) the held share: experts outside ``[first, first + held)`` add nothing;
(2) the vocabulary is the slice the head holds, the mask token its last row
(``mask_id``), and the head is a matrix of its own (the input table is the
parameter server's); (3) the noise is given, not drawn: the masked
positions are where ``xt`` holds the mask id, and ``m_j`` is their count in
block ``j``, so the loss is the paper's estimate for that draw with the
linear schedule's weight ``1 / t`` written ``b / m_j``.  What
``config.json`` leaves open is taken as ``models/sdar_moe.py``'s docstring
lists it (blocks of 4; the loss on ``xt``'s masked positions only,
unshifted; both halves at rotary positions ``0..L-1``; per-head ``q`` /
``k`` RMSNorm; SwiGLU experts under a softmax router, renormalised over its
top k).

**Blocks, so that the published widths fit one chip** (they change no
result): ``q_block`` computes dense scores for a block of queries against
all ``2L`` keys at a time (2.1 GB a sequence and layer otherwise),
``vocab_block`` positions' logits are live at a time, ``layer_remat``
checkpoints each layer and, within the expert layer, each held expert's
part.  The caller hands one sequence at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def swiglu(gate, up, down, x):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def mask(L, b):
    """``[2L, 2L]`` bool, query by key, over ``[xt ; x0]``: a query of
    ``xt`` in block ``j`` sees the ``xt`` keys of block ``j`` and the ``x0``
    keys of blocks ``< j``; a query of ``x0`` in block ``j`` sees the ``x0``
    keys of blocks ``<= j``."""
    i = jnp.arange(2 * L)
    noisy, blk = i < L, (i % L) // b
    qn, kn = noisy[:, None], noisy[None, :]
    qb, kb = blk[:, None], blk[None, :]
    return ((qn & kn & (kb == qb)) | (qn & ~kn & (kb < qb))
            | (~qn & ~kn & (kb <= qb)))


def rotary(x, theta):
    """``x [2L, H, K]``: both halves at positions ``0..L-1``; position ``t``
    turns the pair ``(x[i], x[i + K/2])`` by ``t theta^(-2i / K)``."""
    S, _H, K = x.shape
    freq = (theta ** (-2.0 * np.arange(K // 2, dtype=np.float64) / K)).astype(
        np.float32
    )
    pos = jnp.arange(S, dtype=jnp.float32) % (S // 2)
    angle = pos[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., : K // 2], x[..., K // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(sz, p, x):
    """``x [2L, D]`` of ``[xt ; x0]`` -> ``Attn(x)``."""
    Hkv, K, eps = sz["kv_heads"], sz["head_dim"], sz["eps"]
    S = x.shape[0]
    q = jnp.einsum("sd,dhk->shk", x, p["q"]["kernel"])
    k = jnp.einsum("sd,dhk->shk", x, p["k"]["kernel"])
    v = jnp.einsum("sd,dhk->shk", x, p["v"]["kernel"])
    H = q.shape[1]
    # an RMSNorm over each head's K dimensions, then rotary
    q = rotary(rms_norm(q, p["q_norm"]["scale"], eps), sz["theta"])
    k = rotary(rms_norm(k, p["k_norm"]["scale"], eps), sz["theta"])
    # key-value head g serves query heads g G .. g G + G - 1
    k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, v))
    seen = mask(S // 2, sz["block"])
    qb = sz.get("q_block", 0) or S

    def rows(start, q_rows):
        s = jnp.einsum("qhd,khd->hqk", q_rows, k) / np.sqrt(K)
        m = jax.lax.dynamic_slice_in_dim(seen, start, q_rows.shape[0], axis=0)
        s = jnp.where(m[None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    if qb >= S:
        o = rows(0, q)
    else:
        o = jax.lax.map(
            jax.checkpoint(lambda a: rows(a[0], a[1])),
            (jnp.arange(0, S, qb), q.reshape(S // qb, qb, H, K)),
        ).reshape(S, H, K)
    return jnp.einsum("shk,hkd->sd", o, p["o"]["kernel"])


def experts(sz, p, x):
    """Softmax router over all ``routed`` experts, the top ``k``
    renormalised; the held experts each as a plain SwiGLU over every
    position, masked to the positions that selected it."""
    s = jax.nn.softmax(x @ p["router"]["kernel"], axis=-1)
    _top, idx = jax.lax.top_k(s, sz["top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if sz["renormalize"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    ex = p["experts"]

    # departure (1): only the held experts; the others' part is left out
    def add_expert(y, e_and_weights):
        e, gate, up, down = e_and_weights
        w_e = jnp.sum(jnp.where(idx == sz["first"] + e, w, 0.0), axis=-1)
        return y + w_e[:, None] * swiglu(gate, up, down, x), None

    if sz.get("layer_remat"):  # an expert's activations are not kept
        add_expert = jax.checkpoint(add_expert)
    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (jnp.arange(sz["held"]), ex["gate"], ex["up"], ex["down"]),
    )
    return y


def layer(sz, p, x):
    h = rms_norm(x, p["mixer_norm"]["scale"], sz["eps"])
    x = x + attention(sz, p["attn"], h)
    h = rms_norm(x, p["mlp_norm"]["scale"], sz["eps"])
    return x + experts(sz, p["moe"], h)


def hidden(sz, params, emb):
    """``emb [2L, D]`` of one sequence -> the final norm's output."""
    x = emb.astype(jnp.float32)
    f = functools.partial(layer, sz)
    if sz.get("layer_remat"):
        f = jax.checkpoint(f)
    for i in range(sz["layers"]):
        x = f(params[f"layer_{i}"], x)
    return rms_norm(x, params["final_norm"]["scale"], sz["eps"])


def sequence_loss(sz, params, emb, tokens):
    """One sequence's loss, ``tokens [2L] = [xt ; x0]``: ``(1 / L) sum_j (b
    / m_j) sum_{i masked in j} -log p(x0_i)`` over the held vocabulary
    (departures (2), (3)), read at the masked position of ``xt`` itself.
    ``vocab_block`` positions' logits are live at a time."""
    with jax.default_matmul_precision("highest"):
        L, b = tokens.shape[0] // 2, sz["block"]
        h = hidden(sz, params, emb)[:L]
        xt, x0 = tokens[:L], tokens[L:]
        masked = (xt == sz["mask_id"]).reshape(L // b, b)
        m = jnp.sum(masked, axis=-1, keepdims=True)
        w = jnp.where(masked, b / jnp.maximum(m, 1), 0.0).reshape(L)
        vb = sz.get("vocab_block", 0) or L
        pad = (-L) % vb
        h = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, vb, h.shape[1])
        tg = jnp.pad(x0, (0, pad)).reshape(-1, vb)
        w = jnp.pad(w, (0, pad)).reshape(-1, vb)
        head = params["lm_head"]["kernel"]

        @jax.checkpoint
        def nll(block):
            hb, tb, wb = block
            logp = jax.nn.log_softmax(hb @ head)
            picked = jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]
            return -jnp.sum(wb * picked)

        return jnp.sum(jax.lax.map(nll, (h, tg, w))) / L


def loss(sz, params, emb, tokens):
    """``emb [B, 2L, D]``, ``tokens [B, 2L]`` -> mean over the sequences."""
    return sum(
        sequence_loss(sz, params, emb[b], tokens[b]) for b in range(emb.shape[0])
    ) / emb.shape[0]


def sizes_of(cfg, **blocks) -> dict:
    """``sizes`` from an object with the package's config attributes."""
    return dict(
        layers=cfg.n_layers, kv_heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, theta=cfg.rope_theta,
        routed=cfg.n_routed_experts, top_k=cfg.num_experts_per_token,
        renormalize=cfg.norm_topk_prob, held=cfg.experts_held,
        first=cfg.experts_first, eps=cfg.norm_eps, block=cfg.block_length,
        mask_id=cfg.vocab_size - 1, **blocks,
    )
