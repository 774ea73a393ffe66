"""Plain reference of the hybrid body of ``models/qwen3_next.py``
(Qwen3-Next-80B-A3B-Instruct's layers): forward, loss and gradients in
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``.
No chunking of the recurrence, no dispatch, no kernels, no cache: the gated
delta rule runs token by token (``lax.scan`` over time), attention is a
dense causal softmax, every held expert is a plain SwiGLU over every token
with a mask for the tokens that selected it.  It imports nothing of the
package and takes the parameter tree by its names.

``sizes`` is a plain dict (the published keys the equations need):
``layers`` (``[(mixer, mlp), ...]``), ``heads``, ``kv_heads``,
``head_dim``, ``rotary_dim``, ``theta``, ``k_heads``, ``v_heads``, ``d_k``,
``d_v``, ``routed``, ``top_k``, ``renormalize``, ``held``, ``first``,
``eps``.

The published description, layer ``i`` (``x'`` the normed input of a
sub-layer; every norm but the DeltaNet output's zero-centred, ``x^ (1 +
w)``): ``h = x + Mix_i(x')``, ``y = h + MoE(h')``; a final norm, the head.
Gated DeltaNet: ``[q | k | v | z]`` per key head from ``W_qkvz``, ``[b |
a]`` from ``W_ba``, a causal depthwise convolution of ``[q ; k ; v]`` and
SiLU, l2-normed ``q`` (over ``sqrt(d_k)``) and ``k``, ``beta =
sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``, ``S_t = e^(g_t)
S_{t-1} + beta_t k_t (v_t - e^(g_t) S_{t-1}^T k_t)^T``, ``o_t = S_t^T
q_t``, value head ``j`` reading key head ``j // (Hv / Hk)``, ``RMSNorm(o) w
silu(z)``, ``W_o``.  Gated attention: a head's query and gate from ``W_q``,
zero-centred norms of ``q`` and ``k``, rotary over the first
``rotary_dim`` dimensions (halves), causal softmax at ``1 / sqrt(K)``,
``o * sigmoid(gate)``, ``W_o``.  Experts: ``softmax(x' W_r)`` over all,
top ``k`` renormalised, plus ``sigmoid(x' W_sg) SwiGLU_shared(x')``.

Departures from the published description, each at its line below:
(1) the held share: experts outside ``[first, first + held)`` add nothing;
(2) the vocabulary is the slice the head holds; (3) the multi-token-
prediction module is left out; (4) the convolution's taps are stored
grouped by key head, ``[W, Hk, 2 d_k + r d_v]``, and put in the published
channel order here.

**Blocks, so that the published widths fit one chip** (they change no
result): ``scan_block`` checkpoints the token-by-token scan in blocks of
steps (its backward would else keep a state of ``v_heads x d_k x d_v``
floats a token: 17 GB at 8,192 tokens), ``q_block`` computes dense scores
for a block of queries against every key at a time, ``layer_remat``
checkpoints each layer, and a held expert's part is computed again in the
backward (else its ``[S, D]`` output and its two ``[S, F]`` activations are
kept for all 32 held experts: 8 GB at 8,192 tokens).  The caller hands one
sequence at a time.
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


def causal_conv(x, w):
    """``x [S, C]``, ``w [W, C]``: ``y_t = sum_i w_i x_{t - (W - 1) + i}``."""
    width, S = w.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x])
    return sum(xp[i:i + S] * w[i] for i in range(width))


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def gated_delta(q, k, v, g, beta, scan_block=0):
    """The delta rule under a scalar decay, token by token: ``q, k [S, Hk,
    K]``, ``v [S, Hv, V]``, ``g, beta [S, Hv]``."""
    S, Hk, K = q.shape
    Hv = v.shape[1]
    q, k = (jnp.repeat(a, Hv // Hk, axis=1) for a in (q, k))  # head j: j // r

    def step(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp
        state = jnp.exp(g_t)[:, None, None] * state  # e^(g_t) S_{t-1}
        kv = jnp.einsum("hk,hkv->hv", k_t, state)
        state = state + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (v_t - kv))
        return state, jnp.einsum("hk,hkv->hv", q_t, state)

    xs = (q, k, v, g, beta)
    state = jnp.zeros((Hv, K, v.shape[-1]), v.dtype)
    if not scan_block or S % scan_block:
        return jax.lax.scan(step, state, xs)[1]

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(step, state, inp)

    xs = tuple(a.reshape(S // scan_block, scan_block, *a.shape[1:]) for a in xs)
    return jax.lax.scan(block, state, xs)[1].reshape(S, Hv, -1)


def gdn(sz, p, x):
    Hk, Hv, K, V = sz["k_heads"], sz["v_heads"], sz["d_k"], sz["d_v"]
    r = Hv // Hk
    S = x.shape[0]
    qkvz = jnp.einsum("sd,dhn->shn", x, p["qkvz"]["kernel"])
    ba = jnp.einsum("sd,dhn->shn", x, p["ba"]["kernel"])
    # per key head: q, k, its r value heads' v, then their z
    q, k = qkvz[..., :K], qkvz[..., K:2 * K]
    v = qkvz[..., 2 * K:2 * K + r * V].reshape(S, Hv * V)
    z = qkvz[..., 2 * K + r * V:].reshape(S, Hv, V)
    b, a = ba[..., :r].reshape(S, Hv), ba[..., r:].reshape(S, Hv)
    # (4) the taps [W, Hk, 2 K + r V], grouped by key head, in the
    # published channel order [q of every head ; k ; v]
    w = p["conv"]
    taps = jnp.concatenate([w[..., :K].reshape(-1, Hk * K),
                            w[..., K:2 * K].reshape(-1, Hk * K),
                            w[..., 2 * K:].reshape(-1, Hv * V)], -1)
    mixed = jax.nn.silu(causal_conv(
        jnp.concatenate([q.reshape(S, Hk * K), k.reshape(S, Hk * K), v], -1), taps,
    ))
    q = l2norm(mixed[:, :Hk * K].reshape(S, Hk, K)) / np.sqrt(K)
    k = l2norm(mixed[:, Hk * K:2 * Hk * K].reshape(S, Hk, K))
    v = mixed[:, 2 * Hk * K:].reshape(S, Hv, V)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(a + p["dt_bias"])
    o = gated_delta(q, k, v, g, jax.nn.sigmoid(b), sz.get("scan_block", 0))
    o = rms_norm(o, p["norm"]["scale"], sz["eps"]) * jax.nn.silu(z)
    return o.reshape(S, Hv * V) @ p["o"]["kernel"].reshape(Hv * V, -1)


def rotary(x, theta, dim):
    """``x [S, H, D]``: the pairs ``(x[i], x[i + dim / 2])`` of the first
    ``dim`` dimensions turned by ``t theta^(-2i / dim)``, the rest as they
    are."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :dim // 2], x[..., dim // 2:dim], x[..., dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def full(sz, p, x):
    H, Hkv, K = sz["heads"], sz["kv_heads"], sz["head_dim"]
    S = x.shape[0]
    qg = jnp.einsum("sd,dhk->shk", x, p["q"]["kernel"])
    q, gate = qg[..., :K], qg[..., K:]
    k = jnp.einsum("sd,dhk->shk", x, p["k"]["kernel"])
    v = jnp.einsum("sd,dhk->shk", x, p["v"]["kernel"])
    q = rotary(rms_norm(q, 1.0 + p["q_norm"]["offset"], sz["eps"]),
               sz["theta"], sz["rotary_dim"])
    k = rotary(rms_norm(k, 1.0 + p["k_norm"]["offset"], sz["eps"]),
               sz["theta"], sz["rotary_dim"])
    k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, v))
    qb = sz.get("q_block", 0) or S

    def rows(start, q_rows):
        s = jnp.einsum("qhd,khd->hqk", q_rows, k) / np.sqrt(K)
        ids = start + jnp.arange(q_rows.shape[0])
        s = jnp.where(jnp.arange(S)[None, :] <= ids[:, None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    if qb >= S:
        o = rows(0, q)
    else:
        o = jax.lax.map(
            jax.checkpoint(lambda a: rows(a[0], a[1])),
            (jnp.arange(0, S, qb), q.reshape(S // qb, qb, H, K)),
        ).reshape(S, H, K)
    o = o * jax.nn.sigmoid(gate)
    return jnp.einsum("shk,hkd->sd", o, p["o"]["kernel"])


def experts(sz, p, x):
    """A softmax over all ``routed`` experts, the held ones each as a plain
    SwiGLU over every token, masked to the tokens that selected it, and the
    gated shared expert."""
    s = jax.nn.softmax(x @ p["router"]["kernel"], axis=-1)
    _top, idx = jax.lax.top_k(s, sz["top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if sz["renormalize"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    sh = p["shared"]
    y = jax.nn.sigmoid(x @ p["shared_gate"]["kernel"]) * swiglu(
        *(sh[n]["kernel"] for n in ("gate", "up", "down")), x
    )
    ex = p["experts"]
    # an expert's part, computed again in the backward: its [tokens, D]
    # output is then not kept for every held expert (a block; see above)
    part = jax.checkpoint(
        lambda w_e, gate, up, down: w_e[:, None] * swiglu(gate, up, down, x)
    )

    # departure (1): only the held experts; the others' part is left out
    def add_expert(y, e_and_weights):
        e, gate, up, down = e_and_weights
        w_e = jnp.sum(jnp.where(idx == sz["first"] + e, w, 0.0), axis=-1)
        return y + part(w_e, gate, up, down), None

    y, _ = jax.lax.scan(
        add_expert, y,
        (jnp.arange(sz["held"]), ex["gate"], ex["up"], ex["down"]),
    )
    return y


def layer(sz, kinds, p, x):
    mixer, _mlp = kinds
    h = rms_norm(x, 1.0 + p["mixer_norm"]["offset"], sz["eps"])
    x = x + (gdn if mixer == "gdn" else full)(sz, p[mixer], h)
    h = rms_norm(x, 1.0 + p["mlp_norm"]["offset"], sz["eps"])
    return x + experts(sz, p["moe"], h)


def hidden(sz, params, emb):
    """``emb [S, D]`` of one sequence -> the final norm's output.  A run of
    consecutive layers of one kind is a ``lax.scan`` over their stacked
    parameters: the same layers in the same order, one piece of code a
    run."""
    x = emb.astype(jnp.float32)
    kinds = sz["layers"]
    i = 0
    while i < len(kinds):
        j = i
        while j + 1 < len(kinds) and kinds[j + 1] == kinds[i]:
            j += 1
        f = functools.partial(layer, sz, kinds[i])
        if sz.get("layer_remat"):
            f = jax.checkpoint(f)
        if j == i:
            x = f(params[f"layer_{i}"], x)
        else:
            stacked = jax.tree.map(
                lambda *leaves: jnp.stack(leaves),
                *(params[f"layer_{n}"] for n in range(i, j + 1)),
            )
            x, _ = jax.lax.scan(lambda x, p: (f(p, x), None), x, stacked)
        i = j + 1
    return rms_norm(x, 1.0 + params["final_norm"]["offset"], sz["eps"])


def sequence_loss(sz, params, emb, tokens):
    """Mean next-token loss of one sequence over the held vocabulary
    (departure (2)): position ``t`` predicts ``tokens[t + 1]``; no second
    head predicts further (departure (3)).  ``vocab_block`` positions'
    logits are live at a time."""
    with jax.default_matmul_precision("highest"):
        h = hidden(sz, params, emb)[:-1]
        tg = tokens[1:]
        n = h.shape[0]
        vb = sz.get("vocab_block", 0) or n
        pad = (-n) % vb
        h = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, vb, h.shape[1])
        tg = jnp.pad(tg, (0, pad)).reshape(-1, vb)
        live = (jnp.arange(n + pad) < n).reshape(-1, vb)
        head = params["lm_head"]["kernel"]

        @jax.checkpoint
        def nll(block):
            hb, tb, mb = block
            logp = jax.nn.log_softmax(hb @ head)
            picked = jnp.take_along_axis(logp, tb[:, None], axis=-1)[:, 0]
            return -jnp.sum(jnp.where(mb, picked, 0.0))

        return jnp.sum(jax.lax.map(nll, (h, tg, live))) / n


def loss(sz, params, emb, tokens):
    """``emb [B, S, D]``, ``tokens [B, S]`` -> mean over the sequences."""
    return sum(
        sequence_loss(sz, params, emb[b], tokens[b]) for b in range(emb.shape[0])
    ) / emb.shape[0]


def sizes_of(cfg, **blocks) -> dict:
    """``sizes`` from an object with the package's config attributes."""
    return dict(
        layers=tuple(cfg.layer_kinds()), heads=cfg.num_attention_heads,
        kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        rotary_dim=int(cfg.head_dim * cfg.partial_rotary_factor),
        theta=cfg.rope_theta, k_heads=cfg.linear_num_key_heads,
        v_heads=cfg.linear_num_value_heads, d_k=cfg.linear_key_head_dim,
        d_v=cfg.linear_value_head_dim, routed=cfg.n_routed_experts,
        top_k=cfg.num_experts_per_token, renormalize=cfg.norm_topk_prob,
        held=cfg.experts_held, first=cfg.experts_first, eps=cfg.norm_eps,
        **blocks,
    )
