"""Plain reference of the hybrid body of ``models/lfm2_moe.py``
(LFM2-8B-A1B's layers): forward, loss and gradients in ``jax.numpy`` float32
under ``jax.default_matmul_precision("highest")``.  No blocks of queries, no
dispatch, no kernels, no cache: the convolution is three shifted products,
attention is a dense ``[S, S]`` causal softmax with ``k`` and ``v`` repeated
for every query head of their group, rotary is written out, every held
expert is a plain SwiGLU over every token with a mask for the tokens that
selected it.  It imports nothing of the package and takes the parameter tree
by its names.

``sizes`` is a plain dict (the published keys the equations need):
``layers`` (``[(mixer, mlp), ...]``), ``heads``, ``kv_heads``, ``head_dim``,
``theta``, ``routed``, ``top_k``, ``scale``, ``renormalize``, ``held``,
``first``, ``eps``.

Departures from the published code, each at its line below: (1) the held
share: experts outside ``[first, first + held)`` add nothing; (2) the
vocabulary is the slice the head holds, and the head is a matrix of its own
(the input table is the parameter server's); (3) ``W_in`` is held as its
three parts ``[3, D, D]``, the same product; (4) the selection bias is read
from the parameters where they hold one and takes part in the selection
only, as published; how training moves it is not published, and nothing
here moves it.

**Blocks, so that the published widths fit one chip** (they change no
result): ``q_block`` computes dense scores for a block of queries against
every key at a time (8.6 GB a sequence otherwise), ``vocab_block`` positions'
logits are live at a time, ``layer_remat`` checkpoints each layer.  The
caller hands one sequence at a time.
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


def conv(sz, p, x):
    """The gated short convolution: ``x [S, D]``."""
    w_in = p["in_proj"]["kernel"]  # departure (3): [B, C, u] = x W_in
    b, c, u = x @ w_in[0], x @ w_in[1], x @ w_in[2]
    z = b * u
    taps = p["taps"]
    L, S = taps.shape[0], x.shape[0]
    # c_t = sum_j taps_j z_{t - (L - 1) + j}: L shifted products
    zp = jnp.concatenate([jnp.zeros((L - 1, z.shape[1]), z.dtype), z])
    y = sum(zp[j:j + S] * taps[j] for j in range(L))
    return (c * y) @ p["out_proj"]["kernel"]


def rotary(x, theta):
    """``x [S, H, K]``: position ``t`` turns the pair ``(x[i], x[i + K/2])``
    by ``t / theta^(2i / K)`` (the halves convention)."""
    S, _H, K = x.shape
    freq = 1.0 / (theta ** (jnp.arange(0, K, 2, dtype=jnp.float32) / K))
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * freq  # [S, K/2]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., : K // 2], x[..., K // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def gqa(sz, p, x):
    H, Hkv, K = sz["heads"], sz["kv_heads"], sz["head_dim"]
    S = x.shape[0]
    q = jnp.einsum("sd,dhk->shk", x, p["q"]["kernel"])
    k = jnp.einsum("sd,dhk->shk", x, p["k"]["kernel"])
    v = jnp.einsum("sd,dhk->shk", x, p["v"]["kernel"])
    q = rotary(rms_norm(q, p["q_norm"]["scale"], sz["eps"]), sz["theta"])
    k = rotary(rms_norm(k, p["k_norm"]["scale"], sz["eps"]), sz["theta"])
    # key-value head g serves query heads g G .. g G + G - 1
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
    return jnp.einsum("shk,hkd->sd", o, p["o"]["kernel"])


def experts(sz, p, x):
    """Router over all ``routed`` experts, the held ones each as a plain
    SwiGLU over every token, masked to the tokens that selected it."""
    s = jax.nn.sigmoid(x @ p["router"]["kernel"])
    # departure (4): the bias moves the selection, the weights are s's own
    biased = s + p["expert_bias"] if "expert_bias" in p else s
    _top, idx = jax.lax.top_k(biased, sz["top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if sz["renormalize"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * sz["scale"]
    ex = p["experts"]

    # departure (1): only the held experts; the others' part is left out
    def add_expert(y, e_and_weights):
        e, gate, up, down = e_and_weights
        w_e = jnp.sum(jnp.where(idx == sz["first"] + e, w, 0.0), axis=-1)
        return y + w_e[:, None] * swiglu(gate, up, down, x), None

    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(x),
        (jnp.arange(sz["held"]), ex["gate"], ex["up"], ex["down"]),
    )
    return y


def layer(sz, kinds, p, x):
    mixer, mlp = kinds
    h = rms_norm(x, p["mixer_norm"]["scale"], sz["eps"])
    x = x + (conv if mixer == "conv" else gqa)(sz, p[mixer], h)
    h = rms_norm(x, p["mlp_norm"]["scale"], sz["eps"])
    if mlp == "dense":
        m = p["mlp"]
        return x + swiglu(*(m[n]["kernel"] for n in ("gate", "up", "down")), h)
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
    return rms_norm(x, params["final_norm"]["scale"], sz["eps"])


def sequence_loss(sz, params, emb, tokens):
    """Mean next-token loss of one sequence over the held vocabulary
    (departure (2)): position ``t`` predicts ``tokens[t + 1]``.
    ``vocab_block`` positions' logits are live at a time."""
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
        layers=cfg.layer_kinds(), heads=cfg.num_attention_heads,
        kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        theta=cfg.rope_theta, routed=cfg.n_routed_experts,
        top_k=cfg.num_experts_per_token, scale=cfg.routed_scaling_factor,
        renormalize=cfg.norm_topk_prob, held=cfg.experts_held,
        first=cfg.experts_first, eps=cfg.norm_eps, **blocks,
    )
