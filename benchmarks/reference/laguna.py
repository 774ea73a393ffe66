"""Plain reference of the hybrid body of ``models/laguna.py`` (Laguna-XS.2's
layers): forward, loss and gradients in ``jax.numpy`` float32 under
``jax.default_matmul_precision("highest")``.  No blocks of keys, no
dispatch, no kernels, no cache: attention is a dense ``[S, S]`` masked
softmax per head (causal, and ``t - s < window`` on a window layer) with
``k`` and ``v`` repeated for every query head of their group, both rotary
tables are written out from their formulas, the gate is a sigmoid a head,
every held expert is a plain SwiGLU over every token with a mask for the
tokens that selected it.  It imports nothing of the package and takes the
parameter tree by its names.

``sizes`` is a plain dict (the published keys the equations need):
``layers`` (``[(mixer, mlp), ...]``, mixer ``full`` or ``window``),
``kv_heads``, ``head_dim``, ``window``, ``rotary`` (``{mixer: {theta, share,
factor, original_max, beta_fast, beta_slow, amplitude}}``), ``routed``,
``top_k``, ``scale``, ``renormalize``, ``held``, ``first``, ``eps``.  A
layer's query heads are its ``q`` kernel's.

Departures from the published code, each at its line below: (1) the held
share: experts outside ``[first, first + held)`` add nothing; (2) the
vocabulary is the slice the head holds, and the head is a matrix of its own
(the input table is the parameter server's).  What ``config.json`` leaves
open is taken as ``models/laguna.py``'s docstring lists it (a gate of one
value a head, a sigmoid router renormalised over its top k, no per-head
norm, SwiGLU, YaRN's attention factor on ``cos`` and ``sin``).

**Blocks, so that the published widths fit one chip** (they change no
result): ``q_block`` computes dense scores for a block of queries against
every key at a time (13 GB and more a sequence otherwise), ``vocab_block`` positions'
logits are live at a time, ``layer_remat`` checkpoints each layer and, within
an expert layer, each held expert's part (32 experts' activations over every
token are 2 GB a layer otherwise).  The caller hands one sequence at a time.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def swiglu(gate, up, down, x):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def inv_freq(rot, head_dim):
    """``[dim / 2]`` frequencies of one layer kind's table, ``dim = share x
    head_dim``: ``base^(-2j / dim)``, or YaRN's blend of them with the same
    over ``factor``."""
    dim = int(head_dim * rot["share"])
    base = rot["theta"]
    j = np.arange(dim // 2, dtype=np.float64)
    ext = base ** (-2.0 * j / dim)
    if rot["factor"] == 1.0:
        return ext.astype(np.float32)
    inter = ext / rot["factor"]

    def c(r):  # the dimension that turns r times over the original length
        return dim * math.log(rot["original_max"] / (2 * math.pi * r)) / (
            2 * math.log(base)
        )

    low = max(math.floor(c(rot["beta_fast"])), 0)
    high = min(math.ceil(c(rot["beta_slow"])), dim - 1)
    ramp = np.clip((j - low) / ((high - low) or 1e-3), 0.0, 1.0)
    return (inter * ramp + ext * (1.0 - ramp)).astype(np.float32)


def rotary(x, rot):
    """``x [S, H, K]``: position ``t`` turns the pair ``(x[i], x[i + dim/2])``
    of the first ``dim`` dimensions by ``t inv_freq_i`` (the halves
    convention), ``cos`` and ``sin`` times ``amplitude``; the other ``K -
    dim`` dimensions pass through."""
    S, _H, K = x.shape
    freq = inv_freq(rot, K)
    dim = 2 * freq.shape[0]
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * freq  # [S, dim/2]
    cos = (jnp.cos(angle) * rot["amplitude"])[:, None, :]
    sin = (jnp.sin(angle) * rot["amplitude"])[:, None, :]
    x1, x2, rest = x[..., : dim // 2], x[..., dim // 2:dim], x[..., dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1
    )


def attention(sz, mixer, p, x):
    """``x [S, D]`` -> ``Attn(x)``: ``mixer`` ``full`` (every key up to the
    query's own) or ``window`` (the last ``window`` of them)."""
    Hkv, K = sz["kv_heads"], sz["head_dim"]
    S = x.shape[0]
    q = jnp.einsum("sd,dhk->shk", x, p["q"]["kernel"])
    k = jnp.einsum("sd,dhk->shk", x, p["k"]["kernel"])
    v = jnp.einsum("sd,dhk->shk", x, p["v"]["kernel"])
    H = q.shape[1]  # the layer's own head count
    q, k = rotary(q, sz["rotary"][mixer]), rotary(k, sz["rotary"][mixer])
    # key-value head g serves query heads g G .. g G + G - 1
    k, v = (jnp.repeat(a, H // Hkv, axis=1) for a in (k, v))
    reach = sz["window"] if mixer == "window" else S
    qb = sz.get("q_block", 0) or S

    def rows(start, q_rows):
        s = jnp.einsum("qhd,khd->hqk", q_rows, k) / np.sqrt(K)
        t = (start + jnp.arange(q_rows.shape[0]))[:, None]
        keys = jnp.arange(S)[None, :]
        s = jnp.where((keys <= t) & (t - keys < reach), s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    if qb >= S:
        o = rows(0, q)
    else:
        o = jax.lax.map(
            jax.checkpoint(lambda a: rows(a[0], a[1])),
            (jnp.arange(0, S, qb), q.reshape(S // qb, qb, H, K)),
        ).reshape(S, H, K)
    # the gate: one value a head, from the layer's normed input
    o = o * jax.nn.sigmoid(x @ p["o_gate"]["kernel"])[:, :, None]
    return jnp.einsum("shk,hkd->sd", o, p["o"]["kernel"])


def experts(sz, p, x):
    """Router over all ``routed`` experts, the held ones each as a plain
    SwiGLU over every token, masked to the tokens that selected it, plus
    the shared expert, unweighted."""
    s = jax.nn.sigmoid(x @ p["router"]["kernel"])
    _top, idx = jax.lax.top_k(s, sz["top_k"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    if sz["renormalize"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * sz["scale"]
    y = swiglu(*(p["shared"][n]["kernel"] for n in ("gate", "up", "down")), x)
    ex = p["experts"]

    # departure (1): only the held experts; the others' part is left out
    def add_expert(y, e_and_weights):
        e, gate, up, down = e_and_weights
        w_e = jnp.sum(jnp.where(idx == sz["first"] + e, w, 0.0), axis=-1)
        return y + w_e[:, None] * swiglu(gate, up, down, x), None

    if sz.get("layer_remat"):  # an expert's activations are not kept
        add_expert = jax.checkpoint(add_expert)
    y, _ = jax.lax.scan(
        add_expert, y,
        (jnp.arange(sz["held"]), ex["gate"], ex["up"], ex["down"]),
    )
    return y


def layer(sz, kinds, p, x):
    mixer, mlp = kinds
    h = rms_norm(x, p["mixer_norm"]["scale"], sz["eps"])
    x = x + attention(sz, mixer, p["attn"], h)
    h = rms_norm(x, p["mlp_norm"]["scale"], sz["eps"])
    if mlp == "dense":
        m = p["mlp"]
        return x + swiglu(*(m[n]["kernel"] for n in ("gate", "up", "down")), h)
    return x + experts(sz, p["moe"], h)


def hidden(sz, params, emb):
    """``emb [S, D]`` of one sequence -> the final norm's output: the layers
    one after the other."""
    x = emb.astype(jnp.float32)
    for i, kinds in enumerate(sz["layers"]):
        f = functools.partial(layer, sz, kinds)
        if sz.get("layer_remat"):
            f = jax.checkpoint(f)
        x = f(params[f"layer_{i}"], x)
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
    import dataclasses

    kinds = cfg.layer_kinds()
    return dict(
        layers=kinds, kv_heads=cfg.num_key_value_heads,
        head_dim=cfg.head_dim, window=cfg.sliding_window,
        rotary={m: dataclasses.asdict(cfg.rotary_of(m))
                for m in {mixer for mixer, _ in kinds}},
        routed=cfg.n_routed_experts, top_k=cfg.num_experts_per_token,
        scale=cfg.routed_scaling_factor, renormalize=cfg.norm_topk_prob,
        held=cfg.experts_held, first=cfg.experts_first, eps=cfg.norm_eps,
        **blocks,
    )
