"""Plain reference of the hybrid body of ``models/kimi_linear.py``
(Kimi-Linear-48B-A3B-Instruct's layers): forward, loss and gradients in
``jax.numpy`` float32 under ``jax.default_matmul_precision("highest")``.
No chunking of the recurrence, no dispatch, no kernels, no cache: the delta
rule runs token by token (``lax.scan`` over time), attention is a dense
causal softmax, every held expert is a plain SwiGLU over every token with a
mask for the tokens that selected it.  It imports nothing of the package and
takes the parameter tree by its names.

``sizes`` is a plain dict (the published keys the equations need):
``layers`` (``[(mixer, mlp), ...]``), ``heads``, ``head_dim``, ``conv``,
``mla_heads``, ``kv_rank``, ``d_nope``, ``d_pe``, ``d_v``, ``routed``,
``top_k``, ``scale``, ``renormalize``, ``held``, ``first``, ``eps``.

Departures from the published description, each at its line below:
(1) the held share: experts outside ``[first, first + held)`` add nothing;
(2) the router's selection bias is a buffer held at zero (not trained), so
it is left out; (3) the vocabulary is the slice the head holds.

**Blocks, so that the published widths fit one chip** (they change no
result): ``scan_block`` checkpoints the token-by-token scan in blocks of
steps (its backward would else keep a state of ``heads x d_k x d_v`` floats
a token: 17 GB at 8,192 tokens), ``q_block`` computes dense scores for a
block of queries against every key at a time (8.6 GB a sequence otherwise),
``layer_remat`` checkpoints each layer.  The caller hands one sequence at a
time.
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


def delta_rule(q, k, v, log_a, beta, scan_block=0):
    """``S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T``,
    ``o_t = S_t^T q_t``, token by token.  ``q, k, log_a [S, H, K]``, ``v [S,
    H, V]``, ``beta [S, H]``."""
    S, H, K = q.shape

    def step(state, inp):
        q_t, k_t, v_t, la_t, b_t = inp
        state = jnp.exp(la_t)[..., None] * state  # Diag(a_t) S_{t-1}
        kv = jnp.einsum("hk,hkv->hv", k_t, state)
        state = state + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * (v_t - kv))
        return state, jnp.einsum("hk,hkv->hv", q_t, state)

    xs = (q, k, v, log_a, beta)
    state = jnp.zeros((H, K, v.shape[-1]), jnp.float32)
    if not scan_block or S % scan_block:
        return jax.lax.scan(step, state, xs)[1]

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(step, state, inp)

    xs = tuple(a.reshape(S // scan_block, scan_block, *a.shape[1:]) for a in xs)
    return jax.lax.scan(block, state, xs)[1].reshape(S, H, -1)


def kda(sz, p, x):
    H, K = sz["heads"], sz["head_dim"]
    S = x.shape[0]
    flat = lambda w: w.reshape(w.shape[0], H * K)  # noqa: E731
    q, k, v = (
        jax.nn.silu(causal_conv(x @ flat(p[n]["kernel"]), flat(p[f"conv_{n}"])))
        .reshape(S, H, K) for n in "qkv"
    )
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / np.sqrt(K)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    f = ((x @ p["f_a"]["kernel"]) @ flat(p["f_b"]["kernel"])).reshape(S, H, K)
    log_a = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(f + p["dt_bias"])
    beta = jax.nn.sigmoid(x @ p["b"]["kernel"])
    o = delta_rule(q, k, v, log_a, beta, sz.get("scan_block", 0))
    gate = ((x @ p["g_a"]["kernel"]) @ flat(p["g_b"]["kernel"])).reshape(
        S, H, K
    ) + p["g_b"]["bias"]
    o = rms_norm(o, p["o_norm"]["scale"], sz["eps"]) * jax.nn.sigmoid(gate)
    return o.reshape(S, H * K) @ p["o"]["kernel"].reshape(H * K, -1)


def mla(sz, p, x):
    A, C, dn, dr, dv = (sz["mla_heads"], sz["kv_rank"], sz["d_nope"],
                        sz["d_pe"], sz["d_v"])
    S = x.shape[0]
    q = jnp.einsum("sd,dhk->shk", x, p["q"]["kernel"])
    kva = x @ p["kv_a"]["kernel"]
    c = rms_norm(kva[:, :C], p["kv_norm"]["scale"], sz["eps"])
    kv = jnp.einsum("sr,rhk->shk", c, p["kv_b"]["kernel"])
    # k_h = [k_nope_h, k_pe]: k_pe shared by the heads, no rotation (NoPE)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(kva[:, None, C:], (S, A, dr))], axis=-1
    )
    v = kv[..., dn:]
    qb = sz.get("q_block", 0) or S

    def rows(start, q_rows):
        s = jnp.einsum("qhd,khd->hqk", q_rows, k) / np.sqrt(dn + dr)
        ids = start + jnp.arange(q_rows.shape[0])
        s = jnp.where(jnp.arange(S)[None, :] <= ids[:, None], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    if qb >= S:
        o = rows(0, q)
    else:
        o = jax.lax.map(
            jax.checkpoint(lambda a: rows(a[0], a[1])),
            (jnp.arange(0, S, qb), q.reshape(S // qb, qb, A, dn + dr)),
        ).reshape(S, A, dv)
    return jnp.einsum("shk,hkd->sd", o, p["o"]["kernel"])


def experts(sz, p, x):
    """Router over all ``routed`` experts, the held ones each as a plain
    SwiGLU over every token, masked to the tokens that selected it."""
    s = jax.nn.sigmoid(x @ p["router"]["kernel"])
    # departure (2): selection by s alone, the bias buffer is zero
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

    y, _ = jax.lax.scan(
        add_expert, y,
        (jnp.arange(sz["held"]), ex["gate"], ex["up"], ex["down"]),
    )
    return y


def layer(sz, kinds, p, x):
    mixer, mlp = kinds
    h = rms_norm(x, p["mixer_norm"]["scale"], sz["eps"])
    x = x + (kda if mixer == "kda" else mla)(sz, p[mixer], h)
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
    (departure (3)): position ``t`` predicts ``tokens[t + 1]``.
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
        layers=cfg.layer_kinds(), heads=cfg.linear_num_heads,
        head_dim=cfg.linear_head_dim, conv=cfg.short_conv_kernel_size,
        mla_heads=cfg.num_attention_heads, kv_rank=cfg.kv_lora_rank,
        d_nope=cfg.qk_nope_head_dim, d_pe=cfg.qk_rope_head_dim,
        d_v=cfg.v_head_dim, routed=cfg.n_routed_experts,
        top_k=cfg.num_experts_per_token, scale=cfg.routed_scaling_factor,
        renormalize=cfg.moe_renormalize, held=cfg.experts_held,
        first=cfg.experts_first, eps=cfg.rms_norm_eps, **blocks,
    )
