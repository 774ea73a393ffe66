"""Chunked gated delta rule with a per-channel decay (KDA) or a per-head
scalar one (gated DeltaNet), forward and backward, in plain XLA operations.

The recurrence, per head, with a state ``S [d_k, d_v]``::

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

``a_t in (0, 1]^{d_k}`` arrives as ``g_t = log a_t`` and never leaves log
space: every factor this file exponentiates is a difference of cumulative
sums that is at most 0, so a decay near 0 underflows to 0 and nothing
overflows.

**The chunked form** (chunk ``C``, ``G_t`` the cumulative sum of ``g`` inside
the chunk, ``S_0`` the state the chunk starts from).  With the pseudo-value
``u_t = b_t (v_t - (Diag(a_t) S_{t-1})^T k_t)`` the update is
``S_t = Diag(a_t) S_{t-1} + k_t u_t^T``, and unrolled over the chunk::

    (I + Diag(b) A) U = Diag(b) (V - (K * e^G) S_0)       A[t, s] = sum_c k_t[c] k_s[c] e^(G_t[c] - G_s[c]),  s < t
    O   = (Q * e^G) S_0 + P U                             P[t, s] = sum_c q_t[c] k_s[c] e^(G_t[c] - G_s[c]),  s <= t
    S_C = Diag(e^(G_C)) S_0 + (K * e^(G_C - G))^T U

``I + Diag(b) A`` is unit lower triangular: one triangular solve a chunk
gives ``W_v = T Diag(b) V`` and ``W_k = T Diag(b) (K * e^G)``, after which
``U = W_v - W_k S_0`` and the chunks are a ``lax.scan`` over the state.

``A`` and ``P`` hold ``e^(G_t - G_s)`` per channel, which does not factor
into a product of two safe terms over a whole chunk (``e^(-G_s)`` alone
overflows float32 once a channel has decayed by ``e^-88`` inside the
chunk).  So a chunk is cut into sub-blocks of ``sub`` steps: a pair of
sub-blocks ``i > j`` factors through the cumulative sum at the end of
sub-block ``i - 1`` (three terms, each at most 1, one matrix product), and a
diagonal sub-block is summed exactly over its ``sub x sub x d_k`` terms.

**A scalar decay a head** (gated DeltaNet, :func:`chunk_gated_delta`):
``a_t`` is one value broadcast over ``d_k``, so ``e^(G_t - G_s)`` leaves
the sum over channels, ``A = (K K^T) * D`` and ``P = (Q K^T) * D`` with
``D[t, s] = e^(G_t - G_s)`` (``s <= t``) one ``C x C`` matrix a head, its
exponent masked before it is taken: plain matrix products, no sub-blocks.
Key heads may be fewer than value heads: a key head's products serve each
of its value heads under that head's decay.

**The backward** is jax's differentiation of this file under
``jax.checkpoint``: every step is a matrix product, an elementwise function
or a triangular solve whose transpose XLA derives, the reverse scan over the
chunks' states comes out of ``lax.scan``'s own rule, and a hand-written
``custom_vjp`` would be a second copy of the recurrence to keep equal to the
first.  The checkpoint keeps only ``q, k, v, g, b`` between the passes; the
per-chunk states (``S / C`` of them) live only while this layer's backward
runs.

State, cumulative sums and every exponent are float32.  Matrix products run
at jax's default precision (on a TPU one bfloat16 pass, float32
accumulation); the triangular solve is float32 throughout.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def decayed_gram(x, y, G, *, sub: int, inclusive: bool):
    """``M[t, s] = sum_c x[t, c] y[s, c] exp(G[t, c] - G[s, c])`` for
    ``s < t`` (``s <= t`` when ``inclusive``) and 0 elsewhere.  ``x, y, G``:
    ``[..., C, K]`` with ``G`` non-increasing along ``C`` (a cumulative sum
    of non-positive terms); ``C`` is a multiple of ``sub``."""
    *lead, C, K = x.shape
    n = C // sub
    xs, ys, Gs = (a.reshape(*lead, n, sub, K) for a in (x, y, G))
    t = jnp.arange(sub)
    keep = (t[None, :] <= t[:, None]) if inclusive else (t[None, :] < t[:, None])
    # diagonal sub-blocks: exact, exponent masked before it is taken
    diff = Gs[..., :, None, :] - Gs[..., None, :, :]  # [..., n, t, s, K]
    w = jnp.exp(jnp.where(keep[:, :, None], diff, -jnp.inf))
    diag = jnp.sum(xs[..., :, None, :] * ys[..., None, :, :] * w, axis=-1)
    if n == 1:
        return diag.reshape(*lead, C, C)
    # sub-block pairs i > j, through r_i = G at the end of sub-block i - 1:
    # G_t - r_i <= 0 (t in i), r_i - e_j <= 0, e_j - G_s <= 0 (s in j)
    ends = Gs[..., -1, :]  # e_j [..., n, K]
    starts = jnp.concatenate(
        [jnp.zeros_like(ends[..., :1, :]), ends[..., :-1, :]], axis=-2
    )
    xb = xs * jnp.exp(Gs - starts[..., None, :])
    ye = ys * jnp.exp(ends[..., None, :] - Gs)
    i = jnp.arange(n)
    between = jnp.exp(jnp.where(
        (i[:, None] > i[None, :])[:, :, None],
        starts[..., :, None, :] - ends[..., None, :, :], -jnp.inf,
    ))  # [..., i, j, K]
    off = jnp.einsum(
        "...ijtc,...jsc->...itjs",
        xb[..., :, None, :, :] * between[..., :, :, None, :], ye,
        preferred_element_type=jnp.float32,
    )
    eye = jnp.eye(n, dtype=diag.dtype)
    full = off + diag[..., :, :, None, :] * eye[:, None, :, None]
    return full.reshape(*lead, C, C)


def _chunk_terms(q, k, v, g, beta, sub):
    """Everything of a chunk that does not need the incoming state.
    Inputs ``[..., C, *]``; returns ``(W_k, W_v, Q~, P, K_end, g_C)``."""
    G = jnp.cumsum(g, axis=-2)
    A = decayed_gram(k, k, G, sub=sub, inclusive=False)
    P = decayed_gram(q, k, G, sub=sub, inclusive=True)
    decay = jnp.exp(G)
    bk = beta[..., None] * (k * decay)
    bv = beta[..., None] * v
    # (I + Diag(b) A) [W_k | W_v] = Diag(b) [K e^G | V]: unit lower triangular
    rhs = jnp.concatenate([bk, bv], axis=-1)
    w = jax.lax.linalg.triangular_solve(
        beta[..., None] * A, rhs, left_side=True, lower=True,
        unit_diagonal=True,
    )
    K = k.shape[-1]
    g_end = G[..., -1, :]
    k_end = k * jnp.exp(g_end[..., None, :] - G)
    return w[..., :K], w[..., K:], q * decay, P, k_end, g_end


def _chunks(xs, chunk: int):
    """``xs``: ``[B, S, H, *]`` arrays -> ``[NC, B, H, C, *]`` float32, a
    length that is no multiple of ``chunk`` padded with zeros (steps whose
    ``g = 0`` and ``beta = 0`` leave the state as it is)."""
    S = xs[0].shape[1]
    pad = (-S) % chunk
    nc = (S + pad) // chunk

    def one(a):
        a = a.astype(jnp.float32)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape(a.shape[0], nc, chunk, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)

    return tuple(one(a) for a in xs)


def _scan_chunks(terms, initial_state, S: int):
    """The chunks in turn over the state: ``terms`` ``(W_k, W_v, Q~, P,
    K_end, g_C)`` ``[NC, B, H, C, *]`` (``g_C`` with a last axis of ``K``,
    or of 1 where the decay is a head's scalar) -> ``(o [B, S, H, V],
    final state [B, H, K, V])``."""
    f32 = jnp.float32

    def step(state, inp):
        w_k, w_v, q_dec, p, k_end, g_end = inp
        u = w_v - jnp.einsum(
            "bhck,bhkv->bhcv", w_k, state, preferred_element_type=f32
        )
        o = jnp.einsum(
            "bhck,bhkv->bhcv", q_dec, state, preferred_element_type=f32
        ) + jnp.einsum("bhcs,bhsv->bhcv", p, u, preferred_element_type=f32)
        state = jnp.exp(g_end)[..., None] * state + jnp.einsum(
            "bhck,bhcv->bhkv", k_end, u, preferred_element_type=f32
        )
        return state, o

    state, o = jax.lax.scan(step, initial_state.astype(f32), terms)
    # [NC, B, H, C, V] -> [B, S, H, V]
    nc, B, H, C, V = o.shape
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3).reshape(B, nc * C, H, V)
    return o[:, :S], state


def chunk_kda(q, k, v, g, beta, *, chunk: int = 64, sub: int = 16,
              initial_state=None):
    """``q, k, g [B, S, H, K]``, ``v [B, S, H, V]``, ``beta [B, S, H]`` ->
    ``(o [B, S, H, V], final state [B, H, K, V])``.  ``q`` and ``k`` arrive
    normalised and scaled by the caller; ``g = log a <= 0``.  A length that
    is no multiple of ``chunk`` is padded with steps that leave the state as
    it is (``g = 0``, ``beta = 0``) and whose outputs are cut off."""
    B, S, H, K = q.shape
    V = v.shape[-1]
    chunk = min(chunk, max(sub, -(-S // sub) * sub))
    terms = _chunk_terms(*_chunks((q, k, v, g, beta), chunk), sub)
    if initial_state is None:
        initial_state = jnp.zeros((B, H, K, V), jnp.float32)
    return _scan_chunks(terms, initial_state, S)


def _gated_terms(q, k, v, g, beta):
    """:func:`_chunk_terms` under a head's scalar decay, its key heads
    grouped: ``q, k [..., Hk, C, K]``, ``v [..., Hv, C, V]``, ``g, beta
    [..., Hv, C]``, value head ``j`` reading key head ``j // (Hv / Hk)``.
    ``A`` and ``P`` are the key heads' products times one ``C x C`` decay
    matrix a value head, its exponent masked before it is taken."""
    *lead, Hk, C, K = k.shape
    Hv = v.shape[-3]
    grouped = (*lead, Hk, Hv // Hk, C)

    def per_value(x):  # [..., Hk, C, *] -> [..., Hv, C, *]
        x = jnp.broadcast_to(x[..., :, None, :, :], (*grouped, x.shape[-1]))
        return x.reshape(*lead, Hv, C, x.shape[-1])

    G = jnp.cumsum(g, axis=-1)
    t = jnp.arange(C)
    decay = jnp.exp(jnp.where(
        t[None, :] <= t[:, None], G[..., :, None] - G[..., None, :], -jnp.inf
    ))  # [..., Hv, t, s]: e^(G_t - G_s) for s <= t, else 0
    kk, qk = (
        jnp.einsum("...tc,...sc->...ts", a, k, preferred_element_type=jnp.float32)
        for a in (k, q)
    )

    def decayed(x):  # [..., Hk, C, C] x the value heads' decay
        return (x[..., :, None, :, :] * decay.reshape(*grouped, C)).reshape(decay.shape)

    A = jnp.tril(decayed(kk), -1)
    P = decayed(qk)
    e = jnp.exp(G)[..., None]
    k_v = per_value(k)
    rhs = jnp.concatenate([beta[..., None] * (k_v * e), beta[..., None] * v], axis=-1)
    w = jax.lax.linalg.triangular_solve(
        beta[..., None] * A, rhs, left_side=True, lower=True,
        unit_diagonal=True,
    )
    g_end = G[..., -1:]
    k_end = k_v * jnp.exp(g_end - G)[..., None]
    return w[..., :K], w[..., K:], per_value(q) * e, P, k_end, g_end


def chunk_gated_delta(q, k, v, g, beta, *, chunk: int = 64,
                      initial_state=None):
    """The delta rule under a scalar decay a head and position (gated
    DeltaNet): ``q, k [B, S, Hk, K]``, ``v [B, S, Hv, V]``, ``g, beta [B, S,
    Hv]`` with ``Hv`` a multiple of ``Hk`` (value head ``j`` reads key head
    ``j // (Hv / Hk)``) -> ``(o [B, S, Hv, V], final state [B, Hv, K, V])``.
    ``q`` and ``k`` arrive normalised and scaled by the caller; ``g = log a
    <= 0``.  The recurrence of the module docstring with ``a_t`` one value
    broadcast over ``d_k``: its chunked form needs no sub-blocks, since
    ``e^(G_t - G_s)`` is one ``C x C`` matrix a head, masked before the
    exponent is taken; a length that is no multiple of ``chunk`` is padded
    as :func:`chunk_kda` pads it."""
    B, S, Hk, K = q.shape
    Hv, V = v.shape[2:]
    if Hv % Hk:
        raise ValueError(f"{Hv} value heads over {Hk} key heads")
    terms = _gated_terms(*_chunks((q, k, v, g, beta), min(chunk, S)))
    if initial_state is None:
        initial_state = jnp.zeros((B, Hv, K, V), jnp.float32)
    return _scan_chunks(terms, initial_state, S)


def recurrent_gated_delta(q, k, v, g, beta, initial_state=None):
    """:func:`chunk_gated_delta`'s recurrence token by token: the key heads
    repeated for their value heads and the decay broadcast over ``d_k``
    into :func:`recurrent_kda`.  Same shapes."""
    G = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(a, G, axis=2) for a in (q, k))
    return recurrent_kda(
        q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta, initial_state
    )


def recurrent_kda(q, k, v, g, beta, initial_state=None):
    """The recurrence of the module docstring token by token (``lax.scan``
    over time), float32 at the highest matrix precision: what
    :func:`chunk_kda` is tested against.  Same shapes."""
    B, S, H, K = q.shape
    V = v.shape[-1]
    f32 = jnp.float32

    def step(state, inp):
        q_t, k_t, v_t, g_t, b_t = inp  # [B, H, *]
        state = jnp.exp(g_t)[..., None] * state
        kv = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", k_t, b_t[..., None] * (v_t - kv)
        )
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    if initial_state is None:
        initial_state = jnp.zeros((B, H, K, V), f32)
    with jax.default_matmul_precision("highest"):
        state, o = jax.lax.scan(
            step, initial_state.astype(f32),
            tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta)),
        )
    return jnp.moveaxis(o, 0, 1), state
