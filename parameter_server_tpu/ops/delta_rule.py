"""Chunked gated delta rule with a per-channel decay (KDA), forward and
backward, in plain XLA operations.

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
    pad = (-S) % chunk
    if pad:
        q, k, v, g = (
            jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (q, k, v, g)
        )
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    nc = (S + pad) // chunk

    def chunks(a):  # [B, S, H, *] -> [NC, B, H, C, *]
        a = a.reshape(B, nc, chunk, H, *a.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)

    f32 = jnp.float32
    terms = _chunk_terms(
        chunks(q.astype(f32)), chunks(k.astype(f32)), chunks(v.astype(f32)),
        chunks(g.astype(f32)), chunks(beta.astype(f32)), sub,
    )

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

    if initial_state is None:
        initial_state = jnp.zeros((B, H, K, V), f32)
    state, o = jax.lax.scan(step, initial_state.astype(f32), terms)
    # [NC, B, H, C, V] -> [B, S, H, V]
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3).reshape(B, nc * chunk, H, V)
    return o[:, :S], state


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
