"""Causal softmax attention over tiles of queries and keys, in plain XLA
operations.

**Tiles** (:func:`blocked_causal_attention`): one loop over the blocks of
``block`` queries (``lax.scan``), and inside it one loop over the tiles of
keys up to the block's own (``fori_loop``), each tile ``[block, width]``
scored, masked from the tiles' traced positions and taken into a running
softmax (maximum, sum and weighted values in float32, the online form of
the blockwise recurrence).  No ``[S, S]`` tensor and no score block longer
than a tile is ever live, and no key a block cannot see is scored outside
the tiles that cross the diagonal: at 8,192 tokens and tiles of 256, 528
tiles a pass where the causal half is 512.  The program is two loops a
pass whatever the length.  A tile is a block of keys wide, two where the
head is narrower than 128: its products are then half as deep, and a
wider tile keeps a loop turn's work (at 32 heads of 64 over 8, 8 % less
time for value and gradients on a v5e).

**Grouped queries**: ``k`` and ``v`` may have fewer heads than ``q`` (``Hkv``
key-value heads, each serving the ``G = H / Hkv`` query heads ``g G .. g G +
G - 1``).  The queries are then read as ``[B, S, Hkv, G, D]`` and scored
against their group's one key head; ``k`` and ``v`` are never copied out per
query head, forward or backward (``dk`` and ``dv`` are summed over a group's
query heads by the product that forms them).

**A window** (``window``: a query sees itself and the ``window - 1`` keys
before it).  The keys a block of ``block`` queries can see are ``[start -
back, start + block)`` with ``back = ceil_to_block(window - 1)``: a slice
whose width is the same for every block, so a window layer is one loop over
all its blocks (``lax.scan``), each scoring its slice of the keys at a
traced offset in one block of scores, the keys outside not computed (at
8,192 tokens, blocks of 256 and a window of 512: 768 keys a block of
queries); the keys are padded in front by ``back`` zero rows so that the
first blocks' slices exist, and masked there.  The backward adds a block's
``dk`` and ``dv`` into the same slice of one accumulator for the sequence.
Three tiles of 256 in a running softmax instead took 4.7 % longer at a
window layer's shapes on a v5e.  ``window=None`` and ``window >= S`` are the
causal program.

**Block diffusion** (:func:`block_diffusion_attention`): the body runs on a
noisy copy ``xt`` of a sequence beside its clean one ``x0``, ``2L``
positions, cut into diffusion blocks of ``b`` tokens.  A query of ``x0`` in
block ``j`` sees the ``x0`` keys of blocks ``<= j``; a query of ``xt`` in
block ``j`` sees the ``x0`` keys of blocks ``< j`` and the ``xt`` keys of
block ``j`` in both directions, which lie in its own block of queries (``b``
divides ``block``).  One loop over the blocks of queries takes both halves'
block at once, and an inner loop over the ``x0`` key tiles up to its own
carries a running softmax a half; the ``xt`` rows then take their own
block's ``xt`` keys into the same softmax, one log-sum-exp: ``L^2 + 2 L
block`` pairs a head and sequence where ``L^2 + L b`` are needed, half of a
causal pass over ``2L``.  It shares the running softmax and a tile's
backward with the causal loop, operation for operation what it ran before
(``tests/test_sdar_moe.py`` holds its operations).

A key may have a part all heads share (latent attention's positional part,
``k_shared [B, S, Dr]`` against ``q_shared [B, S, H, Dr]``): it is scored on
its own, so it is never copied out per head; the tiles score no part of
width 0.

**The backward is written out** (a ``custom_vjp``): the forward keeps ``q,
k, v``, the output and the log-sum-exp of every query; the backward runs the
same loops, recomputes one tile's (a window: one block's) probabilities at a
time from the log-sum-exp, carries ``dq`` of its block of queries through
the inner loop and adds the tile's ``dk`` and ``dv`` into one float32
accumulator for the sequence (``dynamic_update_slice``).  Differentiating
the forward instead keeps every tile's scores for the backward.

**Nothing is stacked across a checkpoint**: every loop ties its block's
index to the block's inputs (:func:`_tied`), so the masks, which depend on
the index alone, are computed where they are used.

Scores, softmax, log-sum-exp and the output are float32; the products run
at jax's default precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _grouped(x, kv_heads):
    """``[B, S, H, ...]`` -> ``[B, S, Hkv, G, ...]`` (query head ``h`` is ``(h
    // G, h % G)``); as it came where ``Hkv == H``."""
    H = x.shape[2]
    if H == kv_heads:
        return x
    return x.reshape(*x.shape[:2], kv_heads, H // kv_heads, *x.shape[3:])


def _ungrouped(x, heads):
    """``[B, S, Hkv, G, ...]`` or ``[B, S, H, ...]`` -> ``[B, S, H, ...]``."""
    if x.shape[2] == heads:
        return x
    return x.reshape(*x.shape[:2], heads, *x.shape[4:])


def _g(q, k):
    """The group axis in a product's subscripts: ``g`` where the queries
    carry one (``q [B, bq, Hkv, G, D]``), none otherwise."""
    return "g" if q.ndim > k.ndim else ""


def _pad_queries(x, padded):
    extra = padded - x.shape[1]
    if not extra:
        return x
    return jnp.pad(x, ((0, 0), (0, extra)) + ((0, 0),) * (x.ndim - 2))


def _split(x, block):
    """``[B, n * block, ...]`` -> ``[n, B, block, ...]`` for a scan."""
    B, n = x.shape[0], x.shape[1] // block
    return jnp.moveaxis(x.reshape(B, n, block, *x.shape[2:]), 1, 0)


def _join(x):
    """``[n, B, block, ...]`` -> ``[B, n * block, ...]``."""
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape(x.shape[0], x.shape[1] * x.shape[2], *x.shape[3:])


def _per_query(x):
    """``[B, bq, Hkv, (G)]`` -> ``[B, Hkv, (G,) bq, 1]``, beside the scores."""
    return jnp.moveaxis(x, 1, -1)[..., None]


def _to_rows(x):
    """``[B, Hkv, (G,) bq, 1]`` -> ``[B, bq, Hkv, (G,) 1]``, beside the
    output rows."""
    return jnp.moveaxis(x[..., 0], -1, 1)[..., None]


def _tied(x):
    """A block's index and its arrays, the index not to be had without the
    arrays.  Where a checkpointed layer runs one sequence at a time (a
    ``lax.map`` of a ``jax.checkpoint``: ``models/moe.py::by_sequence``),
    what depends on no input of the layer (the index, so the mask, so the
    mask broadcast to the scores' shape) is split off into a loop of its own
    that runs in the forward pass and keeps its outputs stacked over all
    blocks for the backward pass: 1.9 GB a window layer at 64 heads and
    8,192 tokens, 1.7 GB a full layer at 48 (found by compiling the step for
    the chip's memory; ``tests/test_tpu_compile.py`` holds it).  Tied to the
    queries, they are computed where they are used."""
    return jax.lax.optimization_barrier(tuple(x))


def _slices(xs, start, width):
    return tuple(
        jax.lax.dynamic_slice_in_dim(x, start, width, axis=1) for x in xs
    )


def _lse(lse, H):
    """A scan's ``[n, B, Hkv, (G,) block]`` log-sum-exp -> ``[B, H, n block]``."""
    return jnp.moveaxis(_ungrouped(_join(jnp.moveaxis(lse, -1, 2)), H), 1, 2)


# -- the running softmax over tiles, and a tile's backward ------------------------
#: a running maximum before any key was seen (``exp(-inf - _FLOOR)`` is 0, and
#: a row whose keys so far were all masked keeps a zero sum, not a NaN)
_FLOOR = -1e30


def _empty(q, dv):
    """The running softmax of the rows of ``q [B, bq, Hkv, (G,) D]`` before
    any key: ``(max, sum, weighted values)``."""
    stat = (q.shape[0], *q.shape[2:-1], q.shape[1], 1)
    return (jnp.full(stat, _FLOOR, jnp.float32), jnp.zeros(stat, jnp.float32),
            jnp.zeros((*q.shape[:-1], dv), jnp.float32))


def _online(g, state, s, v):
    """The running softmax after one more tile of masked scores ``s`` and
    the values ``v`` they weigh."""
    m, l, acc = state  # noqa: E741
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    return m_new, alpha * l + jnp.sum(p, axis=-1, keepdims=True), (
        acc * _to_rows(alpha) + jnp.einsum(
            f"bh{g}qk,bkhd->bqh{g}d", p, v, preferred_element_type=jnp.float32
        )
    )


def _finish(state):
    """A block of rows' output and log-sum-exp: every real row saw a key
    (causal: its own; block diffusion: the first ``x0`` key, or its own
    ``xt`` key in the first diffusion block), so its sum is positive."""
    m, l, acc = state  # noqa: E741
    return acc / _to_rows(l), (m + jnp.log(l))[..., 0]


def _tile_grads(g, scale, s, lse, delta, q, do, k, v, shared=()):
    """One tile of scores' part of the backward from the recomputed masked
    scores ``s``, its queries' log-sum-exp and ``delta`` (``[B, bq, Hkv,
    (G)]``), queries ``q``, ``d_out`` and the keys ``k``, ``v`` it saw;
    ``shared``: ``(q_shared, k_shared)`` of a shared key part, or empty.
    Returns ``((dq, *dq_shared), (dk, dv, *dk_shared))``."""
    f32 = jnp.float32
    p = jnp.exp(s - _per_query(lse))
    # dk, dv: the product sums over a group's query heads
    dv = jnp.einsum(f"bh{g}qk,bqh{g}d->bkhd", p, do, preferred_element_type=f32)
    dp = jnp.einsum(f"bqh{g}d,bkhd->bh{g}qk", do, v, preferred_element_type=f32)
    ds = p * (dp - _per_query(delta)) * scale
    dq = jnp.einsum(f"bh{g}qk,bkhd->bqh{g}d", ds, k, preferred_element_type=f32)
    dk = jnp.einsum(f"bh{g}qk,bqh{g}d->bkhd", ds, q, preferred_element_type=f32)
    if not shared:
        return (dq,), (dk, dv)
    qs, ks = shared
    return (dq, jnp.einsum(f"bh{g}qk,bkd->bqh{g}d", ds, ks, preferred_element_type=f32)), (
        dk, dv, jnp.einsum(f"bh{g}qk,bqh{g}d->bkd", ds, qs, preferred_element_type=f32)
    )


# -- causal: a loop over blocks of queries, one over the key tiles up to its own ---
def _width(block, head):
    """Keys a tile: a block's, two blocks' where the ``head`` is narrower
    than the 128 a product contracts at full depth."""
    return block if head >= 128 else 2 * block


def _tile(scale, q_start, k_start, q, k, shared):
    """Masked, scaled scores ``[B, Hkv, (G,) bq, bk]`` of the queries from
    ``q_start`` against the keys from ``k_start``: a query sees the keys up
    to its own; ``shared``: ``(q_shared, k_shared)`` scored beside, or
    empty."""
    g = _g(q, k)
    s = jnp.einsum(
        f"bqh{g}d,bkhd->bh{g}qk", q, k, preferred_element_type=jnp.float32
    )
    if shared:
        s = s + jnp.einsum(
            f"bqh{g}d,bkd->bh{g}qk", *shared, preferred_element_type=jnp.float32
        )
    q_ids = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
    k_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
    return jnp.where(k_ids <= q_ids, s * scale, -jnp.inf)


def _key_tiles(i, block, width):
    """The tiles of ``width`` keys that the block ``i`` of ``block`` queries
    sees: ``0 ..`` the one that holds its last query."""
    return (i * block + block - 1) // width + 1


def _keys(width, S, k, v, k_shared):
    """``(k, v[, k_shared])`` padded behind to whole tiles of ``width``,
    the shared part only where it has a width."""
    keys = (k, v, k_shared) if k_shared.shape[-1] else (k, v)
    padded = -(-S // width) * width
    return tuple(_pad_queries(x, padded) for x in keys)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _attention(block, scale, q, k, v, q_shared, k_shared):
    return _forward(block, scale, q, k, v, q_shared, k_shared)[0]


def _forward(block, scale, q, k, v, q_shared, k_shared):
    S, H, dv = q.shape[1], q.shape[2], v.shape[-1]
    width = _width(block, q.shape[-1])
    padded = -(-S // block) * block
    q, q_shared = (
        _grouped(_pad_queries(x, padded), k.shape[2]) for x in (q, q_shared)
    )
    g = _g(q, k)
    keys = _keys(width, S, k, v, k_shared)

    def one(_, x):
        i, qb, qsb = _tied(x)

        def key_tile(j, state):
            kb, vb, *ksb = _slices(keys, j * width, width)
            s = _tile(scale, i * block, j * width, qb, kb,
                      (qsb, *ksb) if ksb else ())
            return _online(g, state, s, vb)

        return None, _finish(jax.lax.fori_loop(
            0, _key_tiles(i, block, width), key_tile, _empty(qb, dv)
        ))

    _, (out, lse) = jax.lax.scan(
        one, None,
        (jnp.arange(padded // block), _split(q, block), _split(q_shared, block)),
    )
    return _ungrouped(_join(out), H)[:, :S], _lse(lse, H)[:, :, :S]


def _fwd(block, scale, q, k, v, q_shared, k_shared):
    out, lse = _forward(block, scale, q, k, v, q_shared, k_shared)
    return out, (q, k, v, q_shared, k_shared, out, lse)


def _padded_for_bwd(block, res, d_out):
    """What a backward loop reads per block of queries, padded to whole
    blocks and grouped: ``(q, q_shared, d_out, delta, lse)``."""
    q, k, _v, q_shared, _k_shared, out, lse = res
    S = q.shape[1]
    padded = -(-S // block) * block
    # rowsum(dO * O) = rowsum(P * dP): the softmax's own term
    delta = jnp.sum(d_out * out, axis=-1)  # [B, S, H]
    q, q_shared, d_out, delta = (
        _pad_queries(x, padded) for x in (q, q_shared, d_out, delta)
    )
    # a query of the padding has no row of its own: its probabilities are 0
    lse = _pad_queries(jnp.moveaxis(lse, 1, 2), padded)  # [B, S, H]
    if padded > S:
        lse = lse.at[:, S:].set(jnp.inf)
    return tuple(
        _grouped(x, k.shape[2]) for x in (q, q_shared, d_out, delta, lse)
    )


def _bwd(block, scale, res, d_out):
    q_in, k, v, qs_in, k_shared, out, _lse_in = res
    S, H = out.shape[1:3]
    width = _width(block, q_in.shape[-1])
    xs = _padded_for_bwd(block, res, d_out)
    g = _g(xs[0], k)
    keys = _keys(width, S, k, v, k_shared)
    f32 = jnp.float32

    def one(acc, x):
        i, qb, qsb, dob, dlb, lsb = _tied(x)

        def key_tile(j, carry):
            acc, dq = carry
            at = j * width
            kb, vb, *ksb = _slices(keys, at, width)
            shared = (qsb, *ksb) if ksb else ()
            dq_t, dk_t = _tile_grads(
                g, scale, _tile(scale, i * block, at, qb, kb, shared),
                lsb, dlb, qb, dob, kb, vb, shared,
            )
            # the tile's dk, dv are added into the keys it saw
            acc = tuple(
                jax.lax.dynamic_update_slice_in_dim(a, b + t, at, axis=1)
                for a, b, t in zip(acc, _slices(acc, at, width), dk_t)
            )
            return acc, tuple(a + t for a, t in zip(dq, dq_t))

        zeros = tuple(
            jnp.zeros(x.shape, f32) for x in ((qb, qsb) if len(keys) > 2 else (qb,))
        )
        return jax.lax.fori_loop(
            0, _key_tiles(i, block, width), key_tile, (acc, zeros)
        )

    acc, dq = jax.lax.scan(
        one, tuple(jnp.zeros(x.shape, f32) for x in keys),
        (jnp.arange(xs[0].shape[1] // block), *(_split(x, block) for x in xs)),
    )
    dq = tuple(_ungrouped(_join(x), H)[:, :S] for x in dq)
    dk, dv, *dks = (x[:, :S] for x in acc)
    return (
        dq[0].astype(q_in.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
        dq[1].astype(qs_in.dtype) if dks else jnp.zeros_like(qs_in),
        dks[0].astype(k_shared.dtype) if dks else jnp.zeros_like(k_shared),
    )


_attention.defvjp(_fwd, _bwd)


# -- under a window: one loop over all blocks, a slice of the keys each ----------
def _scores(start, scale, q, k, q_shared, k_shared, window, back):
    """Masked, scaled scores ``[B, Hkv, (G,) bq, end]`` of the queries
    ``start ..`` against the slice of the keys that starts ``back`` before
    them (before position 0 for the first blocks: masked): a query sees the
    ``window`` keys that end with its own."""
    g = _g(q, k)
    s = jnp.einsum(
        f"bqh{g}d,bkhd->bh{g}qk", q, k, preferred_element_type=jnp.float32
    ) + jnp.einsum(
        f"bqh{g}d,bkd->bh{g}qk", q_shared, k_shared,
        preferred_element_type=jnp.float32,
    )
    q_ids = start + jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
    k_ids = jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
    k_ids = k_ids + (start - back)
    seen = (k_ids <= q_ids) & (k_ids > q_ids - window) & (k_ids >= 0)
    return jnp.where(seen, s * scale, -jnp.inf)


def _block_out(g, s, vb):
    """A block's output and the log-sum-exp of its queries from its masked
    scores ``s`` and the values ``vb`` they weigh."""
    m = jnp.max(s, axis=-1, keepdims=True)  # finite: a row holds itself
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)  # noqa: E741
    out = jnp.einsum(
        f"bh{g}qk,bkhd->bqh{g}d", p / l, vb,
        preferred_element_type=jnp.float32,
    )
    return out, (m + jnp.log(l))[..., 0]


def _block_grads(g, scale, s, acc, x, keys):
    """A block's part of the backward from its recomputed masked scores
    ``s``: ``acc`` = the ``(dk, dv, dk_shared)`` of the keys ``keys = (k, v,
    k_shared)`` the block saw, with the block's part added; ``x`` = the
    block's ``(q, q_shared, d_out, delta, lse)``.  Returns ``(acc, (dq,
    dq_shared))``."""
    f32 = jnp.float32
    dkb, dvb, dksb = acc
    qb, qsb, do, dl, ls = x
    kb, vb, ksb = keys
    p = jnp.exp(s - _per_query(ls))
    # dk, dv: the product sums over a group's query heads
    dvb = dvb + jnp.einsum(
        f"bh{g}qk,bqh{g}d->bkhd", p, do, preferred_element_type=f32
    )
    dp = jnp.einsum(
        f"bqh{g}d,bkhd->bh{g}qk", do, vb, preferred_element_type=f32
    )
    ds = p * (dp - _per_query(dl)) * scale
    dqb = jnp.einsum(
        f"bh{g}qk,bkhd->bqh{g}d", ds, kb, preferred_element_type=f32
    )
    dqsb = jnp.einsum(
        f"bh{g}qk,bkd->bqh{g}d", ds, ksb, preferred_element_type=f32
    )
    dkb = dkb + jnp.einsum(
        f"bh{g}qk,bqh{g}d->bkhd", ds, qb, preferred_element_type=f32
    )
    dksb = dksb + jnp.einsum(
        f"bh{g}qk,bqh{g}d->bkd", ds, qsb, preferred_element_type=f32
    )
    return (dkb, dvb, dksb), (dqb, dqsb)


def _window_keys(block, window, S, k, v, k_shared):
    """``(back, width, (k, v, k_shared) padded)``: a block of queries at
    ``start`` sees the ``width`` rows from ``start`` of the keys padded in
    front by ``back`` zero rows (and behind to whole blocks)."""
    padded = -(-S // block) * block
    back = -(-(window - 1) // block) * block
    pad = lambda x: jnp.pad(  # noqa: E731
        x, ((0, 0), (back, padded - S)) + ((0, 0),) * (x.ndim - 2)
    )
    return back, back + block, tuple(pad(x) for x in (k, v, k_shared))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _window_attention(block, window, scale, q, k, v, q_shared, k_shared):
    return _window_forward(block, window, scale, q, k, v, q_shared, k_shared)[0]


def _window_forward(block, window, scale, q, k, v, q_shared, k_shared):
    S, H = q.shape[1:3]
    padded = -(-S // block) * block
    q, q_shared = (
        _grouped(_pad_queries(x, padded), k.shape[2]) for x in (q, q_shared)
    )
    g = _g(q, k)
    back, width, keys = _window_keys(block, window, S, k, v, k_shared)

    def one(_, x):
        i, qb, qsb = _tied(x)
        kb, vb, ksb = _slices(keys, i * block, width)
        s = _scores(i * block, scale, qb, kb, qsb, ksb, window, back)
        return None, _block_out(g, s, vb)

    _, (out, lse) = jax.lax.scan(
        one, None,
        (jnp.arange(padded // block), _split(q, block), _split(q_shared, block)),
    )
    return _ungrouped(_join(out), H)[:, :S], _lse(lse, H)[:, :, :S]


def _window_fwd(block, window, scale, q, k, v, q_shared, k_shared):
    out, lse = _window_forward(block, window, scale, q, k, v, q_shared, k_shared)
    return out, (q, k, v, q_shared, k_shared, out, lse)


def _window_bwd(block, window, scale, res, d_out):
    _q, k, v, _q_shared, k_shared, out, _lse = res
    S, H = out.shape[1:3]
    xs = _padded_for_bwd(block, res, d_out)
    g = _g(xs[0], k)
    back, width, keys = _window_keys(block, window, S, k, v, k_shared)

    def one(acc, x):
        i, *xb = _tied(x)
        start = i * block
        kb, _vb, ksb = seen = _slices(keys, start, width)
        s = _scores(start, scale, xb[0], kb, xb[1], ksb, window, back)
        # a block's dk, dv are added into the slice of the keys it saw
        part, dqb = _block_grads(
            g, scale, s, _slices(acc, start, width), xb, seen
        )
        return tuple(
            jax.lax.dynamic_update_slice_in_dim(a, b, start, axis=1)
            for a, b in zip(acc, part)
        ), dqb

    (dk, dv, dks), (dq, dqs) = jax.lax.scan(
        one, tuple(jnp.zeros_like(x) for x in keys),
        (jnp.arange(xs[0].shape[1] // block), *(_split(x, block) for x in xs)),
    )
    dq, dqs = (_ungrouped(_join(x), H)[:, :S] for x in (dq, dqs))
    dk, dv, dks = (x[:, back:back + S] for x in (dk, dv, dks))
    return dq, dk, dv, dqs, dks


_window_attention.defvjp(_window_fwd, _window_bwd)


def blocked_causal_attention(q, k, v, *, block: int, scale: float,
                             q_shared=None, k_shared=None, window=None):
    """``q [B, S, H, D]``, ``k [B, S, Hkv, D]``, ``v [B, S, Hkv, Dv]`` with
    ``H % Hkv == 0`` -> ``[B, S, H, Dv]`` float32.  ``block``: queries a
    block (a causal tile's keys: one block, two under a head narrower than
    128).  ``window``: a query sees itself and the ``window - 1`` keys
    before it (None, or at least ``S``: every key up to its own, the causal
    tiles).

    ``q_shared`` / ``k_shared``: the part all heads share, or None.  A caller
    without one whose layer is a ``jax.checkpoint`` run one sequence at a time
    (``models/moe.py::by_sequence``) hands a window over the empty slices
    ``q[..., :0]`` and ``k[:, :, 0, :0]`` instead of None: the window's slice
    loop scores a shared part of width 0 as zeros made below, which depend
    on no input of the layer (:func:`_tied`).  The causal tiles score no
    part of width 0, and take None or empty slices alike."""
    B, S, H, _ = q.shape
    if k.shape[2] != v.shape[2] or H % k.shape[2]:
        raise ValueError(
            f"{H} query heads over {k.shape[2]} key and {v.shape[2]} value heads"
        )
    if window is not None and window < 1:
        raise ValueError(f"a window of {window} keys")
    if q_shared is None:  # a shared part of width 0
        q_shared = jnp.zeros((B, S, H, 0), q.dtype)
        k_shared = jnp.zeros((B, S, 0), k.dtype)
    if window is None or window >= S:
        return _attention(block, float(scale), q, k, v, q_shared, k_shared)
    return _window_attention(
        block, int(window), float(scale), q, k, v, q_shared, k_shared
    )


# -- block diffusion: a noisy copy beside the clean sequence ----------------------
def _bd_tile(scale, diffusion, L, q_start, k_start, q, k, rule):
    """Masked, scaled scores ``[B, Hkv, (G,) bq, bk]`` of the block of
    queries from ``q_start`` against the block of keys from ``k_start`` (a
    half each, positions ``0..L-1``).  ``rule``: ``clean`` (an ``x0`` query
    sees the ``x0`` keys of its diffusion block and before), ``prefix`` (an
    ``xt`` query sees the ``x0`` keys before its diffusion block) or ``own``
    (an ``xt`` query sees the ``xt`` keys of its diffusion block); keys from
    ``L`` on are padding."""
    g = _g(q, k)
    s = jnp.einsum(
        f"bqh{g}d,bkhd->bh{g}qk", q, k, preferred_element_type=jnp.float32
    )
    q_blk = (q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)) // diffusion
    k_ids = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
    k_blk = k_ids // diffusion
    if rule == "clean":
        seen = k_blk <= q_blk
    elif rule == "prefix":
        seen = k_blk < q_blk
    else:
        seen = k_blk == q_blk
    return jnp.where(seen & (k_ids < L), s * scale, -jnp.inf)


def _bd_halves(block, q, k, v):
    """``(qt, q0)`` padded to whole blocks and grouped, ``(kt, vt, k0, v0)``
    padded to whole blocks (the ``xt`` half first)."""
    L, Hkv = q.shape[1] // 2, k.shape[2]
    padded = -(-L // block) * block
    return (
        tuple(_grouped(_pad_queries(x, padded), Hkv) for x in (q[:, :L], q[:, L:])),
        tuple(_pad_queries(x, padded) for x in (k[:, :L], v[:, :L], k[:, L:], v[:, L:])),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _bd_attention(diffusion, block, scale, q, k, v):
    return _bd_forward(diffusion, block, scale, q, k, v)[0]


def _bd_forward(diffusion, block, scale, q, k, v):
    L, H = q.shape[1] // 2, q.shape[2]
    (qt, q0), (kt, vt, k0, v0) = _bd_halves(block, q, k, v)
    g, dv = _g(q0, k0), v.shape[-1]

    def one(_, x):
        i, qtb, q0b, ktb, vtb = _tied(x)
        start = i * block

        def key_block(j, states):
            clean, noisy = states
            kb, vb = _slices((k0, v0), j * block, block)
            tile = functools.partial(_bd_tile, scale, diffusion, L, start, j * block)
            return (_online(g, clean, tile(q0b, kb, "clean"), vb),
                    _online(g, noisy, tile(qtb, kb, "prefix"), vb))

        # the x0 keys of the blocks up to this one, then the xt keys of its own
        clean, noisy = jax.lax.fori_loop(
            0, i + 1, key_block, (_empty(q0b, dv), _empty(qtb, dv))
        )
        noisy = _online(g, noisy, _bd_tile(
            scale, diffusion, L, start, start, qtb, ktb, "own"
        ), vtb)
        return None, (_finish(noisy), _finish(clean))

    _, parts = jax.lax.scan(
        one, None,
        (jnp.arange(qt.shape[1] // block), *(_split(x, block) for x in (qt, q0, kt, vt))),
    )
    out = jnp.concatenate(
        [_ungrouped(_join(o), H)[:, :L] for o, _ in parts], axis=1
    ).astype(q.dtype)
    lse = jnp.concatenate([_lse(s, H)[:, :, :L] for _, s in parts], axis=2)
    return out, lse


def _bd_fwd(diffusion, block, scale, q, k, v):
    out, lse = _bd_forward(diffusion, block, scale, q, k, v)
    return out, (q, k, v, out, lse)


def _bd_bwd(diffusion, block, scale, res, d_out):
    q, k, v, out, lse = res
    L, H, Hkv = q.shape[1] // 2, q.shape[2], k.shape[2]
    (qt, q0), (kt, vt, k0, v0) = _bd_halves(block, q, k, v)
    padded = qt.shape[1]
    g = _g(q0, k0)
    # rowsum(dO * O) = rowsum(P * dP), over both parts of a noisy row alike
    delta = jnp.sum(d_out * out, axis=-1)  # [B, 2L, H]
    lse = jnp.moveaxis(lse, 1, 2)  # [B, 2L, H]

    def halves(x, fill=None):
        """``x [B, 2L, ...]`` -> its two halves padded to whole blocks and
        grouped; a query of the padding has no row (``fill``: its lse)."""
        out = []
        for h in (x[:, :L], x[:, L:]):
            h = _pad_queries(h, padded)
            if padded > L and fill is not None:
                h = h.at[:, L:].set(fill)
            out.append(_grouped(h, Hkv))
        return out

    (dot, do0), (dlt, dl0) = halves(d_out), halves(delta)
    lst, ls0 = halves(lse, jnp.inf)
    f32 = jnp.float32

    def one(acc, x):
        i, qtb, q0b, dotb, do0b, dltb, dl0b, lstb, ls0b, ktb, vtb = _tied(x)
        start = i * block

        def key_block(j, carry):
            (dk0, dv0), dq0b, dqtb = carry
            at = j * block
            kb, vb = _slices((k0, v0), at, block)
            tile = functools.partial(_bd_tile, scale, diffusion, L, start, at)
            (dq_c,), (dk_c, dv_c) = _tile_grads(
                g, scale, tile(q0b, kb, "clean"), ls0b, dl0b, q0b, do0b, kb, vb
            )
            (dq_p,), (dk_p, dv_p) = _tile_grads(
                g, scale, tile(qtb, kb, "prefix"), lstb, dltb, qtb, dotb, kb, vb
            )
            # the x0 keys take both halves' parts
            dkb, dvb = _slices((dk0, dv0), at, block)
            return (
                jax.lax.dynamic_update_slice_in_dim(dk0, dkb + dk_c + dk_p, at, axis=1),
                jax.lax.dynamic_update_slice_in_dim(dv0, dvb + dv_c + dv_p, at, axis=1),
            ), dq0b + dq_c, dqtb + dq_p

        zeros = jnp.zeros(q0b.shape, f32)
        acc, dq0b, dqtb = jax.lax.fori_loop(0, i + 1, key_block, (acc, zeros, zeros))
        (dq_o,), (dktb, dvtb) = _tile_grads(
            g, scale, _bd_tile(scale, diffusion, L, start, start, qtb, ktb, "own"),
            lstb, dltb, qtb, dotb, ktb, vtb,
        )
        return acc, (dqtb + dq_o, dq0b, dktb, dvtb)

    (dk0, dv0), (dqt, dq0, dkt, dvt) = jax.lax.scan(
        one, (jnp.zeros(k0.shape, f32), jnp.zeros(v0.shape, f32)),
        (jnp.arange(padded // block), *(_split(x, block) for x in (
            qt, q0, dot, do0, dlt, dl0, lst, ls0, kt, vt))),
    )
    dq = jnp.concatenate(
        [_ungrouped(_join(x), H)[:, :L] for x in (dqt, dq0)], axis=1
    )
    dk = jnp.concatenate([_join(dkt)[:, :L], dk0[:, :L]], axis=1)
    dv = jnp.concatenate([_join(dvt)[:, :L], dv0[:, :L]], axis=1)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


_bd_attention.defvjp(_bd_fwd, _bd_bwd)


def block_diffusion_attention(q, k, v, *, diffusion_block: int, block: int,
                              scale: float):
    """Block-diffusion attention over ``[xt ; x0]``: ``q [B, 2L, H, D]``,
    ``k [B, 2L, Hkv, D]``, ``v [B, 2L, Hkv, Dv]``, the noisy copy's ``L``
    positions first, then the clean sequence's -> ``[B, 2L, H, Dv]``.
    ``diffusion_block``: tokens of a diffusion block (``b``), which divides
    ``block``, the queries and keys of a tile.  ``L`` need not be a whole
    number of either: the last diffusion block is then short."""
    B, S, H, _ = q.shape
    if S % 2 or k.shape[1] != S or v.shape[1] != S:
        raise ValueError(f"{S} positions are not two halves of one length")
    if k.shape[2] != v.shape[2] or H % k.shape[2]:
        raise ValueError(
            f"{H} query heads over {k.shape[2]} key and {v.shape[2]} value heads"
        )
    if diffusion_block < 1 or block % diffusion_block:
        raise ValueError(
            f"a diffusion block of {diffusion_block} does not divide {block}"
        )
    return _bd_attention(int(diffusion_block), int(block), float(scale), q, k, v)
