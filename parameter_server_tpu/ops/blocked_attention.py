"""Causal softmax attention blocked over queries, in plain XLA operations.

A block of ``block`` queries attends to the keys up to the last position of
its band (a few blocks that share one key prefix) and no further: the scores
of one block are ``[B, H, block, end]`` float32, and no ``[S, S]`` tensor of
all heads is ever live (at 8,192 tokens and 32 heads that tensor is 8.6 GB a
sequence).  The work is the causal half plus half a band's width of masked
scores a block.

**Grouped queries**: ``k`` and ``v`` may have fewer heads than ``q`` (``Hkv``
key-value heads, each serving the ``G = H / Hkv`` query heads ``g G .. g G +
G - 1``).  The queries are then read as ``[B, S, Hkv, G, D]`` and scored
against their group's one key head; ``k`` and ``v`` are never copied out per
query head, forward or backward (``dk`` and ``dv`` are summed over a group's
query heads by the product that forms them).  With ``Hkv == H`` there is no
group axis, and the program is the one it was before groups existed.

**A window** (``window``: a query sees itself and the ``window - 1`` keys
before it).  The keys a block of ``block`` queries can see are ``[start -
back, start + block)`` with ``back = ceil_to_block(window - 1)``: a slice
whose width is the same for every block, so a window layer is **one loop**
over all its blocks (``lax.scan``), each taking its slice of the keys at a
traced offset, the keys outside **not computed** (at 8,192 tokens, blocks of
256 and a window of 512: 3 key blocks a query block, 0.19 of the causal half
with its band overhead); the keys are padded in front by ``back`` zero rows
so that the first blocks' slices exist, and masked there.  The backward adds
a block's ``dk`` and ``dv`` into the same slice of one accumulator for the
sequence.  **No bands under a window**: a band is what a growing prefix
needed (one piece of code for blocks whose key ranges differ in length); a
window's slices are all one shape, and one loop's scores are live at a time
by the loop's own order.  ``window=None`` and ``window >= S`` are the causal
program above, as it was before windows existed.

A key may have a part all heads share (latent attention's positional part,
``k_shared [B, S, Dr]`` against ``q_shared [B, S, H, Dr]``): it is scored on
its own, so it is never copied out per head.

**The backward is written out** (a ``custom_vjp``, the blockwise recurrence
``ops/ring_attention.py`` uses): the forward keeps ``q, k, v``, the output
and the log-sum-exp of every query; the backward recomputes one block's
probabilities at a time and adds its part of ``dk`` and ``dv`` into one
accumulator a band, the band's into one for the sequence.  Differentiating
the blocked forward instead leaves one cotangent per block for every prefix
``k[:, :end]`` and ``v[:, :end]`` it sliced, 8 GB of them at 32 blocks of
8,192 tokens (found by compiling the step for the chip's memory, PR 28).

Scores, softmax and log-sum-exp are float32; the products run at jax's
default precision.
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


def _scores(start, scale, q, k, q_shared, k_shared, window=None, back=0):
    """Masked, scaled scores ``[B, Hkv, (G,) bq, end]`` of the queries
    ``start ..``.  Under a ``window`` the keys are the slice that starts
    ``back`` before the queries (before position 0 for the first blocks:
    masked), and a query sees the ``window`` keys that end with its own."""
    g = _g(q, k)
    s = jnp.einsum(
        f"bqh{g}d,bkhd->bh{g}qk", q, k, preferred_element_type=jnp.float32
    ) + jnp.einsum(
        f"bqh{g}d,bkd->bh{g}qk", q_shared, k_shared,
        preferred_element_type=jnp.float32,
    )
    q_ids = start + jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 0)
    k_ids = jax.lax.broadcasted_iota(jnp.int32, s.shape[-2:], 1)
    if window is None:
        seen = k_ids <= q_ids
    else:
        k_ids = k_ids + (start - back)
        seen = (k_ids <= q_ids) & (k_ids > q_ids - window) & (k_ids >= 0)
    return jnp.where(seen, s * scale, -jnp.inf)


def _after(x, done):
    """The arrays ``x``, not to be computed before ``done`` is: the bands share
    no data, and a scheduler free to run them side by side keeps every
    band's scores live at once (4 GB at 32 blocks, found by compiling for
    the chip's memory, PR 28)."""
    return x if done is None else jax.lax.optimization_barrier((x, done))[0]


def _bands(S, block, band):
    """``[(first query, queries, keys), ...]``: a band is ``band`` blocks of
    ``block`` queries that share one key prefix, the one its last query
    needs.  One loop (``lax.scan``) a band: a band is one piece of code
    whatever its blocks, where a block of its own length each was 96 pieces
    at 32 blocks (a 420 MB program that took 12 minutes to compile on the
    chip's host, PR 28); the price is the masked scores between a block's
    own prefix and its band's (a sixteenth more at 8 bands of 4)."""
    padded = -(-S // block) * block
    width = band * block
    return [(a, min(width, padded - a), min(a + width, S))
            for a in range(0, padded, width)]


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


def _per_query(x):
    """``[B, bq, Hkv, (G)]`` -> ``[B, Hkv, (G,) bq, 1]``, beside the scores."""
    return jnp.moveaxis(x, 1, -1)[..., None]


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _attention(block, band, scale, q, k, v, q_shared, k_shared):
    return _forward(block, band, scale, q, k, v, q_shared, k_shared)[0]


def _forward(block, band, scale, q, k, v, q_shared, k_shared):
    S, H = q.shape[1:3]
    padded = -(-S // block) * block
    q, q_shared = (
        _grouped(_pad_queries(x, padded), k.shape[2]) for x in (q, q_shared)
    )
    g = _g(q, k)
    outs, lses = [], []
    for a, n, end in _bands(S, block, band):
        qa, qsa = _after(
            (q[:, a:a + n], q_shared[:, a:a + n]), outs[-1] if outs else None
        )
        kb, vb, ksb = k[:, :end], v[:, :end], k_shared[:, :end]

        def one(_, x, a=a, kb=kb, vb=vb, ksb=ksb):
            i, qb, qsb = x
            s = _scores(a + i * block, scale, qb, kb, qsb, ksb)
            return None, _block_out(g, s, vb)

        _, (out, lse) = jax.lax.scan(
            one, None, (jnp.arange(n // block), _split(qa, block), _split(qsa, block))
        )
        outs.append(_ungrouped(_join(out), H))
        lses.append(jnp.moveaxis(
            _ungrouped(_join(jnp.moveaxis(lse, -1, 2)), H), 1, 2
        ))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
    lse = lses[0] if len(lses) == 1 else jnp.concatenate(lses, axis=2)
    return out[:, :S], lse[:, :, :S]


def _fwd(block, band, scale, q, k, v, q_shared, k_shared):
    out, lse = _forward(block, band, scale, q, k, v, q_shared, k_shared)
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


def _bwd(block, band, scale, res, d_out):
    _q, k, v, _q_shared, k_shared, out, _lse = res
    S = out.shape[1]
    q, q_shared, d_out, delta, lse = _padded_for_bwd(block, res, d_out)
    g = _g(q, k)
    dk, dv, dks = jnp.zeros_like(k), jnp.zeros_like(v), jnp.zeros_like(k_shared)
    dq, dqs = [], []
    for a, n, end in _bands(S, block, band):
        xs = _after(
            tuple(x[:, a:a + n] for x in (q, q_shared, d_out, delta, lse)),
            (dq[-1], dqs[-1], dk, dv, dks) if dq else None,
        )
        kb, vb, ksb = k[:, :end], v[:, :end], k_shared[:, :end]

        def one(carry, x, a=a, kb=kb, vb=vb, ksb=ksb):
            i, *xb = x
            s = _scores(a + i * block, scale, xb[0], kb, xb[1], ksb)
            return _block_grads(g, scale, s, carry, xb, (kb, vb, ksb))

        (dkb, dvb, dksb), (dqa, dqsa) = jax.lax.scan(
            one, (jnp.zeros_like(kb), jnp.zeros_like(vb), jnp.zeros_like(ksb)),
            (jnp.arange(n // block), *(_split(x, block) for x in xs)),
        )
        dk, dv, dks = (
            acc.at[:, :end].add(part)
            for acc, part in ((dk, dkb), (dv, dvb), (dks, dksb))
        )
        dq.append(_ungrouped(_join(dqa), out.shape[2]))
        dqs.append(_ungrouped(_join(dqsa), out.shape[2]))
    cat = lambda xs: (xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=1))[:, :S]  # noqa: E731
    return cat(dq), dk, dv, cat(dqs), dks


_attention.defvjp(_fwd, _bwd)


# -- under a window: one loop over all blocks, a slice of the keys each ----------
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


def _tied(x):
    """A block's index and its arrays, the index not to be had without the
    arrays.  Where a checkpointed layer runs one sequence at a time (a
    ``lax.map`` of a ``jax.checkpoint``: ``models/moe.py::by_sequence``),
    what depends on no input of the layer (the index, so the mask, so the
    mask broadcast to the scores' shape; the scores of a shared part of
    width 0) is split off into a loop of its own that runs in the forward
    pass and keeps its outputs stacked over all blocks for the backward
    pass: 1.9 GB a window layer at 64 heads and 8,192 tokens (found by
    compiling the step for the chip's memory, PR 35;
    ``tests/test_tpu_compile.py`` holds it).  Tied to the queries, they are
    computed where they are used.  The causal loops above are as they were,
    stacks and all (1.7 GB of masks at 48 heads): they are other cells'
    programs too."""
    return jax.lax.optimization_barrier(tuple(x))


def _slices(xs, start, width):
    return tuple(
        jax.lax.dynamic_slice_in_dim(x, start, width, axis=1) for x in xs
    )


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
    out = _ungrouped(_join(out), H)
    lse = jnp.moveaxis(_ungrouped(_join(jnp.moveaxis(lse, -1, 2)), H), 1, 2)
    return out[:, :S], lse[:, :, :S]


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
                             band: int = 4, q_shared=None, k_shared=None,
                             window=None):
    """``q [B, S, H, D]``, ``k [B, S, Hkv, D]``, ``v [B, S, Hkv, Dv]`` with
    ``H % Hkv == 0`` -> ``[B, S, H, Dv]`` float32.  ``band``: blocks of
    ``block`` queries that share a key prefix.  ``window``: a query sees
    itself and the ``window - 1`` keys before it (None, or at least ``S``:
    every key up to its own, the causal program with its bands).

    ``q_shared`` / ``k_shared``: the part all heads share, or None.  A caller
    without one whose layer is a ``jax.checkpoint`` run one sequence at a time
    (``models/moe.py::by_sequence``) hands over the empty slices
    ``q[..., :0]`` and ``k[:, :, 0, :0]`` instead of None: the zeros made
    below depend on no input of the layer, so the causal loops' scores of 0
    are computed in the forward pass for every block of a band and kept
    stacked for the backward pass (0.8 GB a band at 48 heads, :func:`_tied`;
    found by compiling the step for the chip's memory, PR 35).  The window's
    loop ties them itself; tying the causal loops here, so that no caller
    need know, moves three cells' programs and waits for its own measurement
    (``PERF.md`` section 7)."""
    B, S, H, _ = q.shape
    if k.shape[2] != v.shape[2] or H % k.shape[2]:
        raise ValueError(
            f"{H} query heads over {k.shape[2]} key and {v.shape[2]} value heads"
        )
    if window is not None and window < 1:
        raise ValueError(f"a window of {window} keys")
    if q_shared is None:  # a shared part of width 0 scores 0
        q_shared = jnp.zeros((B, S, H, 0), q.dtype)
        k_shared = jnp.zeros((B, S, 0), k.dtype)
    if window is None or window >= S:
        return _attention(block, band, float(scale), q, k, v, q_shared, k_shared)
    return _window_attention(
        block, int(window), float(scale), q, k, v, q_shared, k_shared
    )
