"""Blocked attention under a window (``ops/blocked_attention.py``,
``window=``) against a dense masked softmax on seeded inputs: values and the
gradients of ``q``, ``k``, ``v`` (and a shared key part's), and how many key
blocks a block of queries is scored against, read from the jaxpr's shapes
and loop lengths, not from a timing.

Tolerances (CPU, every product float32 at the highest matrix precision, so
what is left is summation order): 2e-5 of the largest entry for outputs,
1e-4 for gradients, as the causal path's tests hold
(``tests/test_lfm2_moe.py``); a gradient that is zero throughout (a window
of one key: the softmax is 1 whatever the scores) is held to 1e-6 outright.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_tpu.ops.blocked_attention import blocked_causal_attention

OUT, GRAD = 2e-5, 1e-4
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SCALE = 0.3


def close(got, want, tol):
    scale = float(jnp.abs(want).max())
    if scale < 1e-12:  # zero throughout
        return float(jnp.abs(got).max()) < 1e-6
    return float(jnp.abs(got - want).max()) / scale < tol


def inputs(H, Hkv, S, shared, B=2, D=8, Dv=5, Dr=4):
    ks = jax.random.split(jax.random.PRNGKey(S + H), 5)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, Dv))
    if not shared:
        return q, k, v
    return (q, k, v, jax.random.normal(ks[3], (B, S, H, Dr)),
            jax.random.normal(ks[4], (B, S, Dr)))


def dense(window, q, k, v, qs=None, kshared=None):
    """Softmax over the keys ``t - window < s <= t``, ``k`` and ``v``
    repeated for every query head of their group."""
    S, H, Hkv = q.shape[1], q.shape[2], k.shape[2]
    k, v = (jnp.repeat(a, H // Hkv, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    if qs is not None:
        s = s + jnp.einsum("bqhd,bkd->bhqk", qs, kshared)
    t = jnp.arange(S)
    seen = (t[None, :] <= t[:, None]) & (t[:, None] - t[None, :] < window)
    s = jnp.where(seen, s * SCALE, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def blocked(window, block, q, k, v, qs=None, kshared=None):
    return blocked_causal_attention(
        q, k, v, block=block, scale=SCALE, q_shared=qs,
        k_shared=kshared, window=window,
    )


@pytest.mark.parametrize("H,Hkv,S,window,block,shared", [
    (8, 2, 48, 16, 16, False),  # grouped; S and window multiples of a block
    (8, 2, 45, 7, 16, False),  # neither a multiple; a window inside a block
    (4, 4, 45, 20, 16, False),  # ungrouped; a window of more than a block
    (6, 2, 45, 1, 16, False),  # a query sees itself alone
    (4, 1, 64, 33, 16, False),  # one key head for all; a window one over two blocks
    (8, 2, 45, 44, 16, False),  # one key short of every key
    (8, 2, 40, 17, 8, True),  # grouped queries and a shared key part
    (4, 4, 45, 7, 16, True),  # a shared key part, no groups
])
def test_a_window_is_a_dense_masked_softmax(H, Hkv, S, window, block, shared):
    args = inputs(H, Hkv, S, shared)
    nums = tuple(range(len(args)))
    def both(f):  # the value and, of a scalar of it, every argument's gradient
        return jax.jit(lambda *a: (
            f(*a), jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=nums)(*a)
        ))

    with jax.default_matmul_precision("highest"):
        out, got = both(functools.partial(blocked, window, block))(*args)
        ref, want = both(functools.partial(dense, window))(*args)
    assert close(out, ref, OUT)
    for name, a, b in zip(("dq", "dk", "dv", "dq_shared", "dk_shared"), got, want):
        assert close(a, b, GRAD), name


@pytest.mark.parametrize("window", [45, 46, 1000])
def test_a_window_of_every_key_is_the_causal_program(window):
    """``window >= S`` takes the causal path: the same result bit for bit,
    values and gradients, and the same jaxpr as no window at all."""
    args = inputs(8, 2, 45, True)
    f = lambda w: lambda *a: jnp.sum(jnp.sin(blocked(w, 16, *a)))  # noqa: E731
    assert (blocked(window, 16, *args) == blocked(None, 16, *args)).all()
    got = jax.grad(f(window), argnums=(0, 1, 2, 3, 4))(*args)
    want = jax.grad(f(None), argnums=(0, 1, 2, 3, 4))(*args)
    assert all((a == b).all() for a, b in zip(got, want))
    assert str(jax.make_jaxpr(jax.grad(f(window)))(*args)) == str(
        jax.make_jaxpr(jax.grad(f(None)))(*args)
    )


def _operations(jaxpr, depth=0, out=None):
    """A jaxpr as lines ``<primitive> <output shapes> <what shapes it>``, loop
    bodies indented: what the program computes, without the names a printed
    jaxpr gives its variables."""
    out = [] if out is None else out
    for e in jaxpr.eqns:
        shapes = ",".join("x".join(map(str, v.aval.shape)) or "-" for v in e.outvars)
        extra = "".join(
            f" {k}={e.params[k]}" for k in
            ("length", "dimension_numbers", "axes", "dimensions", "permutation")
            if k in e.params
        )
        out.append("  " * depth + f"{e.primitive.name} {shapes}{extra}")
        for sub in jax.core.jaxprs_in_params(e.params):
            _operations(sub, depth + 1, out)
    return out


#: the attention shapes of the cells that run the causal tiles: ``(H, Hkv, D,
#: Dv, Dr)``, and whether the body hands over empty shared slices
CAUSAL_CELLS = {
    # latent attention: 32 heads, no groups, a shared positional key part
    "kimi_linear_a3b": ((32, 32, 128, 128, 64), False),
    # grouped queries: 32 heads of 64 over 8 key heads, no shared part
    "lfm2_8b_a1b": ((32, 8, 64, 64, 0), False),
    # a full layer: 48 heads of 128 over 8, empty shared slices
    "laguna_xs2": ((48, 8, 128, 128, 0), True),
}


def causal_operations(H, Hkv, D, Dv, Dr, empty=False):
    """The operations of value and gradients without a window, one sequence
    of 8,192 tokens, blocks of 256; ``empty``: the shared part handed over
    as the empty slices ``q[..., :0]``, ``k[:, :, 0, :0]``."""
    S = 8192
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    args = [sds(1, S, H, D), sds(1, S, Hkv, D), sds(1, S, Hkv, Dv)]
    if Dr:
        args += [sds(1, S, H, Dr), sds(1, S, Dr)]

    def f(q, k, v, qs=None, ks=None):
        if empty:
            qs, ks = q[..., :0], k[:, :, 0, :0]
        return jnp.sum(blocked_causal_attention(
            q, k, v, block=256, scale=0.125, q_shared=qs, k_shared=ks,
        ))

    nums = tuple(range(len(args)))
    return _operations(jax.make_jaxpr(jax.value_and_grad(f, argnums=nums))(*args).jaxpr)


@pytest.mark.parametrize("cell,H,Hkv,D,Dv,Dr", [
    (cell, *shape) for cell, (shape, empty) in CAUSAL_CELLS.items() if not empty
])
def test_the_causal_program_of_a_cell_is_what_it_was(cell, H, Hkv, D, Dv, Dr):
    """Without a window, at the attention shapes of the two cells that had
    this kernel before windows existed, value and gradients trace to the
    operations of the causal tiles (``tests/data/blocked_causal_ops.<cell>.txt``,
    re-taken from the change that replaced the banded rows by tiles, by
    ``python tests/test_blocked_window.py``; a failure shows the lines that
    differ; laguna's list: ``tests/test_sdar_moe.py``).  A change to the
    causal path moves those cells: if it is meant, measure them and write
    the files again."""
    with open(os.path.join(DATA, f"blocked_causal_ops.{cell}.txt")) as f:
        want = f.read().splitlines()
    assert causal_operations(H, Hkv, D, Dv, Dr) == want


def test_a_window_of_no_key_is_refused():
    x = jnp.zeros((1, 4, 2, 8))
    with pytest.raises(ValueError, match="a window of 0 keys"):
        blocked_causal_attention(x, x, x, block=4, scale=1.0, window=0)


# -- how many keys a block of queries is scored against ---------------------------
def _scored(jaxpr, block, turns=1, out=None):
    """``[(loop turns, keys), ...]`` of every product in ``jaxpr`` that makes
    a block of scores (``block`` queries against ``keys`` keys, the head
    size summed over: the scores and, in the backward, ``dP``), loops'
    bodies counted by their lengths."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            shape = eqn.outvars[0].aval.shape
            (lc, rc), _ = eqn.params["dimension_numbers"]
            contracted = eqn.invars[0].aval.shape[lc[0]]
            # scores contract the head size (the weighted sums contract
            # keys or queries); whatever order the product leaves its axes
            # in, it holds HEADS x block x keys numbers
            if block in shape and contracted == HEAD:
                out.append((turns, int(np.prod(shape)) // (HEADS * block)))
        if name == "while":  # counted turn by turn by the scan around it
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scored(sub, block, turns * eqn.params.get("length", 1), out)
        if name == "scan":
            for i in range(eqn.params["length"]):
                for trips, body in _inner_loops(eqn.params["jaxpr"], i):
                    _scored(body, block, turns * trips, out)
    return out


def _inner_loops(closed, i):
    """``[(turns, body)]`` of the first ``while`` loop (a ``fori_loop`` with
    traced bounds) in a scan's body at the scan's turn ``i``, or ``[]``: the
    loop's bounds, read off the carry it starts from (``fori_loop``'s
    ``(lower, upper, ...)``), evaluated from the body's operations that lead
    to them with ``i`` for the body's index (its one integer scalar input)
    and zeros for its other inputs."""
    from jax.extend.core import Literal

    jaxpr = closed.jaxpr
    at = next(
        (n for n, e in enumerate(jaxpr.eqns) if e.primitive.name == "while"), None
    )
    if at is None:
        return []
    loop = jaxpr.eqns[at]
    first = loop.params["cond_nconsts"] + loop.params["body_nconsts"]
    bounds = loop.invars[first:first + 2]
    # the operations the bounds depend on, in order
    needed, leading = {v for v in bounds if not isinstance(v, Literal)}, []
    for eqn in reversed(jaxpr.eqns[:at]):
        if needed & set(eqn.outvars):
            leading.insert(0, eqn)
            needed |= {v for v in eqn.invars if not isinstance(v, Literal)}
    env = dict(zip(jaxpr.constvars, closed.consts))
    for var in jaxpr.invars:
        aval = var.aval
        env[var] = (
            np.int32(i) if aval.shape == () and aval.dtype == jnp.int32
            else np.zeros(aval.shape, aval.dtype)
        )
    read = lambda v: v.val if isinstance(v, Literal) else env[v]  # noqa: E731
    for eqn in leading:
        subfuns, params = eqn.primitive.get_bind_params(eqn.params)
        outs = eqn.primitive.bind(*subfuns, *map(read, eqn.invars), **params)
        env.update(zip(eqn.outvars, outs if eqn.primitive.multiple_results else [outs]))
    lower, upper = (int(read(v)) for v in bounds)
    return [(upper - lower, loop.params["body_jaxpr"].jaxpr)]


HEAD, HEADS = 128, 64  # a window layer's published heads, one sequence


@pytest.mark.parametrize("passes", ["forward", "backward"])
def test_a_block_of_queries_is_scored_against_three_key_blocks(passes):
    """At 8,192 tokens, blocks of 256 and a window of 512 (the published
    shapes of a window layer, one sequence): every block of queries is
    scored against 768 keys, 3 key blocks, in the forward and in the
    written-out backward (which recomputes the scores and forms ``dP``), and
    against the causal tiles' count that is under 0.19: 528 tiles of 256 a
    pass (32 x 33 / 2), read from the loops' bounds, where the banded rows
    before the tiles scored 640 blocks' worth."""
    S, block, window = 8192, 256, 512
    q = jax.ShapeDtypeStruct((1, S, HEADS, HEAD), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, S, 8, HEAD), jnp.float32)

    def attn(w):
        f = lambda q, k, v: blocked_causal_attention(  # noqa: E731
            q, k, v, block=block, scale=0.1, window=w
        )
        if passes == "forward":
            return f
        return jax.grad(lambda q, k, v: jnp.sum(f(q, k, v)), argnums=(0, 1, 2))

    per_pass = 1 if passes == "forward" else 3  # fwd; fwd again, scores, dP
    win = _scored(jax.make_jaxpr(attn(window))(q, kv, kv).jaxpr, block)
    assert win and {keys for _t, keys in win} == {3 * block}
    assert sum(t for t, _k in win) == per_pass * (S // block)
    causal = _scored(jax.make_jaxpr(attn(None))(q, kv, kv).jaxpr, block)
    blocks = lambda found: sum(t * keys for t, keys in found) // block  # noqa: E731
    # 32 query blocks x 3 key blocks against 1 + 2 + ... + 32 tiles
    assert {keys for _t, keys in causal} == {block}
    assert blocks(win) == per_pass * 96 and blocks(causal) == per_pass * 528
    assert blocks(win) / blocks(causal) < 0.19
    # no [S, S] tensor and nothing stacked over the blocks but their outputs
    biggest = max(
        int(np.prod(v.aval.shape))
        for eqn in _all_eqns(jax.make_jaxpr(attn(window))(q, kv, kv).jaxpr)
        for v in eqn.outvars if hasattr(v.aval, "shape")
    )
    assert biggest <= HEADS * S * HEAD  # the queries' own size


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


@pytest.mark.parametrize("passes", ["forward", "backward"])
def test_no_causal_intermediate_is_larger_than_the_queries(passes):
    """At a full layer's published shapes (one sequence of 8,192, 48 heads
    of 128 over 8, blocks of 256), forward and written-out backward: every
    block of scores is one ``[256, 256]`` tile, and no value the program
    forms is larger than the queries' own ``[S, 48, 128]`` (a block of
    queries scored against the whole prefix, ``[48, 256, 8,192]``, is 1.5
    times that; an ``[S, S]`` block 1,365 times)."""
    S, H, block = 8192, 48, 256
    q = jax.ShapeDtypeStruct((1, S, H, HEAD), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, S, 8, HEAD), jnp.float32)

    def attn(q, k, v):
        return blocked_causal_attention(q, k, v, block=block, scale=0.1)

    f = attn if passes == "forward" else jax.grad(
        lambda *a: jnp.sum(attn(*a)), argnums=(0, 1, 2)
    )
    jaxpr = jax.make_jaxpr(f)(q, kv, kv).jaxpr
    tiles = {  # the scores and dP, the head summed over: pairs a head
        int(np.prod(eqn.outvars[0].aval.shape)) // H for eqn in _all_eqns(jaxpr)
        if eqn.primitive.name == "dot_general" and eqn.invars[0].aval.shape[
            eqn.params["dimension_numbers"][0][0][0]
        ] == HEAD
    }
    assert tiles == {block * block}
    biggest = max(
        int(np.prod(v.aval.shape))
        for eqn in _all_eqns(jaxpr)
        for v in eqn.outvars if hasattr(v.aval, "shape")
    )
    assert biggest <= H * S * HEAD


if __name__ == "__main__":  # the causal lists, from the checkout on the path
    for cell, (shape, empty) in CAUSAL_CELLS.items():
        with open(os.path.join(DATA, f"blocked_causal_ops.{cell}.txt"), "w") as f:
            f.write("\n".join(causal_operations(*shape, empty=empty)) + "\n")
