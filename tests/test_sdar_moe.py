"""The block-diffusion body (``models/sdar_moe.py``) against its plain
reference (``models/sdar_moe_ref.py``) on seeded weights, its attention
(``ops/blocked_attention.py::block_diffusion_attention``) against a dense
masked softmax, what no query may see, the held share of softmax-routed
experts, the published shapes, broken paths the comparison must refuse, and
the programs of the sibling bodies left as they were; then the registered
app, through the trainer and the PS plane (``benchmarks/tests/
test_sdar_cell.py``'s dry run takes the trainer's step at the cell's path).

Tolerances (CPU, every product float32 at the highest matrix precision, so
what is left is summation order): attention outputs 2e-5 and gradients 1e-4
of the largest entry (``tests/test_blocked_window.py``'s); the loss to 1e-5
and the embedding gradient and every parameter leaf's, each as a share of
its largest entry, to 2e-6 (a float32 sum's rounding over these sizes reads
1e-7 to 1e-6); a broken path reads 1e-3 or more."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_tpu.models import moe
from parameter_server_tpu.models import sdar_moe as sd
from parameter_server_tpu.models import sdar_moe_ref as ref
from parameter_server_tpu.ops import blocked_attention as ba

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(ROOT, "tests", "data")
PUBLISHED = os.path.join(ROOT, "benchmarks/configs/sdar_30b_a3b.json")
TOL = 2e-6


def rel(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-30))


def worst_leaf(got, want):
    return max(rel(a, b) for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


# -- the attention ---------------------------------------------------------------
def dense_bd(b, q, k, v, scale=0.3):
    """A softmax over the ``2L`` keys under the reference's ``[2L, 2L]``
    mask, ``k`` and ``v`` repeated for every query head of their group."""
    H, Hkv = q.shape[2], k.shape[2]
    k, v = (jnp.repeat(a, H // Hkv, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    s = jnp.where(ref.mask(q.shape[1] // 2, b), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def bd(b, block, q, k, v):
    return ba.block_diffusion_attention(
        q, k, v, diffusion_block=b, block=block, scale=0.3
    )


def qkv(H, Hkv, L, seed=0, B=2, D=8, Dv=5):
    ks = jax.random.split(jax.random.PRNGKey(seed + L), 3)
    return (jax.random.normal(ks[0], (B, 2 * L, H, D)),
            jax.random.normal(ks[1], (B, 2 * L, Hkv, D)),
            jax.random.normal(ks[2], (B, 2 * L, Hkv, Dv)))


@pytest.mark.parametrize("H,Hkv,L,b,block", [
    # grouped; the last block of queries holds diffusion blocks of padding alone
    (8, 2, 40, 4, 16),
    (4, 4, 45, 4, 16),  # ungrouped; the last block of queries ends mid diffusion block
    (6, 1, 42, 2, 8),  # one key head for all, blocks of 2
])
def test_block_diffusion_is_a_dense_masked_softmax(H, Hkv, L, b, block):
    args = qkv(H, Hkv, L)

    def both(f):  # the value and, of a scalar of it, every argument's gradient
        return jax.jit(lambda *a: (
            f(*a), jax.grad(lambda *a: jnp.sum(jnp.sin(f(*a))), argnums=(0, 1, 2))(*a)
        ))

    with jax.default_matmul_precision("highest"):
        out, got = both(lambda *a: bd(b, block, *a))(*args)
        want_out, want = both(lambda *a: dense_bd(b, *a))(*args)
    assert rel(out, want_out) < 2e-5
    for name, a, w in zip(("dq", "dk", "dv"), got, want):
        assert rel(a, w) < 1e-4, name


def test_no_query_sees_a_later_clean_block_or_another_noisy_block():
    """Changing ``x0``'s keys and values in blocks ``>= j``, and ``xt``'s
    outside block ``j``, leaves ``xt``'s outputs in block ``j`` as they were,
    bit for bit; changing ``x0`` in blocks ``> j`` leaves ``x0``'s block
    ``j``."""
    H, Hkv, L, b = 4, 2, 32, 4
    q, k, v = qkv(H, Hkv, L)
    f = jax.jit(lambda q, k, v: bd(b, 8, q, k, v))
    base = f(q, k, v)
    noise = jax.random.normal(jax.random.PRNGKey(9), k.shape)
    for j in (0, 3, 7):
        pos = jnp.arange(2 * L)
        later_clean = (pos >= L) & ((pos - L) // b >= j)
        other_noisy = (pos < L) & (pos // b != j)
        moved = (later_clean | other_noisy)[None, :, None, None]
        got = f(q, jnp.where(moved, k + noise, k), jnp.where(moved, v - noise[..., :1], v))
        rows = slice(j * b, (j + 1) * b)
        assert (got[:, rows] == base[:, rows]).all()
        # x0's block j under x0 blocks past j changed
        beyond = ((pos >= L) & ((pos - L) // b > j))[None, :, None, None]
        got = f(q, jnp.where(beyond, k + noise, k), v)
        rows = slice(L + j * b, L + (j + 1) * b)
        assert (got[:, rows] == base[:, rows]).all()


def test_the_keys_a_query_cannot_see_are_not_scored():
    """At 2 x 4,096 positions and blocks of 256 (the cell's), one sequence:
    every score product of the forward is one tile of 256 queries by 256
    keys; a loop over the 16 blocks of queries scores the ``xt`` block
    against its own ``xt`` keys, and an inner loop over the ``x0`` key
    blocks up to its own scores both halves' blocks against each: ``L^2 + 2
    L block`` pairs, 1.13 times the ``L^2 + L b`` needed, 0.56 of a causal
    pass over ``2L``, and nothing ``[2L, 2L]``."""
    L, block, H, Hkv, D = 4096, 256, 32, 4, 128
    q = jax.ShapeDtypeStruct((1, 2 * L, H, D), jnp.float32)
    kv = jax.ShapeDtypeStruct((1, 2 * L, Hkv, D), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, k, v: ba.block_diffusion_attention(
        q, k, v, diffusion_block=4, block=block, scale=0.1
    ))(q, kv, kv).jaxpr
    found, biggest = [], 0

    def walk(jaxpr, loops):
        nonlocal biggest
        for eqn in jaxpr.eqns:
            for out in eqn.outvars:
                biggest = max(biggest, int(np.prod(getattr(out.aval, "shape", ()))))
            if eqn.primitive.name == "dot_general":
                (lc, _rc), _ = eqn.params["dimension_numbers"]
                if eqn.invars[0].aval.shape[lc[0]] == D:  # scores contract the head
                    # whatever order the product leaves its axes in
                    found.append((loops, int(np.prod(eqn.outvars[0].aval.shape)) // H))
            name = eqn.primitive.name
            inner = loops + ((name, eqn.params.get("length")),) if name in ("scan", "while") else loops
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, inner)

    walk(jaxpr, ())
    nb = L // block
    assert {pairs for _l, pairs in found} == {block * block}
    assert sorted(loops for loops, _s in found) == [
        (("scan", nb),), (("scan", nb), ("while", None)), (("scan", nb), ("while", None)),
    ]
    # block i's inner loop runs over i + 1 key blocks
    pairs = block * block * (2 * nb * (nb + 1) // 2 + nb)
    assert pairs == L * L + 2 * L * block
    assert 1.0 < pairs / (L * L + L * 4) < 1.13
    assert pairs < 0.57 * (2 * L) * (2 * L + 1) / 2
    assert biggest < (2 * L) * (2 * L)


def test_a_diffusion_block_that_does_not_divide_a_block_of_queries_is_refused():
    q, k, v = jnp.zeros((1, 32, 4, 8)), jnp.zeros((1, 32, 2, 8)), jnp.zeros((1, 32, 2, 8))
    with pytest.raises(ValueError, match="does not divide"):
        ba.block_diffusion_attention(q, k, v, diffusion_block=3, block=8, scale=1.0)


# -- what the siblings run is what it was ---------------------------------------------
def _route_before(layer, router_kernel, xt, bias=None):
    """``models/moe.py::route`` as it was before the softmax router."""
    s = jax.nn.sigmoid(jnp.dot(xt, router_kernel, precision=moe.HIGHEST))
    _top, idx = jax.lax.top_k(s if bias is None else s + bias, layer.top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if layer.renormalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * layer.scale


@pytest.mark.parametrize("bias", [False, True])
def test_the_sigmoid_router_is_the_program_it_was(bias):
    layer = moe.ExpertLayer(root="r", n_routed=16, held=4, first=0, top_k=4,
                            scale=2.5, renormalize=True, block=8)
    args = (jnp.zeros((64, 16)), jnp.zeros((24, 64)))
    if bias:
        args = args + (jnp.zeros(16),)
    now = jax.make_jaxpr(lambda *a: moe.route(layer, *a))(*args)
    was = jax.make_jaxpr(lambda *a: _route_before(layer, *a))(*args)
    assert str(now) == str(was)


@pytest.mark.parametrize("kind,H,window", [("window", 64, 512), ("causal", 48, None)])
def test_laguna_s_attention_programs_are_what_they_were(kind, H, window):
    """A window layer's and a full layer's value and gradients at Laguna's
    published shapes (one sequence of 8,192, 8 key heads of 128, empty
    shared slices as the body passes them) trace to the stored operations
    (``tests/data``): the window's slice loop as at the parent of the
    block-diffusion loops, the full layer's causal tiles as re-taken from
    the change that replaced the banded rows by tiles (``python
    tests/test_blocked_window.py`` writes the three cells' causal lists)."""
    from test_blocked_window import _operations

    S = 8192
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731

    def f(q, k, v):
        return jnp.sum(ba.blocked_causal_attention(
            q, k, v, block=256, scale=0.125, window=window,
            q_shared=q[..., :0], k_shared=k[:, :, 0, :0],
        ))

    got = _operations(jax.make_jaxpr(jax.value_and_grad(f, argnums=(0, 1, 2)))(
        sds(1, S, H, 128), sds(1, S, 8, 128), sds(1, S, 8, 128)
    ).jaxpr)
    with open(os.path.join(DATA, f"blocked_{kind}_ops.laguna_xs2.txt")) as fh:
        assert got == fh.read().splitlines()


def block_diffusion_operations():
    """The operations of value and gradients of block-diffusion attention at
    the cell's attention shape: one sequence of 2 x 4,096 positions, 32
    query heads over 4 key heads of 128, diffusion blocks of 4, tiles of
    256."""
    from test_blocked_window import _operations

    L, H, Hkv, D = 4096, 32, 4, 128
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731

    def f(q, k, v):
        return jnp.sum(ba.block_diffusion_attention(
            q, k, v, diffusion_block=4, block=256, scale=D ** -0.5
        ))

    return _operations(jax.make_jaxpr(jax.value_and_grad(f, argnums=(0, 1, 2)))(
        sds(1, 2 * L, H, D), sds(1, 2 * L, Hkv, D), sds(1, 2 * L, Hkv, D)
    ).jaxpr)


def test_block_diffusion_s_program_is_what_it_was():
    """The block-diffusion loop shares its running-softmax helpers with the
    causal loop of the same module, and its program is held to the
    operations it traced to before they were shared
    (``tests/data/block_diffusion_ops.sdar_30b_a3b.txt``, written by
    ``python tests/test_sdar_moe.py`` from the checkout before the causal
    loop was tiled; a failure shows the lines that differ)."""
    with open(os.path.join(DATA, "block_diffusion_ops.sdar_30b_a3b.txt")) as fh:
        assert block_diffusion_operations() == fh.read().splitlines()


# -- the body against the reference ------------------------------------------------------
def batch(cfg, B=2, L=16, seed=0):
    rng = np.random.default_rng(seed)
    x0 = rng.integers(0, cfg.mask_id, size=(B, L))
    tok = sd.noise(x0, rng, cfg)
    emb = 0.02 * jax.random.normal(jax.random.PRNGKey(seed + 1), (B, 2 * L, cfg.hidden_size))
    return emb, jnp.asarray(tok)


def system(cfg, params, emb, tok):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p, e: sd.loss_fn(cfg, p, e, tok, 16), argnums=(0, 1), has_aux=True,
        ))(params, emb)


def reference(cfg, params, emb, tok):
    sz = ref.sizes_of(cfg)
    return jax.jit(jax.value_and_grad(
        lambda p, e: ref.loss(sz, p, e, tok), argnums=(0, 1)
    ))(params, emb)


CASES = {
    # blocks of 4, both sequences through a mixer at once
    "b4": dict(block_length=4),
    # blocks of 2, the mixers one sequence at a time (4 heads x 16 x 32
    # positions a sequence), the held share and a layer from the middle
    "b2_by_sequence_cut": dict(block_length=2, live_elems=2048, experts_first=4,
                               layers_first=1, n_layers=1),
}


@functools.lru_cache(maxsize=None)
def sound(case):
    """A tiny body's inputs, its step's loss and gradients and the
    reference's, computed once."""
    cfg = sd.tiny_config(**CASES[case])
    params = sd.init_params(cfg, jax.random.PRNGKey(0))
    emb, tok = batch(cfg)
    return cfg, (params, emb, tok), system(cfg, params, emb, tok), reference(cfg, params, emb, tok)


@pytest.mark.parametrize("case", sorted(CASES))
def test_loss_and_gradients_are_the_reference_s(case):
    cfg, (_params, emb, _tok), ((loss, counters), (gp, ge)), (want, (gp_ref, ge_ref)) = sound(case)
    assert sd.schedule(cfg, *emb.shape[:2]) == ("by_sequence" in case)
    assert abs(float(loss) - float(want)) < 1e-5
    assert int(counters["moe_dropped_slots"]) == 0 and int(counters["moe_held_slots"]) > 0
    assert rel(ge, ge_ref) < TOL and worst_leaf(gp, gp_ref) < TOL
    assert all(np.asarray(g).any() for g in jax.tree.leaves(gp))
    # the clean half takes gradient too: its keys are what xt reads
    assert float(jnp.abs(ge[:, 16:]).max()) > 0


@pytest.mark.parametrize("factor", [0.5, 3.0])
def test_a_static_capacity_changes_no_result_and_counts_its_blocks(factor):
    """Under the load (0.5) the loop runs the blocks that hold rows, over it
    (3.0) the capacity's: either way the loss, the gradients and the
    counters are the step's without one, bit for bit, and no slot drops."""
    cfg, (params, emb, tok), ((loss, counters), (gp, ge)), _want = sound("b4")
    capped = dataclasses.replace(cfg, moe_capacity_factor=factor)
    (loss_c, counters_c), (gp_c, ge_c) = system(capped, params, emb, tok)
    assert float(loss_c) == float(loss) and np.array_equal(ge_c, ge)
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(gp_c), jax.tree.leaves(gp)))
    assert "moe_blocks_run" not in counters
    run = int(counters_c.pop("moe_blocks_run"))
    assert {k: int(v) for k, v in counters_c.items()} == {k: int(v) for k, v in counters.items()}
    floor = cfg.n_layers * moe.capacity_blocks(sd.expert_layer(capped), tok.size)
    # 64 positions x 2 of 8 experts x 2 held: 32 expected slots, 4 blocks of 8
    assert floor == cfg.n_layers * int(np.ceil(factor * 4))
    # the blocks that hold rows: every held slot, each expert's last block padded
    live = int(counters["moe_held_slots"]) // cfg.moe_block
    assert run >= max(floor, live)
    if factor > 1:
        assert run == floor


def test_the_capacity_at_the_published_widths():
    """c = 4 at 2 x 8,192 body positions, top 8, 16 of 128 experts held,
    blocks of 1,024: 65,536 rows, 64 blocks a layer; a factor past the
    layout's room runs the layout's 144."""
    layer = moe.ExpertLayer(root="r", n_routed=128, held=16, first=0, top_k=8,
                            scale=1.0, renormalize=True, block=1024, capacity_factor=4)
    assert moe.capacity_blocks(layer, 16384) == 64
    assert moe.capacity_blocks(dataclasses.replace(layer, capacity_factor=100), 16384) == 144


def test_the_masked_positions_are_counted_and_weighted():
    """``bd_masked_positions`` is the count of mask ids in ``xt``; the loss
    weighs a masked position of block ``j`` by ``b / m_j`` over ``B L`` and
    reads nothing of ``x0``'s half of the hidden states or unmasked
    positions."""
    cfg, (_p, _emb, tok), ((_loss, counters), _g), _want = sound("b4")
    assert int(counters["bd_masked_positions"]) == int(jnp.sum(tok[:, :16] == cfg.mask_id))
    x0, w, masked = sd.diffusion_targets(cfg, tok)
    assert (x0 == tok[:, 16:]).all() and ((w > 0) == masked).all()
    m = masked.reshape(2, 4, 4).sum(-1)
    assert (m >= 1).all() and (m <= 4).all()
    assert np.allclose(np.asarray(w.reshape(2, 4, 4).sum(-1)), 4 / 32)


def test_the_noise_masks_one_to_b_positions_a_block():
    cfg = sd.tiny_config()
    rng = np.random.default_rng(3)
    x0 = rng.integers(0, cfg.mask_id, size=(64, 400))
    out = sd.noise(x0, rng, cfg)
    assert out.shape == (64, 800) and out.dtype == np.int32
    assert (out[:, 400:] == x0).all()
    m = (out[:, :400] == cfg.mask_id).reshape(64, 100, 4).sum(-1)
    assert set(np.unique(m)) == {1, 2, 3, 4}
    assert abs(m.mean() - 2.5) < 0.05  # E[m] = (b + 1) / 2
    kept = out[:, :400] != cfg.mask_id
    assert (out[:, :400][kept] == x0[kept]).all()
    with pytest.raises(ValueError, match="whole blocks"):
        sd.noise(x0[:, :6], rng, cfg)


def test_four_softmax_routed_shares_add_up_to_the_uncut_layer():
    """16 experts, top 4, softmax-routed: the parts the four shares of 4
    give add up to the uncut reference layer, and every slot was some
    share's."""
    whole = sd.tiny_config(n_routed_experts=16, experts_held=16, num_experts_per_token=4)
    params = sd.init_params(whole, jax.random.PRNGKey(0))
    p = params["layer_0"]["moe"]
    emb, _tok = batch(whole)
    x = emb.reshape(-1, whole.hidden_size) * 50.0

    @jax.jit
    def shares(p, x):  # each share's part, its counters and the reference's
        out = []
        for first in range(0, 16, 4):
            share = dataclasses.replace(whole, experts_held=4, experts_first=first)
            mine = dict(p, experts={n: w[first:first + 4] for n, w in p["experts"].items()})
            y, counters = moe.moe_layer(sd.expert_layer(share), mine, x[None])
            out.append((y[0], counters, ref.experts(ref.sizes_of(share), mine, x)))
        return out, ref.experts(ref.sizes_of(whole), p, x)

    with jax.default_matmul_precision("highest"):
        parts, want = shares(p, x)
    for _y, counters, _want in parts:
        assert int(counters["moe_dropped_slots"]) == 0
    assert sum(int(c["moe_held_slots"]) for _y, c, _w in parts) == x.shape[0] * 4
    assert max(rel(y, w) for y, _c, w in parts) < 2e-5
    assert rel(sum(y for y, _c, _w in parts), want) < 2e-5


@pytest.mark.parametrize("broken", ["xt_sees_its_own_clean_block", "next_token_shift",
                                    "sigmoid_router"])
def test_a_broken_path_fails_the_comparison(broken, monkeypatch):
    """The same comparison with one thing wrong in the system: ``xt``
    queries that also see their own block's ``x0`` keys, the loss read one
    position on (a next-token shift), a sigmoid router: each is off the
    reference by 1e-3 or more, five hundred times the limit."""
    cfg, (params, emb, tok), _got, (_want, (gp_ref, ge_ref)) = sound("b4")
    if broken == "xt_sees_its_own_clean_block":
        tile = ba._bd_tile  # the xt queries also see their own block's x0 keys
        monkeypatch.setattr(ba, "_bd_tile", lambda *a: tile(
            *a[:-1], "clean" if a[-1] == "prefix" else a[-1]
        ))
    elif broken == "next_token_shift":
        targets = sd.diffusion_targets
        monkeypatch.setattr(sd, "diffusion_targets", lambda cfg, t: (
            lambda x0, w, m: (jnp.roll(x0, -1, axis=1), w, m)
        )(*targets(cfg, t)))
    else:
        layer = sd.expert_layer
        monkeypatch.setattr(sd, "expert_layer", lambda cfg: dataclasses.replace(
            layer(cfg), scoring="sigmoid"
        ))
    (_loss, _c), (gp, ge) = system(cfg, params, emb, tok)
    assert rel(ge, ge_ref) > 1e-3 and worst_leaf(gp, gp_ref) > 1e-3


def test_the_two_copies_of_the_reference_agree():
    from benchmarks.reference import sdar_moe as bench_ref

    cfg = sd.tiny_config(n_layers=1)
    params = sd.init_params(cfg, jax.random.PRNGKey(0))
    emb, tok = batch(cfg, L=8)
    a = jax.jit(lambda p, e: ref.loss(ref.sizes_of(cfg), p, e, tok))(params, emb)
    b = jax.jit(lambda p, e: bench_ref.loss(bench_ref.sizes_of(cfg), p, e, tok))(params, emb)
    assert float(a) == float(b)
    here = open(os.path.join(ROOT, "parameter_server_tpu/models/sdar_moe_ref.py")).read()
    there = open(os.path.join(ROOT, "benchmarks/reference/sdar_moe.py")).read()
    assert here == there


def test_the_reference_s_blocks_change_nothing():
    cfg = sd.tiny_config(n_layers=1)
    params = sd.init_params(cfg, jax.random.PRNGKey(0))
    emb, tok = batch(cfg)
    f = lambda sz: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda e: ref.loss(sz, params, e, tok)
    ))(emb)
    (a, ga), (b, gb) = f(ref.sizes_of(cfg)), f(ref.sizes_of(
        cfg, q_block=8, vocab_block=8, layer_remat=True
    ))
    assert abs(float(a) - float(b)) < 1e-6 and rel(gb, ga) < 1e-5


# -- the published shapes ---------------------------------------------------------------
def test_the_published_shapes_hold_30_53_b_whole_and_606_73_m_cut():
    pub = json.load(open(PUBLISHED))
    whole = sd.SdarMoeConfig.from_published(pub)
    assert whole.layer_kinds() == [("bd", "experts")] * 48
    body = sd.count_params(whole)["held"]
    assert body + 151936 * 2048 == 30_532_122_624  # 30.53 B with one embedding
    cfg = sd.SdarMoeConfig.from_published(
        pub, n_layers=pub["n_layers"], layers_first=pub["layers_first"],
        experts_held=pub["experts_held"], vocab_size=pub["vocab_rows"],
    )
    shapes = jax.eval_shape(lambda: sd.init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert held == 606_727_680  # 606.73 M
    one = lambda k: sum(  # noqa: E731
        int(np.prod(s.shape)) for s in jax.tree.leaves(shapes["layer_0"][k])
    )
    assert one("attn") == 18_874_368 + 256
    assert one("moe") == 16 * 3 * 2048 * 768 + 2048 * 128
    assert sd.count_params(cfg) == {"held": held, "active": held - 6 * 16 * 4_718_592
                                    + 6 * int(16 * 4_718_592 * 8 / 128)}
    assert cfg.mask_id == 18991
    # both sequences at once; one at a time from a third
    assert sd.schedule(cfg, 2, 8192) is False and sd.schedule(cfg, 3, 8192) is True


@pytest.mark.parametrize("key,value", [
    ("use_sliding_window", True), ("mlp_only_layers", [0]),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}), ("attention_bias", True),
    ("tie_word_embeddings", True), ("decoder_sparse_step", 2),
    ("model_type", "qwen3_moe"), ("hidden_act", "gelu"),
])
def test_a_published_key_without_code_is_refused(key, value):
    pub = dict(json.load(open(PUBLISHED)), **{key: value})
    with pytest.raises(ValueError, match="no code for"):
        sd.SdarMoeConfig.from_published(pub)


# -- the registered app: the trainer, the PS plane ------------------------------------------
def test_the_registered_app_trains(tmp_path):
    """``sdar_hybrid`` through ``app.create``: the loss falls over the app's
    steps and no held slot is dropped."""
    from parameter_server_tpu import app as app_lib

    raw = {"app": "sdar_hybrid", "steps": 4,
           "table": {"name": "emb", "rows": 256, "dim": 1,
                     "optimizer": {"kind": "adagrad"}},
           "data": {"kind": "synthetic", "key_space": 64, "nnz": 2,
                    "batch_size": 512, "seed": 0},
           "consistency": {"mode": "ssp", "max_delay": 1},
           "topology": {"num_servers": 2}}
    path = tmp_path / "cfg.json"
    path.write_text(json.dumps(raw))
    out = app_lib.create(app_lib.load_config(str(path)))()
    assert len(out["losses"]) == 4 and np.isfinite(out["losses"]).all()
    assert out["losses"][-1] < out["losses"][0]
    assert set(out["counters"]) == set(moe.COUNTERS) | {"bd_masked_positions"}
    assert out["counters"]["moe_dropped_slots"] == 0
    assert out["counters"]["bd_masked_positions"] > 0


if __name__ == "__main__":  # the file of the program test above, from the checkout on the path
    with open(os.path.join(DATA, "block_diffusion_ops.sdar_30b_a3b.txt"), "w") as f:
        f.write("\n".join(block_diffusion_operations()) + "\n")
