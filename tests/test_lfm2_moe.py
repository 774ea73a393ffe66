"""The convolution-and-attention body (``models/lfm2_moe.py``) against its
plain reference (``models/lfm2_moe_ref.py``) on seeded weights: mixer by
mixer, grouped-query blocked attention against dense attention, the held
share of the bias-selected experts, the whole loss and its gradients
(``tests/test_lfm2_hybrid.py`` has the trainer, the sharding rules and the
app).

Tolerances (CPU: every product float32; the comparisons run the system at
the highest matrix precision too, so what is left is summation order): 2e-5
of the largest entry for outputs and the embedding gradient, 1e-4 for
parameter gradients, whose sums are longest; the loss, one number of order
5, to 1e-5."""

import json
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_tpu.models import kimi_linear as km
from parameter_server_tpu.models import lfm2_moe as lm
from parameter_server_tpu.models import lfm2_moe_ref as ref
from parameter_server_tpu.models import moe
from parameter_server_tpu.ops.blocked_attention import blocked_causal_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT, GRAD = 2e-5, 1e-4
PUBLISHED = os.path.join(ROOT, "benchmarks/configs/lfm2_8b_a1b.json")
#: the benchmark's cut in small: published layers 1-5 of a pattern with two
#: leading dense layers, a quarter of 16 experts
PERIOD = dict(
    layer_types=("conv", "conv", "full_attention", "conv", "conv", "conv"),
    n_layers=5, layers_first=1, num_dense_layers=2, n_routed_experts=16,
    experts_held=4,
)


def rel(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-30))


def worst_leaf(got, want):
    return max(rel(a, b) for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def with_bias(params, seed=7, scale=0.05):
    """``params`` with every ``expert_bias`` seeded non-zero: at the initial
    weights the sigmoid scores lie within a few per cent of each other, so a
    bias of this size changes most selections."""
    def put(path, x):
        if path[-1].key != "expert_bias":
            return x
        key = jax.random.fold_in(
            jax.random.PRNGKey(seed), zlib.crc32(str(path).encode()) % 1000
        )
        return scale * jax.random.normal(key, x.shape)
    return jax.tree_util.tree_map_with_path(put, params)


def setup(cfg, B=2, S=40, seed=0):
    params = with_bias(lm.init_params(cfg, jax.random.PRNGKey(seed)))
    emb = 0.02 * jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, cfg.hidden_size))
    tok = jax.random.randint(jax.random.PRNGKey(seed + 2), (B, S), 0, cfg.vocab_size)
    return params, emb, tok


# -- mixers, one layer at a time ------------------------------------------------
@pytest.mark.parametrize("kind", ["conv", "gqa"])
def test_a_mixer_is_its_reference(kind):
    cfg = lm.tiny_config()
    params, emb, _tok = setup(cfg, S=37)  # no multiple of a block
    p = params[{"conv": "layer_0", "gqa": "layer_1"}[kind]][kind]
    sz = ref.sizes_of(cfg)
    with jax.default_matmul_precision("highest"):
        got = (
            lm.conv_mixer(cfg, p, emb) if kind == "conv"
            else lm.gqa_mixer(cfg, 2, p, emb)  # two blocks a band
        )
        want = jnp.stack([getattr(ref, kind)(sz, p, emb[b]) for b in range(2)])
    assert rel(got, want) < OUT


@pytest.mark.parametrize("H,Hkv,shared", [
    (32, 8, False),  # the published heads
    (8, 2, True),  # groups and a shared key part together
    (4, 4, True),  # no groups: what it was
    (4, 1, False),  # one key head for all
])
def test_blocked_attention_with_fewer_key_heads_is_dense_attention(H, Hkv, shared):
    """Value, ``dq``, ``dk``, ``dv`` (and the shared part's) against dense
    attention with ``k`` and ``v`` repeated for every query head of their
    group: ``dk`` and ``dv`` are then summed over the group by the repeat's
    own derivative."""
    B, S, D, Dv, Dr = 2, 45, 8, 5, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, Hkv, D))
    v = jax.random.normal(ks[2], (B, S, Hkv, Dv))
    qs = jax.random.normal(ks[3], (B, S, H, Dr)) if shared else None
    kshared = jax.random.normal(ks[4], (B, S, Dr)) if shared else None
    scale = 0.3

    def dense(q, k, v, qs, kshared):
        k, v = (jnp.repeat(a, H // Hkv, axis=2) for a in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
        if qs is not None:
            s = s + jnp.einsum("bqhd,bkd->bhqk", qs, kshared)
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s * scale, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    def blocked(q, k, v, qs, kshared):
        return blocked_causal_attention(
            q, k, v, block=16, band=2, scale=scale, q_shared=qs, k_shared=kshared
        )

    args = (q, k, v, qs, kshared)
    nums = (0, 1, 2, 3, 4) if shared else (0, 1, 2)
    with jax.default_matmul_precision("highest"):
        assert rel(blocked(*args), dense(*args)) < OUT
        got = jax.grad(lambda *a: jnp.sum(jnp.sin(blocked(*a))), argnums=nums)(*args)
        want = jax.grad(lambda *a: jnp.sum(jnp.sin(dense(*a))), argnums=nums)(*args)
    assert worst_leaf(got, want) < GRAD


def test_heads_that_do_not_divide_are_refused():
    x = jnp.zeros((1, 4, 6, 8))
    with pytest.raises(ValueError, match="6 query heads over 4"):
        blocked_causal_attention(x, x[:, :, :4], x[:, :, :4], block=4, scale=1.0)


# -- the held share and the selection bias -------------------------------------------
def test_four_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """32 routed experts, top 4, a seeded non-zero selection bias: the parts
    that the four shares of 8 give are the uncut reference layer (there is
    no shared expert to count once)."""
    whole = lm.tiny_config(n_routed_experts=32, experts_held=32,
                           num_experts_per_token=4)
    params, emb, _tok = setup(whole)
    p = params["layer_1"]["moe"]
    assert float(jnp.abs(p["expert_bias"]).max()) > 0.01 and "shared" not in p
    x = emb.reshape(-1, whole.hidden_size)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(ref.sizes_of(whole), p, x)
        total, held = jnp.zeros_like(x), 0
        for first in range(0, 32, 8):
            share = lm.tiny_config(
                n_routed_experts=32, experts_held=8, experts_first=first,
                num_experts_per_token=4,
            )
            mine = dict(p, experts={
                n: w[first:first + 8] for n, w in p["experts"].items()
            })
            y, counters = moe.moe_layer(lm.expert_layer(share), mine, emb)
            assert int(counters["moe_dropped_slots"]) == 0
            held += int(counters["moe_held_slots"])
            total = total + y.reshape(x.shape)
            # the share itself is the reference's share
            part = ref.experts(ref.sizes_of(share), mine, x)
            assert rel(y.reshape(x.shape), part) < OUT
    assert held == x.shape[0] * 4  # every slot was some share's
    assert rel(total, want) < OUT


def test_a_bias_moves_the_selection_and_no_weight_s_formula():
    """``sel = top_k(s + b)``, ``w = s / sum_sel s``: under a bias that
    changes most tokens' selections every weight is still its own score over
    the selected scores' sum, and a zero bias is no bias, bit for bit."""
    layer = lm.expert_layer(lm.tiny_config(n_routed_experts=32,
                                           num_experts_per_token=4))
    kernel = 0.02 * jax.random.normal(jax.random.PRNGKey(1), (64, 32))
    x = jax.random.normal(jax.random.PRNGKey(2), (200, 64))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (32,))
    idx0, w0 = moe.route(layer, kernel, x)
    idx, w = moe.route(layer, kernel, x, bias)
    changed = np.asarray(jnp.sort(idx, -1) != jnp.sort(idx0, -1)).any(-1)
    assert changed.mean() > 0.5
    s = np.asarray(jax.nn.sigmoid(jnp.dot(x, kernel, precision=moe.HIGHEST)))
    # the selection is the biased scores' top 4 ...
    assert (np.sort(np.asarray(idx), -1)
            == np.sort(np.argsort(-(s + np.asarray(bias)), -1)[:, :4], -1)).all()
    # ... and the weights are the unbiased scores', renormalised
    picked = np.take_along_axis(s, np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(w), picked / picked.sum(-1, keepdims=True), rtol=1e-6
    )
    idx_z, w_z = moe.route(layer, kernel, x, jnp.zeros(32))
    assert (idx_z == idx0).all() and (w_z == w0).all()


def test_kimi_s_route_is_what_it_was():
    """The layer-pattern body passes no bias: its selection and weights are,
    bit for bit, the formula ``models/kimi_linear.py::route`` held before
    the expert layer moved to ``models/moe.py``."""
    cfg = km.tiny_config(n_routed_experts=16, num_experts_per_token=3)
    kernel = 0.02 * jax.random.normal(jax.random.PRNGKey(4), (cfg.hidden_size, 16))
    x = jax.random.normal(jax.random.PRNGKey(5), (300, cfg.hidden_size))
    s = jax.nn.sigmoid(jnp.dot(x, kernel, precision=jax.lax.Precision.HIGHEST))
    _top, idx = jax.lax.top_k(s, 3)
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * cfg.routed_scaling_factor
    got_idx, got_w = km.route(cfg, kernel, x)
    assert (got_idx == idx).all() and (got_w == w).all()
    assert "expert_bias" not in km.param_shapes(cfg)["layer_1"]["moe"]


# -- the whole body ------------------------------------------------------------------
@pytest.mark.parametrize("how,B,live,block,cut", [
    # S 40, hidden 64: the conv mixer's widest activation is 7,680 a sequence
    ("plain", 2, 1 << 26, 16, (False, 1)),
    ("by_sequence", 2, 7680, 16, (True, 1)),
    ("bands_of_two", 1, 1 << 26, 4, (False, 3)),
])
def test_loss_and_gradients_are_the_reference_s(how, B, live, block, cut):
    cfg = lm.tiny_config(**PERIOD, live_elems=live, attn_block=block)
    params, emb, tok = setup(cfg, B=B)
    assert lm.schedule(cfg, *emb.shape[:2]) == cut
    sz = ref.sizes_of(cfg)
    with jax.default_matmul_precision("highest"):
        (loss, counters), (gp, ge) = jax.jit(jax.value_and_grad(
            lambda p, e: lm.loss_fn(cfg, p, e, tok, 16), argnums=(0, 1),
            has_aux=True,
        ))(params, emb)
    want, (gp_ref, ge_ref) = jax.jit(jax.value_and_grad(
        lambda p, e: ref.loss(sz, p, e, tok), argnums=(0, 1)
    ))(params, emb)
    assert abs(float(loss) - float(want)) < 1e-5
    assert int(counters["moe_dropped_slots"]) == 0
    assert int(counters["moe_held_slots"]) > 0
    assert rel(ge, ge_ref) < OUT
    assert worst_leaf(gp, gp_ref) < GRAD
    # the selection bias takes no gradient
    assert not np.asarray(gp["layer_1"]["moe"]["expert_bias"]).any()


def test_the_reference_s_blocks_change_nothing():
    cfg = lm.tiny_config(**PERIOD)
    params, emb, tok = setup(cfg, S=32)
    plain = ref.sizes_of(cfg)
    blocked = ref.sizes_of(cfg, q_block=8, vocab_block=8, layer_remat=True)
    f = lambda sz: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda e: ref.loss(sz, params, e, tok)
    ))(emb)
    (a, ga), (b, gb) = f(plain), f(blocked)
    assert abs(float(a) - float(b)) < 1e-6 and rel(gb, ga) < 1e-5


def test_the_two_copies_of_the_reference_agree():
    from benchmarks.reference import lfm2_moe as bench_ref

    cfg = lm.tiny_config(**PERIOD)
    params, emb, tok = setup(cfg, S=24)
    a = ref.loss(ref.sizes_of(cfg), params, emb, tok)
    b = bench_ref.loss(bench_ref.sizes_of(cfg), params, emb, tok)
    assert float(a) == float(b)
    here = open(os.path.join(ROOT, "parameter_server_tpu/models/lfm2_moe_ref.py")).read()
    there = open(os.path.join(ROOT, "benchmarks/reference/lfm2_moe.py")).read()
    assert here == there


# -- the published shapes ---------------------------------------------------------------
def test_the_published_shapes_hold_8_34_b_whole_and_507_8_m_cut():
    """Ties ISSUE 33's arithmetic to the code, without allocating."""
    pub = json.load(open(PUBLISHED))
    whole = lm.Lfm2MoeConfig.from_published(pub)
    kinds = whole.layer_kinds()
    assert len(kinds) == 24 and sum(m == "gqa" for m, _ in kinds) == 6
    assert [mlp for _, mlp in kinds[:3]] == ["dense", "dense", "experts"]
    assert whole.head_dim == 64 and whole.buffers == ("expert_bias",)
    assert lm.count_params(whole)["held"] == 8_339_930_560  # 8.34 B
    cfg = lm.Lfm2MoeConfig.from_published(
        pub, n_layers=pub["n_layers"], layers_first=pub["layers_first"],
        experts_held=pub["experts_held"], vocab_size=pub["vocab_rows"],
    )
    assert cfg.layer_kinds() == [
        ("conv", "dense"), ("gqa", "experts"), ("conv", "experts"),
        ("conv", "experts"), ("conv", "experts"),
    ]
    shapes = jax.eval_shape(lambda: lm.init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    counts = lm.count_params(cfg)
    assert counts == {"held": held, "active": 199_538_944}
    assert held == 507_820_288  # 507.8 M
    one = lambda i, k: sum(  # noqa: E731
        int(np.prod(s.shape)) for s in jax.tree.leaves(shapes[f"layer_{i}"][k])
    )
    assert one(0, "conv") == 16_783_360 and one(1, "gqa") == 10_485_888
    assert one(0, "mlp") == 44_040_192
    assert one(1, "moe") == 8 * 11_010_048 + 65_568  # experts, router, bias


def test_the_schedule_follows_from_the_shapes():
    """At the published widths and the cell's 2 x 8,192 tokens: one sequence
    at a time, attention's 32 blocks in bands of 8; a tiny body is not cut."""
    pub = json.load(open(PUBLISHED))
    cfg = lm.Lfm2MoeConfig.from_published(
        pub, n_layers=5, layers_first=1, experts_held=8, vocab_size=16384
    )
    assert lm.schedule(cfg, 2, 8192) == (True, 8)
    assert lm.schedule(cfg, 1, 8192) == (False, 8)
    assert lm.schedule(cfg, 2, 1024) == (False, 1)
    assert lm.schedule(lm.tiny_config(), 2, 64) == (False, 1)
    assert moe.moe_capacity(lm.expert_layer(cfg), 16384) == 65536 + 4096


@pytest.mark.parametrize("key,value", [
    ("conv_bias", True), ("layer_types", ["conv", "sliding_attention"] * 12),
    ("num_hidden_layers", 23), ("model_type", "lfm2"),
])
def test_a_published_key_without_code_is_refused(key, value):
    pub = dict(json.load(open(PUBLISHED)), **{key: value})
    with pytest.raises(ValueError, match="no code for"):
        lm.Lfm2MoeConfig.from_published(pub)
