"""The window-and-full-attention body (``models/laguna.py``) against its
plain reference (``models/laguna_ref.py``) on seeded weights: the two rotary
tables against a NumPy transcription of their formulas, the held share of
the experts with the shared expert counted once, the whole loss and its
gradients, and broken paths that the comparison must refuse
(``tests/test_laguna_hybrid.py`` has the trainer, the sharding rules and the
app; ``tests/test_blocked_window.py`` the attention under a window).

Tolerances (CPU: every product float32; the comparisons run the system at
the highest matrix precision too, so what is left is summation order): the
loss, one number of order 5, and the embedding gradient and every parameter
leaf's, each as a share of its largest entry, to 2e-6 (a float32 sum's
rounding over these sizes reads 2e-7 to 8e-7); a broken path reads 1e-3 or
more."""

import dataclasses
import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_tpu.models import laguna as lg
from parameter_server_tpu.models import laguna_ref as ref
from parameter_server_tpu.models import moe
from parameter_server_tpu.models import transformer as tfm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-6
PUBLISHED = os.path.join(ROOT, "benchmarks/configs/laguna_xs2.json")


def rel(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-30))


def worst_leaf(got, want):
    return max(rel(a, b) for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def setup(cfg, B=2, S=40, seed=0):
    params = lg.init_params(cfg, jax.random.PRNGKey(seed))
    emb = 0.02 * jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, cfg.hidden_size))
    tok = jax.random.randint(jax.random.PRNGKey(seed + 2), (B, S), 0, cfg.vocab_size)
    return params, emb, tok


def system(cfg, params, emb, tok):
    with jax.default_matmul_precision("highest"):
        (loss, counters), grads = jax.jit(jax.value_and_grad(
            lambda p, e: lg.loss_fn(cfg, p, e, tok, 16), argnums=(0, 1),
            has_aux=True,
        ))(params, emb)
    return loss, counters, grads


def reference(cfg, params, emb, tok):
    sz = ref.sizes_of(cfg)
    return jax.jit(jax.value_and_grad(
        lambda p, e: ref.loss(sz, p, e, tok), argnums=(0, 1)
    ))(params, emb)


@functools.lru_cache(maxsize=None)
def sound():
    """The default tiny body's inputs and its reference, computed once."""
    cfg = lg.tiny_config()
    args = setup(cfg)
    return cfg, args, reference(cfg, *args)


# -- rotary ------------------------------------------------------------------------
def yarn_by_hand(dim, base, factor, original, beta_fast, beta_slow):
    """ISSUE 35's formulas, transcribed: frequency ``j`` of ``dim / 2``."""
    out = []
    c = lambda r: dim * math.log(original / (2 * math.pi * r)) / (2 * math.log(base))  # noqa: E731
    low, high = max(math.floor(c(beta_fast)), 0), min(math.ceil(c(beta_slow)), dim - 1)
    for j in range(dim // 2):
        ext = base ** (-2 * j / dim)
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        out.append(ext / factor * ramp + ext * (1 - ramp))
    return np.array(out), low, high


def test_the_published_tables_are_the_formulas():
    pub = json.load(open(PUBLISHED))
    cfg = lg.LagunaConfig.from_published(pub)
    full, window = cfg.rotary_of("full"), cfg.rotary_of("window")
    want, low, high = yarn_by_hand(64, 500000.0, 64.0, 4096, 64.0, 1.0)
    assert (low, high) == (5, 16)
    assert full.dim(128) == 64 and full.amplitude == 1.4158883083359672
    assert abs(full.amplitude - (0.1 * math.log(64.0) + 1.0)) < 1e-12
    np.testing.assert_allclose(full.inv_freq(128), want, rtol=1e-6)
    # below ``low`` the table is the base's own, above ``high`` 64 times slower
    base = 500000.0 ** (-2 * np.arange(32) / 64)
    np.testing.assert_allclose(full.inv_freq(128)[:6], base[:6], rtol=1e-6)
    np.testing.assert_allclose(full.inv_freq(128)[16:], base[16:] / 64, rtol=1e-6)
    assert window.dim(128) == 128 and window.amplitude == 1.0
    np.testing.assert_allclose(
        window.inv_freq(128), 10000.0 ** (-2 * np.arange(64) / 128), rtol=1e-6
    )
    # the reference writes the same tables out on its own
    for mixer in ("full", "window"):
        rot = dataclasses.asdict(cfg.rotary_of(mixer))
        assert (ref.inv_freq(rot, 128) == cfg.rotary_of(mixer).inv_freq(128)).all()


@pytest.mark.parametrize("mixer", ["full", "window"])
def test_rotary_turns_its_share_of_the_head_and_passes_the_rest(mixer):
    """Against NumPy: position ``t`` turns the pair ``(x[i], x[i + dim/2])``
    by ``t inv_freq_i``, ``cos`` and ``sin`` times the amplitude; the
    dimensions past ``dim`` come out bit for bit as they went in."""
    pub = json.load(open(PUBLISHED))
    rot = lg.LagunaConfig.from_published(pub).rotary_of(mixer)
    B, S, H, K = 2, 37, 3, 128
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, K))
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    got = np.asarray(tfm._rotary(
        x, pos, rot.theta, halves=True, inv_freq=rot.inv_freq(K),
        rotary_dim=rot.dim(K), amplitude=rot.amplitude,
    ))
    xn, dim = np.asarray(x, np.float64), rot.dim(K)
    angle = np.arange(S)[:, None] * rot.inv_freq(K).astype(np.float64)
    cos = (np.cos(angle) * rot.amplitude)[None, :, None, :]
    sin = (np.sin(angle) * rot.amplitude)[None, :, None, :]
    x1, x2 = xn[..., : dim // 2], xn[..., dim // 2:dim]
    np.testing.assert_allclose(got[..., : dim // 2], x1 * cos - x2 * sin, atol=2e-5)
    np.testing.assert_allclose(got[..., dim // 2:dim], x2 * cos + x1 * sin, atol=2e-5)
    assert (got[..., dim:] == np.asarray(x)[..., dim:]).all()
    assert (dim < K) == (mixer == "full")
    # the reference's own rotary, one sequence
    want = ref.rotary(x[0], dataclasses.asdict(rot))
    assert rel(jnp.asarray(got[0]), want) < 1e-6


def test_rotary_with_its_old_arguments_is_what_it_was():
    """No table, share or amplitude given: the jaxpr ``_rotary`` built
    before it took them (written out here), for both conventions."""
    def before(x, positions, theta, halves=False):
        d = x.shape[-1]
        freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
        angles = positions[:, :, None].astype(jnp.float32) * freq
        cos = jnp.cos(angles)[:, :, None, :]
        sin = jnp.sin(angles)[:, :, None, :]
        if halves:
            x1, x2 = x[..., : d // 2], x[..., d // 2:]
        else:
            x1, x2 = x[..., 0::2], x[..., 1::2]
        out1 = x1 * cos - x2 * sin
        out2 = x2 * cos + x1 * sin
        if halves:
            out = jnp.concatenate([out1, out2], axis=-1)
        else:
            out = jnp.stack([out1, out2], axis=-1).reshape(x.shape)
        return out.astype(x.dtype)

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 16))
    pos = jnp.broadcast_to(jnp.arange(9), (2, 9))
    for halves in (False, True):
        now = jax.make_jaxpr(lambda x: tfm._rotary(x, pos, 1e4, halves))(x)
        was = jax.make_jaxpr(lambda x: before(x, pos, 1e4, halves))(x)
        assert str(now) == str(was)


# -- the held share ----------------------------------------------------------------
def test_eight_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """64 routed experts, top 8, a shared expert: the routed parts that the
    eight shares of 8 give, plus the shared expert counted once (every share
    computes it alike), are the uncut reference layer."""
    whole = lg.tiny_config(n_routed_experts=64, experts_held=64,
                           num_experts_per_token=8)
    params, emb, _tok = setup(whole)
    p = params["layer_1"]["moe"]
    x = emb.reshape(-1, whole.hidden_size)
    shared = lambda: ref.swiglu(  # noqa: E731
        *(p["shared"][n]["kernel"] for n in ("gate", "up", "down")), x
    )
    with jax.default_matmul_precision("highest"):
        want = ref.experts(ref.sizes_of(whole), p, x)
        total, held = jnp.zeros_like(x), 0
        for first in range(0, 64, 8):
            share = lg.tiny_config(
                n_routed_experts=64, experts_held=8, experts_first=first,
                num_experts_per_token=8,
            )
            mine = dict(p, experts={
                n: w[first:first + 8] for n, w in p["experts"].items()
            })
            y, counters = moe.moe_layer(lg.expert_layer(share), mine, emb)
            assert int(counters["moe_dropped_slots"]) == 0
            held += int(counters["moe_held_slots"])
            # the share itself is the reference's share, shared expert and all
            part = ref.experts(ref.sizes_of(share), mine, x)
            assert rel(y.reshape(x.shape), part) < 2e-5
            total = total + (y.reshape(x.shape) - shared())
        total = total + shared()
    assert held == x.shape[0] * 8  # every slot was some share's
    assert rel(total, want) < 2e-5


# -- the whole body ------------------------------------------------------------------
@pytest.mark.parametrize("how,B,live,block,cut", [
    # S 40: a window layer's widest activation is 6 x 16 x 40 = 3,840 a sequence
    ("plain", 2, 1 << 26, 8, (False, 2)),
    ("by_sequence", 2, 3840, 8, (True, 2)),
    ("one_band_one_block_windows", 1, 1 << 26, 16, (False, 1)),
])
def test_loss_and_gradients_are_the_reference_s(how, B, live, block, cut):
    cfg = lg.tiny_config(live_elems=live, attn_block=block)
    params, emb, tok = setup(cfg, B=B)
    assert lg.schedule(cfg, *emb.shape[:2]) == cut
    loss, counters, (gp, ge) = system(cfg, params, emb, tok)
    want, (gp_ref, ge_ref) = reference(cfg, params, emb, tok)
    assert abs(float(loss) - float(want)) < TOL * 5
    assert int(counters["moe_dropped_slots"]) == 0
    assert int(counters["moe_held_slots"]) > 0
    assert rel(ge, ge_ref) < TOL
    assert worst_leaf(gp, gp_ref) < TOL
    assert all(np.asarray(g).any() for g in jax.tree.leaves(gp))


def _swapped_tables(cfg):
    (a, ra), (b, rb) = cfg.rotary
    return dataclasses.replace(cfg, rotary=((a, rb), (b, ra)))


@pytest.mark.parametrize("broken", [
    "window_dropped", "gate_dropped", "tables_swapped", "a_key_head_too_far",
])
def test_a_broken_path_fails_the_comparison(broken, monkeypatch):
    """The same comparison, with one thing wrong in the system: a window
    layer that sees every key, an attention output left ungated, each layer
    kind turned by the other kind's rotary table, a group of query heads
    served by the next key head: each is off the reference by 1e-3 or more,
    five hundred times the limit the sound body keeps."""
    cfg, (params, emb, tok), (_want, (gp_ref, ge_ref)) = sound()
    run = cfg
    if broken == "window_dropped":
        run = dataclasses.replace(cfg, sliding_window=10 ** 6)
    elif broken == "gate_dropped":
        monkeypatch.setattr(lg, "_gated", lambda o, gate_in: o)
    elif broken == "tables_swapped":
        run = _swapped_tables(cfg)
    else:
        attend = lg.blocked_causal_attention
        monkeypatch.setattr(
            lg, "blocked_causal_attention",
            lambda q, k, v, **kw: attend(
                q, jnp.roll(k, 1, axis=2), jnp.roll(v, 1, axis=2), **kw
            ),
        )
    _loss, _c, (gp, ge) = system(run, params, emb, tok)
    assert rel(ge, ge_ref) > 1e-3 and worst_leaf(gp, gp_ref) > 1e-3


def test_swapped_head_counts_are_another_parameter_tree():
    """A layer's head count is its own kernels': the two kinds' counts
    swapped give ``q``, ``o`` and gate kernels of other shapes, so such a
    body cannot take the sound one's parameters, and the system reads a
    layer's heads from its kernels, never from one count for the body."""
    cfg = lg.tiny_config()
    swapped = dataclasses.replace(cfg, heads_per_layer=(6, 4, 4, 6))
    a, b = lg.param_shapes(cfg), lg.param_shapes(swapped)
    assert a["layer_0"]["attn"]["q"]["kernel"] == (64, 4, 16)
    assert a["layer_1"]["attn"]["q"]["kernel"] == (64, 6, 16)
    assert a["layer_1"]["attn"]["o_gate"]["kernel"] == (64, 6)
    assert b["layer_1"]["attn"]["q"]["kernel"] == (64, 4, 16)
    params, emb, tok = setup(cfg)
    # the sound parameters under the swapped config: the kernels decide
    loss, _c, _g = system(swapped, params, emb, tok)
    want, _g = reference(cfg, params, emb, tok)
    assert abs(float(loss) - float(want)) < TOL * 5


def test_the_reference_s_blocks_change_nothing():
    cfg = lg.tiny_config()
    params, emb, tok = setup(cfg, S=32)
    plain = ref.sizes_of(cfg)
    blocked = ref.sizes_of(cfg, q_block=8, vocab_block=8, layer_remat=True)
    f = lambda sz: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda e: ref.loss(sz, params, e, tok)
    ))(emb)
    (a, ga), (b, gb) = f(plain), f(blocked)
    assert abs(float(a) - float(b)) < 1e-6 and rel(gb, ga) < 1e-5


def test_the_two_copies_of_the_reference_agree():
    from benchmarks.reference import laguna as bench_ref

    cfg = lg.tiny_config()
    params, emb, tok = setup(cfg, S=24)
    a = ref.loss(ref.sizes_of(cfg), params, emb, tok)
    b = bench_ref.loss(bench_ref.sizes_of(cfg), params, emb, tok)
    assert float(a) == float(b)
    here = open(os.path.join(ROOT, "parameter_server_tpu/models/laguna_ref.py")).read()
    there = open(os.path.join(ROOT, "benchmarks/reference/laguna.py")).read()
    assert here == there


# -- the published shapes ---------------------------------------------------------------
def test_the_published_shapes_hold_33_4_b_whole_and_665_9_m_cut():
    """Ties the configuration file's arithmetic to the code, without
    allocating."""
    pub = json.load(open(PUBLISHED))
    whole = lg.LagunaConfig.from_published(pub)
    kinds = whole.layer_kinds()
    assert len(kinds) == 40 and sum(m == "full" for m, _ in kinds) == 10
    assert [mlp for _, mlp in kinds[:2]] == ["dense", "experts"]
    assert set(whole.layer_heads()) == {48, 64}
    assert all((m == "full") == (h == 48)
               for (m, _), h in zip(kinds, whole.layer_heads()))
    body = lg.count_params(whole)["held"]
    assert body == 33_237_075_968  # 33.24 B, and 33.44 B with the embedding
    assert body + 100352 * 2048 == 33_442_596_864
    cfg = lg.LagunaConfig.from_published(
        pub, n_layers=pub["n_layers"], layers_first=pub["layers_first"],
        experts_held=pub["experts_held"], vocab_size=pub["vocab_rows"],
    )
    assert cfg.layer_kinds() == [
        ("full", "dense"), ("window", "experts"), ("window", "experts"),
        ("window", "experts"), ("full", "experts"),
    ]
    assert cfg.layer_heads() == [48, 64, 64, 64, 48]
    shapes = jax.eval_shape(lambda: lg.init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert lg.count_params(cfg) == {"held": held, "active": 275_863_552}
    assert held == 665_933_824  # 665.93 M
    one = lambda i, k: sum(  # noqa: E731
        int(np.prod(s.shape)) for s in jax.tree.leaves(shapes[f"layer_{i}"][k])
    )
    assert one(0, "attn") == 29_458_432 and one(1, "attn") == 37_879_808
    assert one(0, "mlp") == 50_331_648
    assert one(1, "moe") == 32 * 3_145_728 + 3_145_728 + 524_288


def test_the_schedule_follows_from_the_shapes():
    """At the published widths and the cell's 2 x 8,192 tokens: one sequence
    at a time (a window layer's 64 heads of 128 are the widest), a full
    layer's 32 blocks in bands of 8; a tiny body is not cut."""
    pub = json.load(open(PUBLISHED))
    cfg = lg.LagunaConfig.from_published(
        pub, n_layers=5, experts_held=32, vocab_size=12544
    )
    assert lg.schedule(cfg, 2, 8192) == (True, 8)
    assert lg.schedule(cfg, 1, 8192) == (False, 8)
    assert lg.schedule(cfg, 2, 1024) == (False, 1)
    assert lg.schedule(lg.tiny_config(), 2, 64) == (False, 2)
    # 16,384 x 8 slots a layer laid out for the worst case in blocks of 512
    assert moe.moe_capacity(lg.expert_layer(cfg), 16384) == 131072 + 32 * 512


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("gating", "per-element"),
    ("layer_types", ["full_attention", "linear_attention"] * 20),
    ("mlp_layer_types", ["dense"] * 39), ("num_hidden_layers", 39),
    ("model_type", "laguna2"), ("tie_word_embeddings", True),
    ("moe_apply_router_weight_on_input", True),
    ("num_attention_heads_per_layer", [44] * 40),
])
def test_a_published_key_without_code_is_refused(key, value):
    pub = dict(json.load(open(PUBLISHED)), **{key: value})
    with pytest.raises(ValueError, match="no code for"):
        lg.LagunaConfig.from_published(pub)


def test_a_rope_type_without_code_is_refused():
    pub = json.load(open(PUBLISHED))
    pub["rope_parameters"] = dict(
        pub["rope_parameters"],
        sliding_attention=dict(pub["rope_parameters"]["sliding_attention"],
                               rope_type="llama3"),
    )
    with pytest.raises(ValueError, match="no code for"):
        lg.LagunaConfig.from_published(pub)


@pytest.mark.parametrize("key", [
    "factor", "original_max_position_embeddings", "beta_fast", "beta_slow",
    "attention_factor",
])
def test_a_yarn_table_without_one_of_its_keys_is_refused(key):
    """Nothing of YaRN's is defaulted: the published file gives every key."""
    pub = json.load(open(PUBLISHED))
    full = {k: v for k, v in pub["rope_parameters"]["full_attention"].items() if k != key}
    pub["rope_parameters"] = dict(pub["rope_parameters"], full_attention=full)
    with pytest.raises(ValueError, match=f"yarn table without.*{key}"):
        lg.LagunaConfig.from_published(pub)
