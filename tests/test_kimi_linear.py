"""The layer-pattern body (``models/kimi_linear.py``) against its plain
reference (``models/kimi_linear_ref.py``) on seeded weights: layer by layer,
the held share of the experts, the whole loss and its gradients
(``tests/test_kimi_hybrid.py`` has the trainer, the sharding rules and the
app).

Tolerances (CPU: every product float32; the comparisons run the system at
the highest matrix precision too, so what is left is summation order): 2e-5
of the largest entry for outputs and the embedding gradient, 1e-4 for
parameter gradients, whose sums are longest."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_tpu.models import kimi_linear as km
from parameter_server_tpu.models import kimi_linear_ref as ref
from parameter_server_tpu.ops.blocked_attention import blocked_causal_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT, GRAD = 2e-5, 1e-4


def rel(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-30))


def worst_leaf(got, want):
    return max(rel(a, b) for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def setup(cfg, B=2, S=40, seed=0):
    params = km.init_params(cfg, jax.random.PRNGKey(seed))
    emb = 0.02 * jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, cfg.hidden_size))
    tok = jax.random.randint(jax.random.PRNGKey(seed + 2), (B, S), 0, cfg.vocab_size)
    return params, emb, tok


# -- mixers, one layer at a time ------------------------------------------------
@pytest.mark.parametrize("kind", ["kda", "mla"])
def test_a_mixer_is_its_reference(kind):
    cfg = km.tiny_config()
    params, emb, _tok = setup(cfg, S=37)  # no multiple of chunk or block
    layer = {"kda": 0, "mla": 1}[kind]
    p = params[f"layer_{layer}"][kind]
    sz = ref.sizes_of(cfg)
    with jax.default_matmul_precision("highest"):
        got = (
            km.kda_mixer(cfg, p, emb) if kind == "kda"
            else km.mla_mixer(cfg, p, emb)  # three blocks of 16 queries
        )
        want = jnp.stack([getattr(ref, kind)(sz, p, emb[b]) for b in range(2)])
    assert rel(got, want) < OUT


@pytest.mark.parametrize("shared", [False, True])
def test_blocked_attention_is_dense_attention(shared):
    B, S, H, D, Dv, Dr = 2, 45, 3, 8, 5, 4
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, Dv))
    qs = jax.random.normal(ks[3], (B, S, H, Dr)) if shared else None
    kshared = jax.random.normal(ks[4], (B, S, Dr)) if shared else None
    scale = 0.3

    def dense(q, k, v, qs, kshared):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k)
        if qs is not None:
            s = s + jnp.einsum("bqhd,bkd->bhqk", qs, kshared)
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s * scale, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    def blocked(q, k, v, qs, kshared):
        return blocked_causal_attention(
            q, k, v, block=16, scale=scale, q_shared=qs, k_shared=kshared
        )

    args = (q, k, v, qs, kshared)
    nums = (0, 1, 2, 3, 4) if shared else (0, 1, 2)
    with jax.default_matmul_precision("highest"):
        assert rel(blocked(*args), dense(*args)) < OUT
        got = jax.grad(lambda *a: jnp.sum(jnp.sin(blocked(*a))), argnums=nums)(*args)
        want = jax.grad(lambda *a: jnp.sum(jnp.sin(dense(*a))), argnums=nums)(*args)
    assert worst_leaf(got, want) < GRAD


# -- the held share ----------------------------------------------------------------
def test_four_shares_of_four_experts_add_up_to_the_uncut_layer():
    """16 routed experts, top 3: the parts that the four shares of 4 give,
    the shared expert counted once, are the uncut reference layer."""
    whole = km.tiny_config(n_routed_experts=16, experts_held=16,
                           num_experts_per_token=3)
    params, emb, _tok = setup(whole)
    p = params["layer_1"]["moe"]
    x = emb.reshape(-1, whole.hidden_size)
    with jax.default_matmul_precision("highest"):
        want = ref.experts(ref.sizes_of(whole), p, x)
        shared = ref.swiglu(
            *(p["shared"][n]["kernel"] for n in ("gate", "up", "down")), x
        )
        total = shared
        held = 0
        for first in range(0, 16, 4):
            share = km.tiny_config(
                n_routed_experts=16, experts_held=4, experts_first=first,
                num_experts_per_token=3,
            )
            mine = dict(p, experts={
                n: w[first:first + 4] for n, w in p["experts"].items()
            })
            y, counters = km.moe_layer(share, mine, emb)
            assert int(counters["moe_dropped_slots"]) == 0
            held += int(counters["moe_held_slots"])
            total = total + (y.reshape(x.shape) - shared)
            # the share itself is the reference's share
            part = ref.experts(ref.sizes_of(share), mine, x)
            assert rel(y.reshape(x.shape), part) < OUT
    assert held == x.shape[0] * 3  # every slot was some share's
    assert rel(total, want) < OUT


@pytest.mark.parametrize("rows,dropped", [(40, 0), (24, 4), (16, 12), (8, 13)])
def test_a_slot_that_finds_no_row_is_counted(rows, dropped):
    """The layout by hand: 3 held experts chosen by 9, 0 and 12 slots, blocks
    of 8 rows: expert 0 takes rows 0-15 (9 filled), expert 2 rows 16-31 (12
    filled).  ``moe_capacity`` gives every slot a row; a layout cut short
    counts what it left out."""
    group = jnp.asarray([0] * 9 + [2] * 12 + [3] * 4)[::-1]  # 3: none held
    slot, filled, block_expert, live, counters = km.dispatch_layout(
        group, 3, 8, rows
    )
    assert int(counters["moe_held_slots"]) == 21
    assert int(counters["moe_max_expert_slots"]) == 12
    assert int(counters["moe_dropped_slots"]) == dropped
    assert int(live) == min(rows // 8, 4)
    assert int(filled.sum()) == 21 - dropped
    # a filled row holds a slot of its block's expert, each slot once
    got = np.asarray(slot)[np.asarray(filled)]
    assert len(set(got.tolist())) == got.size
    assert (np.asarray(group)[got]
            == np.repeat(np.asarray(block_expert), 8)[np.asarray(filled)]).all()


def test_the_schedule_follows_from_the_shapes():
    """At the published widths and the cell's 2 x 8,192 tokens: one sequence
    at a time, KDA's 32 heads in 4 groups; a tiny body is not cut; one budget
    moves all of it (attention's tiles follow from its shapes: nothing to
    cut)."""
    pub = json.load(open(os.path.join(ROOT, "benchmarks/configs/kimi_linear_a3b.json")))
    cfg = km.KimiLinearConfig.from_published(
        pub, n_layers=5, experts_held=8, vocab_size=20480
    )
    assert km.schedule(cfg, 2, 8192) == (True, 4)
    assert km.schedule(cfg, 1, 8192) == (False, 4)
    assert km.schedule(cfg, 2, 1024) == (False, 1)
    tiny = km.tiny_config()
    assert km.schedule(tiny, 2, 64) == (False, 1)
    assert km.schedule(km.tiny_config(live_elems=1024), 2, 64) == (True, 2)
    assert km.moe_capacity(cfg, 16384) == 131072 + 4096


# -- the whole body ------------------------------------------------------------------
PERIOD = dict(n_layers=5, kda_layers=(1, 2, 3, 5), full_attn_layers=(4,),
              n_routed_experts=16, experts_held=4)


@pytest.mark.parametrize("how,B,live,cut", [
    # S 40, 2 heads of 16: an activation is 1,280 elements a sequence
    ("plain", 2, 1 << 23, (False, 1)), ("grouped", 1, 640, (False, 2)),
    ("by_sequence", 2, 1280, (True, 1)), ("both", 2, 640, (True, 2)),
])
def test_loss_and_gradients_are_the_reference_s(how, B, live, cut):
    cfg = km.tiny_config(**PERIOD, live_elems=live)
    params, emb, tok = setup(cfg, B=B)
    assert km.schedule(cfg, *emb.shape[:2]) == cut
    sz = ref.sizes_of(cfg)
    with jax.default_matmul_precision("highest"):
        (loss, counters), (gp, ge) = jax.jit(jax.value_and_grad(
            lambda p, e: km.loss_fn(cfg, p, e, tok, 16), argnums=(0, 1),
            has_aux=True,
        ))(params, emb)
    want, (gp_ref, ge_ref) = jax.jit(jax.value_and_grad(
        lambda p, e: ref.loss(sz, p, e, tok), argnums=(0, 1)
    ))(params, emb)
    assert abs(float(loss) - float(want)) < 1e-5
    assert int(counters["moe_dropped_slots"]) == 0
    assert rel(ge, ge_ref) < OUT
    assert worst_leaf(gp, gp_ref) < GRAD


def test_the_reference_s_blocks_change_nothing():
    cfg = km.tiny_config(**PERIOD)
    params, emb, tok = setup(cfg, S=32)
    plain = ref.sizes_of(cfg)
    blocked = ref.sizes_of(cfg, scan_block=8, q_block=8, vocab_block=8,
                           layer_remat=True)
    f = lambda sz: jax.jit(jax.value_and_grad(  # noqa: E731
        lambda e: ref.loss(sz, params, e, tok)
    ))(emb)
    (a, ga), (b, gb) = f(plain), f(blocked)
    assert abs(float(a) - float(b)) < 1e-6 and rel(gb, ga) < 1e-5


def test_the_two_copies_of_the_reference_agree():
    from benchmarks.reference import kimi_linear as bench_ref

    cfg = km.tiny_config(**PERIOD)
    params, emb, tok = setup(cfg, S=24)
    a = ref.loss(ref.sizes_of(cfg), params, emb, tok)
    b = bench_ref.loss(bench_ref.sizes_of(cfg), params, emb, tok)
    assert float(a) == float(b)
    here = open(os.path.join(ROOT, "parameter_server_tpu/models/kimi_linear_ref.py")).read()
    there = open(os.path.join(ROOT, "benchmarks/reference/kimi_linear.py")).read()
    assert here == there


def test_the_published_widths_hold_555_m_parameters():
    """Ties ISSUE 28's arithmetic to the code, without allocating."""
    pub = json.load(open(os.path.join(ROOT, "benchmarks/configs/kimi_linear_a3b.json")))
    cfg = km.KimiLinearConfig.from_published(
        pub, n_layers=pub["n_layers"], experts_held=pub["experts_held"],
        vocab_size=pub["vocab_rows"],
    )
    assert cfg.layer_kinds() == [
        ("kda", "dense"), ("kda", "experts"), ("kda", "experts"),
        ("mla", "experts"), ("kda", "experts"),
    ]
    shapes = jax.eval_shape(lambda: km.init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert abs(held - 555e6) < 0.01 * 555e6
    counts = km.count_params(cfg)
    assert counts["held"] == held
    assert abs(counts["active"] - 336e6) < 0.01 * 336e6
    one = lambda i, k: sum(  # noqa: E731
        int(np.prod(s.shape)) for s in jax.tree.leaves(shapes[f"layer_{i}"][k])
    )
    assert abs(one(0, "kda") - 39.5e6) < 0.1e6  # the KDA mixer
    assert abs(one(3, "mla") - 29.1e6) < 0.1e6  # the MLA mixer
    assert abs(one(0, "mlp") - 63.7e6) < 0.1e6  # the dense MLP
