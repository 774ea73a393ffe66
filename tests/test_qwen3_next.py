"""The gated DeltaNet body (``models/qwen3_next.py``) against its plain
reference (``benchmarks/reference/qwen3_next.py``) on seeded weights; the chunked
scalar-decay delta rule (``ops/delta_rule.py::chunk_gated_delta``) against
its token-by-token form and against ``chunk_kda``; the held share of the
experts with the gated shared expert; the published shapes; broken paths
the comparison must refuse; the registered app; and the programs of the
sibling bodies left as they were.

Tolerances (CPU, every product float32 at the highest matrix precision, so
what is left is summation order): the scan's outputs 2e-5 and gradients
1e-4 of the largest entry (``tests/test_kimi_linear.py``'s); the body's
loss to 1e-5 and the embedding gradient and every parameter leaf's, each as
a share of its largest entry, to 2e-5; a broken path reads 1e-3 or more."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import qwen3_next as ref
from parameter_server_tpu.models import moe
from parameter_server_tpu.models import qwen3_next as qn
from parameter_server_tpu.ops import delta_rule

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(ROOT, "tests", "data")
PUBLISHED = os.path.join(ROOT, "benchmarks/configs/qwen3_next_80b_a3b.json")
TOL = 2e-5


def rel(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-30))


def worst_leaf(got, want):
    return max(rel(a, b) for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


# -- the scan ---------------------------------------------------------------------------
#: log-decay a step as a function of a uniform draw in [0, 1)
DECAYS = {
    "mid": lambda u: -u,
    "near1": lambda u: -1e-4 * u,  # a ~ 1: the state never forgets
    # a down to e^-10 a step: e^-640 inside a chunk of 64, where a factor
    # e^(-G) alone overflows float32
    "near0": lambda u: -8.0 * u - 2.0,
}


def draws(decay, B=2, S=150, Hk=2, Hv=4, K=16, V=8, seed=0):
    """Two value heads a key head, a length that is no multiple of a chunk."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, S, Hk, K))
    k = jax.random.normal(ks[1], (B, S, Hk, K))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / np.sqrt(K)
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, S, Hv, V))
    g = DECAYS[decay](jax.random.uniform(ks[3], (B, S, Hv)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, S, Hv)))
    return q, k, v, g, beta


def _loss(f, *a):
    o, s = f(*a)
    return jnp.sum(jnp.sin(o)) + jnp.sum(s * s)


def _chunked(*a):
    return delta_rule.chunk_gated_delta(*a, chunk=64)


def _kda(q, k, v, g, beta):
    """``chunk_kda`` fed the key heads repeated for their value heads and the
    decay broadcast over the channels."""
    r = v.shape[2] // q.shape[2]
    q, k = (jnp.repeat(a, r, axis=2) for a in (q, k))
    return delta_rule.chunk_kda(
        q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta, chunk=64
    )


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("against", ["recurrence", "chunk_kda"])
def test_the_chunked_scalar_decay_is_its_recurrence(decay, against):
    """Outputs, final state and the gradients of all five inputs: against the
    token-by-token recurrence to 1e-5 / 1e-4 of the largest entry, and
    against ``chunk_kda`` with the decay broadcast over ``d_k``."""
    args = draws(decay)
    want_f = delta_rule.recurrent_gated_delta if against == "recurrence" else _kda
    with jax.default_matmul_precision("highest"):
        o, s = jax.jit(_chunked)(*args)
        o_ref, s_ref = jax.jit(want_f)(*args)
        got = jax.jit(jax.grad(lambda *a: _loss(_chunked, *a), argnums=tuple(range(5))))(*args)
        want = jax.jit(jax.grad(lambda *a: _loss(want_f, *a), argnums=tuple(range(5))))(*args)
    assert bool(jnp.isfinite(o).all())
    assert rel(o, o_ref) < 1e-5 and rel(s, s_ref) < 1e-5
    for name, a, b in zip("qkvgb", got, want):
        assert bool(jnp.isfinite(a).all()), name
        assert rel(a, b) < 1e-4, (name, rel(a, b))


def test_a_value_head_reads_its_own_key_head():
    """Value head ``j`` reads key head ``j // r``: a key head's change moves
    its two value heads' outputs and no other's."""
    q, k, v, g, beta = draws("mid", S=40)
    o, _ = delta_rule.chunk_gated_delta(q, k, v, g, beta, chunk=16)
    o2, _ = delta_rule.chunk_gated_delta(q, k.at[:, :, 1].multiply(-1.0), v, g, beta,
                                         chunk=16)
    moved = jnp.abs(o2 - o).max(axis=(0, 1, 3))
    assert float(moved[:2].max()) == 0.0 and float(moved[2:].min()) > 1e-3
    with pytest.raises(ValueError, match="value heads over"):
        delta_rule.chunk_gated_delta(q, k, v[:, :, :3], g[..., :3], beta[..., :3])


# -- the body against the reference ------------------------------------------------------
def draw_params(cfg, seed=0):
    """Seeded weights as published, then the zero-centred norms' ``w`` drawn
    nonzero (``w`` against ``1 + w`` shows) and the decay rates drawn in
    ``[0.05, 0.5)`` (the published ``U(0, 16]`` forgets everything in a
    step at these sizes, so the decay's gradient would be a sum of terms of
    ``e^-20``)."""
    params = qn.init_params(cfg, jax.random.PRNGKey(seed))

    def draw(path, x):
        k = jax.random.fold_in(jax.random.PRNGKey(seed + 7), len(jax.tree_util.keystr(path)))
        if path[-1].key == "offset":
            return 0.5 * jax.random.normal(k, x.shape)
        if path[-1].key == "A_log":
            return jnp.log(jax.random.uniform(k, x.shape, minval=0.05, maxval=0.5))
        return x

    return jax.tree_util.tree_map_with_path(draw, params)


def batch(cfg, B=2, S=21, seed=0):
    emb = jax.random.normal(jax.random.PRNGKey(seed + 1), (B, S, cfg.hidden_size))
    tok = jax.random.randint(jax.random.PRNGKey(seed + 2), (B, S), 0, cfg.vocab_size)
    return emb, tok


def system(cfg, params, emb, tok):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p, e: qn.loss_fn(cfg, p, e, tok, 16), argnums=(0, 1), has_aux=True,
        ))(params, emb)


def reference(cfg, params, emb, tok):
    sz = ref.sizes_of(cfg)
    return jax.jit(jax.value_and_grad(
        lambda p, e: ref.loss(sz, p, e, tok), argnums=(0, 1)
    ))(params, emb)


CASES = {
    # one period G G G F, both sequences through a mixer at once
    "period": dict(),
    # the mixers one sequence at a time, two layers from the middle of a
    # longer stack (G F), the held share from the fifth expert on
    "by_sequence_cut": dict(live_elems=2048, num_hidden_layers=8, layers_first=2,
                            n_layers=2, experts_first=4),
}


@functools.lru_cache(maxsize=None)
def sound(case):
    """A tiny body's inputs, its step's loss and gradients and the
    reference's, computed once."""
    cfg = qn.tiny_config(**CASES[case])
    params = draw_params(cfg)
    emb, tok = batch(cfg)
    return (cfg, (params, emb, tok), system(cfg, params, emb, tok),
            reference(cfg, params, emb, tok))


@pytest.mark.parametrize("case", sorted(CASES))
def test_loss_and_gradients_are_the_reference_s(case):
    cfg, (_p, emb, _tok), ((loss, counters), (gp, ge)), (want, (gp_ref, ge_ref)) = sound(case)
    assert qn.schedule(cfg, *emb.shape[:2]) == ("by_sequence" in case)
    assert abs(float(loss) - float(want)) < 1e-5
    assert int(counters["moe_dropped_slots"]) == 0 and int(counters["moe_held_slots"]) > 0
    assert rel(ge, ge_ref) < TOL and worst_leaf(gp, gp_ref) < TOL
    assert all(np.asarray(g).any() for g in jax.tree.leaves(gp))


def test_the_layer_pattern_is_the_interval_s():
    cfg = qn.tiny_config(num_hidden_layers=8, n_layers=8)
    assert [m for m, _ in cfg.layer_kinds()] == ["gdn", "gdn", "gdn", "full"] * 2
    assert {mlp for _, mlp in cfg.layer_kinds()} == {"experts"}
    cut = dataclasses.replace(cfg, layers_first=2, n_layers=3)
    assert [m for m, _ in cut.layer_kinds()] == ["gdn", "full", "gdn"]
    with pytest.raises(ValueError):
        dataclasses.replace(cfg, layers_first=6, n_layers=3).layer_kinds()


def test_four_shares_and_the_gated_shared_expert_add_up_to_the_uncut_layer():
    """16 experts, top 4, softmax-routed, and a shared expert under its
    gate: the four shares of 4, the gated shared expert counted once, add up
    to the uncut reference layer, and every slot was some share's."""
    whole = qn.tiny_config(n_routed_experts=16, experts_held=16, num_experts_per_token=4)
    params = draw_params(whole)
    p = params["layer_0"]["moe"]
    emb, _tok = batch(whole)
    x = emb.reshape(-1, whole.hidden_size)

    @jax.jit
    def shares(p, x):  # each share's part and its counters; the reference's
        out = []
        for first in range(0, 16, 4):
            share = dataclasses.replace(whole, experts_held=4, experts_first=first)
            mine = dict(p, experts={n: w[first:first + 4] for n, w in p["experts"].items()})
            if first:  # the shared expert and its gate: the first share's
                mine = {k: v for k, v in mine.items() if not k.startswith("shared")}
            out.append(moe.moe_layer(qn.expert_layer(share), mine, x[None]))
        ungated = {k: v for k, v in p.items() if k != "shared_gate"}
        return (out, moe.moe_layer(qn.expert_layer(whole), ungated, x[None])[0],
                ref.experts(ref.sizes_of(whole), p, x))

    with jax.default_matmul_precision("highest"):
        parts, ungated, want = shares(p, x)
    assert all(int(c["moe_dropped_slots"]) == 0 for _y, c in parts)
    assert sum(int(c["moe_held_slots"]) for _y, c in parts) == x.shape[0] * 4
    assert rel(sum(y[0] for y, _c in parts), want) < 2e-5
    # the gate is on: without it the layer is another
    assert rel(ungated[0], want) > 1e-2


def _tiled_key_heads(q, k, v, g, beta, **kw):
    """Value head ``j`` reading key head ``j % Hk`` (the key heads tiled,
    not repeated)."""
    r = v.shape[2] // q.shape[2]
    return delta_rule.chunk_gated_delta(
        jnp.tile(q, (1, 1, r, 1)), jnp.tile(k, (1, 1, r, 1)), v, g, beta, **kw
    )


@pytest.mark.parametrize("broken", ["no_output_gate", "key_head_j", "no_convolution",
                                    "w_for_1_plus_w"])
def test_a_broken_path_fails_the_comparison(broken, monkeypatch):
    """The same comparison with one thing wrong in the system: attention's
    output not gated, value head ``j`` reading key head ``j`` (mod ``Hk``)
    where it reads ``j // 2``, the DeltaNet without its convolution, the
    zero-centred norms read as ``x^ w``: each is off the reference by 1e-3 or
    more, fifty times the limit."""
    cfg, (params, emb, tok), _got, (_want, (gp_ref, ge_ref)) = sound("by_sequence_cut")
    if broken == "no_output_gate":
        attn = qn.attn_mixer

        def ungated(cfg, p, x):  # the gate's sigmoid is the mixer's only one
            with monkeypatch.context() as m:
                m.setattr(jax.nn, "sigmoid", jnp.ones_like)
                return attn(cfg, p, x)
        monkeypatch.setattr(qn, "_MIXERS", dict(qn._MIXERS, full=ungated))
    elif broken == "key_head_j":
        monkeypatch.setattr(qn, "chunk_gated_delta", _tiled_key_heads)
    elif broken == "no_convolution":
        monkeypatch.setattr(moe, "causal_conv", lambda x, w: x)
    else:
        monkeypatch.setattr(moe, "norm", lambda p, x, eps: moe.rms_norm(
            x, p["offset"] if "offset" in p else p["scale"], eps
        ))
    (_loss, _c), (gp, ge) = system(cfg, params, emb, tok)
    assert rel(ge, ge_ref) > 1e-3 and worst_leaf(gp, gp_ref) > 1e-3


def test_the_reference_s_blocks_change_nothing():
    """The scan checkpointed in blocks of 7 steps, 7 queries a block of
    scores, 5 positions of logits and each layer rematerialised: the loss
    and the embedding gradient are the unblocked reference's."""
    cfg, (params, emb, tok), _got, (want, (_gp, ge_ref)) = sound("period")
    sz = ref.sizes_of(cfg, scan_block=7, q_block=7, vocab_block=5, layer_remat=True)
    with jax.default_matmul_precision("highest"):
        got, ge = jax.jit(jax.value_and_grad(lambda e: ref.loss(sz, params, e, tok)))(emb)
    assert abs(float(got) - float(want)) < 1e-5 and rel(ge, ge_ref) < 1e-5


# -- the published shapes ---------------------------------------------------------------
def test_the_published_shapes_hold_79_67_b_whole_and_586_77_m_cut():
    pub = json.load(open(PUBLISHED))
    whole = qn.Qwen3NextConfig.from_published(pub)
    assert [m for m, _ in whole.layer_kinds()] == ["gdn", "gdn", "gdn", "full"] * 12
    body = qn.count_params(whole)
    assert body["held"] + 151936 * 2048 == 79_674_391_296  # 79.67 B with one embedding
    assert abs(body["active"] - 3.56e9) < 0.01e9
    cfg = qn.Qwen3NextConfig.from_published(
        pub, n_layers=pub["n_layers"], layers_first=pub["layers_first"],
        experts_held=pub["experts_held"], vocab_size=pub["vocab_rows"],
    )
    shapes = jax.eval_shape(lambda: qn.init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert held == 586_771_520  # 586.77 M: 9.39 GB at 16 B a parameter

    def one(layer, k):
        return sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes[layer][k]))

    assert one("layer_0", "gdn") == 33_718_464
    assert one("layer_3", "full") == 27_263_488
    assert one("layer_0", "moe") == 1_048_576 + 32 * 3_145_728 + 3_145_728 + 2048
    assert shapes["layer_0"]["gdn"]["qkvz"]["kernel"].shape == (2048, 16, 768)
    assert shapes["layer_0"]["gdn"]["conv"].shape == (4, 16, 512)  # 8,192 channels
    assert shapes["layer_3"]["full"]["q"]["kernel"].shape == (2048, 16, 512)
    assert shapes["layer_0"]["moe"]["shared_gate"]["kernel"].shape == (2048, 1)
    assert qn.count_params(cfg)["active"] == 191_982_656
    # one sequence through a mixer at a time: 12,288 a position is over 2^26
    assert qn.schedule(cfg, 2, 8192) is True and qn.schedule(cfg, 1, 8192) is False


def test_the_published_initialisation():
    cfg = qn.tiny_config(linear_num_value_heads=64, linear_num_key_heads=32)
    p = qn.init_params(cfg, jax.random.PRNGKey(3))
    gdn, attn = p["layer_0"]["gdn"], p["layer_3"]["full"]
    rates = np.exp(np.asarray(gdn["A_log"]))
    assert (rates > 0).all() and (rates <= 16).all() and rates.std() > 2
    assert (np.asarray(gdn["dt_bias"]) == 1).all()
    assert (np.asarray(gdn["norm"]["scale"]) == 1).all()
    for leaf in (p["layer_0"]["mixer_norm"], p["final_norm"], attn["q_norm"]):
        assert set(leaf) == {"offset"} and not np.asarray(leaf["offset"]).any()
    assert abs(float(jnp.std(gdn["qkvz"]["kernel"])) - 0.02) < 0.002


@pytest.mark.parametrize("key,value", [
    ("use_sliding_window", True), ("mlp_only_layers", [0]),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}), ("attention_bias", True),
    ("tie_word_embeddings", True), ("decoder_sparse_step", 2),
    ("model_type", "qwen3_moe"), ("hidden_act", "gelu"),
    ("linear_num_value_heads", 24),
])
def test_a_published_key_without_code_is_refused(key, value):
    pub = dict(json.load(open(PUBLISHED)), **{key: value})
    with pytest.raises(ValueError, match="no code for"):
        qn.Qwen3NextConfig.from_published(pub)


# -- where the system takes it: the sharding rules, the registered app ---------------------
def test_a_1x4_mesh_gives_the_single_device_loss():
    from jax.sharding import PartitionSpec as P

    from parameter_server_tpu.parallel import mesh as mesh_lib
    from parameter_server_tpu.parallel.tp import place_params, transformer_param_shardings

    cfg = qn.tiny_config(linear_num_key_heads=4, linear_num_value_heads=8,
                         num_attention_heads=8, num_key_value_heads=4,
                         n_routed_experts=16, experts_held=4)
    params = draw_params(cfg)
    emb, tok = batch(cfg)
    mesh = mesh_lib.make_mesh((1, 4), devices=jax.devices()[:4])
    specs = transformer_param_shardings(params, mesh)
    gdn, attn = specs["layer_0"]["gdn"], specs["layer_3"]["full"]
    experts = specs["layer_0"]["moe"]
    # the key-head axis carries each key head's value heads whole
    assert gdn["qkvz"]["kernel"].spec == gdn["ba"]["kernel"].spec == P(None, "model", None)
    assert gdn["conv"].spec == P(None, "model", None)
    assert gdn["A_log"].spec == gdn["dt_bias"].spec == P("model")
    assert gdn["o"]["kernel"].spec == P("model", None, None)
    assert gdn["norm"]["scale"].spec == P()
    assert attn["q"]["kernel"].spec == P(None, "model", None)  # a head's query and gate
    assert attn["q_norm"]["offset"].spec == P()
    assert experts["shared_gate"]["kernel"].spec == P()
    assert experts["experts"]["gate"].spec == P("model", None, None)
    f = jax.jit(jax.value_and_grad(
        lambda p, e: qn.loss_fn(cfg, p, e, tok, 16)[0], argnums=1
    ))
    with jax.default_matmul_precision("highest"):
        loss, ge = f(params, emb)
        loss4, ge4 = f(place_params(params, mesh), emb)
    assert abs(float(loss) - float(loss4)) < 1e-5 and rel(ge4, ge) < 1e-4


def test_the_deltanet_mixer_splits_with_its_key_heads():
    """On a 1 x 4 mesh every operand of the convolution, the scan and the
    output norm is split where its key heads are (``qkvz``, ``ba``, the
    taps, ``A_log``, ``dt_bias``, ``o``): the mixer's value and gradients
    compile to the row-parallel all-reduces alone, with nothing moved
    between the shards."""
    import re

    from parameter_server_tpu.parallel import mesh as mesh_lib
    from parameter_server_tpu.parallel.tp import place_params

    cfg = qn.tiny_config(linear_num_key_heads=4, linear_num_value_heads=8)
    mesh = mesh_lib.make_mesh((1, 4), devices=jax.devices()[:4])
    gdn = qn.init_params(cfg, jax.random.PRNGKey(0))["layer_0"]["gdn"]
    p = place_params({"gdn": gdn}, mesh)["gdn"]
    x = jnp.ones((2, 32, cfg.hidden_size))
    f = jax.grad(lambda p, x: jnp.sum(jnp.sin(qn.gdn_mixer(cfg, p, x))), argnums=(0, 1))
    hlo = jax.jit(f).lower(p, x).compile().as_text()
    moved = re.findall(r"= \S+ ([a-z-]+)(?:-start)?\(", hlo)
    collectives = {op for op in moved if op.startswith(("all-", "collective-", "reduce-s"))}
    assert collectives == {"all-reduce"}


def test_the_registered_app_trains(tmp_path):
    """``qwen3_next_hybrid`` through ``app.create``: the loss falls over the
    app's steps and no held slot is dropped."""
    from parameter_server_tpu import app as app_lib

    raw = {"app": "qwen3_next_hybrid", "steps": 4,
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
    assert set(out["counters"]) == set(moe.COUNTERS)
    assert out["counters"]["moe_dropped_slots"] == 0


# -- what the siblings run is what it was ---------------------------------------------
def kda_scan_operations():
    """The operations of value and gradients of kimi's KDA scan as its
    mixer runs it: one sequence of 8,192 tokens, a group of 8 heads of 128
    (``models/kimi_linear.py::schedule``: 4 groups), chunks of 64, under a
    checkpoint."""
    from test_blocked_window import _operations

    S, H, K = 8192, 8, 128
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731

    @jax.checkpoint
    def f(q, k, v, g, beta):
        return jnp.sum(delta_rule.chunk_kda(q, k, v, g, beta, chunk=64)[0])

    args = (sds(1, S, H, K),) * 4 + (sds(1, S, H),)
    return _operations(jax.make_jaxpr(
        jax.value_and_grad(f, argnums=tuple(range(5)))
    )(*args).jaxpr)


def expert_layer_operations(shared: bool):
    """The operations of value and gradients of the expert layer at a small
    shape, with an ungated shared expert (kimi's and laguna's) or none
    (lfm2's and sdar's)."""
    from test_blocked_window import _operations

    D, F, E = 32, 16, 8
    layer = moe.ExpertLayer(root="r", n_routed=E, held=2, first=0, top_k=2,
                            scale=2.5, renormalize=True, block=8)
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    p = {"router": {"kernel": sds(D, E)},
         "experts": {k: v["kernel"] for k, v in
                     moe.swiglu_shapes(D, F, (2,)).items()}}
    p["experts"] = {k: sds(*s) for k, s in p["experts"].items()}
    if shared:
        p["shared"] = {k: {"kernel": sds(*v["kernel"])}
                       for k, v in moe.swiglu_shapes(D, F).items()}

    def f(p, x):
        return jnp.sum(moe.moe_layer(layer, p, x)[0])

    return _operations(jax.make_jaxpr(jax.value_and_grad(f, argnums=(0, 1)))(
        p, sds(2, 16, D)
    ).jaxpr)


#: ``tests/data`` files of the siblings' programs, each from its function
SIBLING_PROGRAMS = {
    "kda_scan_ops.kimi_linear_a3b.txt": kda_scan_operations,
    "expert_layer_ops.shared.txt": lambda: expert_layer_operations(True),
    "expert_layer_ops.unshared.txt": lambda: expert_layer_operations(False),
}


@pytest.mark.parametrize("name", sorted(SIBLING_PROGRAMS))
def test_a_sibling_s_program_is_what_it_was(name):
    """Kimi's KDA scan (value and gradients at its mixer's shapes) and the
    expert layer without a shared gate, with a shared expert (kimi's,
    laguna's) or none (lfm2's, sdar's), trace to the operations stored
    before the scalar-decay scan and the gated shared expert were added
    (``python tests/test_qwen3_next.py`` writes them from the checkout on
    the path; a failure shows the lines that differ).  A change to these
    paths moves the siblings' cells: if it is meant, measure them and write
    the files again."""
    with open(os.path.join(DATA, name)) as f:
        assert SIBLING_PROGRAMS[name]() == f.read().splitlines()


if __name__ == "__main__":  # the siblings' lists, from the checkout on the path
    for name, operations in SIBLING_PROGRAMS.items():
        with open(os.path.join(DATA, name), "w") as f:
            f.write("\n".join(operations()) + "\n")
