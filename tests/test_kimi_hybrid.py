"""The layer-pattern body (``models/kimi_linear.py``) where the system
takes it: ``parallel/tp.py``'s rules on a 1 x 4 mesh, a whole
``HybridLMTrainer`` step against the plain reference
(``models/kimi_linear_ref.py``), steps through the PS plane, the registered
app.

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


PERIOD = dict(n_layers=5, kda_layers=(1, 2, 3, 5), full_attn_layers=(4,),
              n_routed_experts=16, experts_held=4)


# -- sharding, trainer, app ------------------------------------------------------------
def test_a_1x4_mesh_gives_the_single_device_loss():
    from jax.sharding import PartitionSpec as P

    from parameter_server_tpu.parallel import mesh as mesh_lib
    from parameter_server_tpu.parallel.tp import (
        place_params, transformer_param_shardings,
    )

    cfg = km.tiny_config(**PERIOD, linear_num_heads=4, num_attention_heads=4)
    params, emb, tok = setup(cfg)
    mesh = mesh_lib.make_mesh((1, 4), devices=jax.devices()[:4])
    specs = transformer_param_shardings(params, mesh)
    moe, kda, mla = (specs["layer_1"]["moe"], specs["layer_0"]["kda"],
                     specs["layer_3"]["mla"])
    assert moe["experts"]["gate"].spec == P("model", None, None)
    assert moe["router"]["kernel"].spec == P()
    assert kda["conv_q"].spec == P(None, "model", None)
    assert kda["A_log"].spec == P("model")
    assert kda["f_b"]["kernel"].spec == P(None, "model", None)
    assert kda["f_a"]["kernel"].spec == P()
    assert mla["kv_b"]["kernel"].spec == P(None, "model", None)
    assert mla["kv_a"]["kernel"].spec == P()
    f = jax.jit(jax.value_and_grad(
        lambda p, e: km.loss_fn(cfg, p, e, tok, 16)[0], argnums=1
    ))
    loss, g = f(params, emb)
    loss4, g4 = f(place_params(params, mesh), emb)
    assert abs(float(loss) - float(loss4)) < 1e-5 and rel(g4, g) < 1e-4


def _trainer(**kw):
    from parameter_server_tpu.core.postoffice import Postoffice
    from parameter_server_tpu.core.van import LoopbackVan
    from parameter_server_tpu.kv.server import KVServer
    from parameter_server_tpu.kv.worker import KVWorker
    from parameter_server_tpu.learner import hybrid
    from parameter_server_tpu.parallel import mesh as mesh_lib

    cfg = km.tiny_config()  # the benchmark's dry-run size: K M, layer 1 dense
    van = LoopbackVan()
    tables = {"emb": hybrid.embedding_table_cfg(cfg)}
    servers = [
        KVServer(Postoffice(f"S{i}", van), tables, i, 2, device_replies=True)
        for i in range(2)
    ]
    worker = KVWorker(Postoffice("W0", van), tables, 2,
                      localizers=hybrid.embedding_localizers(cfg))
    tr = hybrid.HybridLMTrainer(
        cfg, mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1]), worker,
        max_delay=1, seed=5, loss_chunk=16, **kw,
    )
    return tr, servers, van


@pytest.fixture()
def trainer():
    tr, servers, van = _trainer()
    yield tr, servers
    van.close()


@pytest.mark.parametrize("warmup_steps,share", [(0, 1.0), (4, 0.25)])
def test_a_warm_up_starts_at_its_share_of_the_rate(warmup_steps, share):
    """AdamW's first step moves a parameter by the rate it takes (its first
    moments over the root of its second are the gradient's sign; the weight
    decay is 1e-4 of the parameter): ``learning_rate`` without a warm-up,
    ``learning_rate / warmup_steps`` with one, within a per cent."""
    tr, _servers, van = _trainer(learning_rate=1e-2, warmup_steps=warmup_steps)
    try:
        before = np.asarray(tr.params["lm_head"]["kernel"])
        tokens = np.random.default_rng(2).integers(0, 64, size=(2, 64)).astype(np.int32)
        tr.step(tokens)
        tr.drain()
        moved = np.abs(np.asarray(tr.params["lm_head"]["kernel"]) - before).max()
        assert moved == pytest.approx(1e-2 * share, rel=1e-2)
    finally:
        van.close()


def test_a_whole_trainer_step_is_the_reference_s(trainer):
    tr, _servers = trainer
    cfg = tr.cfg
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size, size=(2, 64)).astype(np.int32)
    rows = np.asarray(tr.worker.pull_sync("emb", tokens))
    emb = jnp.asarray(rows).reshape(2, 64, cfg.hidden_size)
    sz = ref.sizes_of(cfg)
    want, (gp_ref, ge_ref) = jax.jit(jax.value_and_grad(
        lambda p, e: ref.loss(sz, p, e, tokens), argnums=(0, 1)
    ))(tr.params, emb)
    before = jax.tree.map(np.asarray, tr.params)
    params, _opt, loss, g_emb, counters = tr._step(
        tr.params, tr.opt_state, emb, jnp.asarray(tokens)
    )
    tr.params, tr.opt_state = params, _opt
    assert abs(float(loss) - float(want)) < 1e-5
    assert rel(g_emb, ge_ref) < 1e-4
    assert set(counters) == set(km.COUNTERS)
    # named leaves, through the trainer's own loss_fn (what the benchmark's
    # driver compares on the chip)
    got = jax.jit(jax.grad(lambda p: tr.loss_fn(p, emb, tokens)[0]))(before)
    for path in (("layer_1", "moe", "router", "kernel"),
                 ("layer_1", "moe", "experts", "gate"),
                 ("layer_0", "kda", "A_log"), ("layer_0", "kda", "f_b", "kernel"),
                 ("layer_1", "mla", "kv_b", "kernel"), ("lm_head", "kernel")):
        a, b = got, gp_ref
        for k in path:
            a, b = a[k], b[k]
        assert rel(a, b) < 2e-4, path


def test_steps_train_through_the_ps_plane(trainer):
    tr, servers = trainer
    rng = np.random.default_rng(1)
    batches = [rng.integers(0, 64, size=(2, 64)).astype(np.int32) for _ in range(2)]
    losses = [
        tr.step(batches[i % 2], next_tokens=batches[(i + 1) % 2]) for i in range(6)
    ]
    tr.drain()
    assert losses[-1] < losses[0]
    assert tr.counters["moe_dropped_slots"] == 0
    assert tr.counters["moe_held_slots"] > 0
    assert sum(s.pushes for s in servers) == 6 * 2  # a push has a leg a server
    assert tr.n_active_params < tr.n_body_params  # experts: 6ND takes the active
    assert tr.dashboard.flops_per_example == 6.0 * tr.n_active_params * 64
    assert tr.logits(batches[0]).shape == (2, 64, tr.cfg.vocab_size)


def test_the_device_plane_compiles_a_bucket_not_a_leg_size(trainer):
    """Every batch splits its unique rows over the two shards at another
    place: the programs of the device-reply side (a push's legs taken from
    the combined plane, a pull's legs assembled) are one a power-of-two
    bucket, and pulled rows are still the servers' rows bit for bit."""
    from parameter_server_tpu.kv import worker as worker_mod

    tr, _servers = trainer
    rng = np.random.default_rng(2)
    tops = (9, 30, 70, 100, 129, 140, 170, 200, 230, 256)
    batches = [rng.integers(0, hi, size=(2, 64)).astype(np.int32) for hi in tops]
    take0 = worker_mod._take_rows._cache_size()
    asm0 = worker_mod._assemble_device._cache_size()
    legs = set()
    for i, b in enumerate(batches):
        legs.add((int((np.unique(b) < 128).sum()), int((np.unique(b) >= 128).sum())))
        tr.step(b, next_tokens=batches[(i + 1) % len(batches)])
    tr.drain()
    assert len(legs) == len(tops)  # ten leg-size pairs
    # legs of 8 .. 128 rows: five buckets a leg at most
    assert worker_mod._take_rows._cache_size() - take0 <= 5
    assert worker_mod._assemble_device._cache_size() - asm0 <= 6
    host = tr.worker.pull_sync("emb", batches[3])
    dev = tr.worker.pull_result_device(tr.worker.pull("emb", batches[3]))
    assert np.array_equal(np.asarray(dev), host)


def test_the_registered_app_runs_two_steps(tmp_path):
    from parameter_server_tpu import app as app_lib

    raw = {"app": "kimi_linear_hybrid", "steps": 2,
           "table": {"name": "emb", "rows": 256, "dim": 1,
                     "optimizer": {"kind": "adagrad"}},
           "data": {"kind": "synthetic", "key_space": 256, "nnz": 2,
                    "batch_size": 512, "seed": 0},
           "consistency": {"mode": "ssp", "max_delay": 1},
           "topology": {"num_servers": 2}}
    path = tmp_path / "cfg.json"
    path.write_text(json.dumps(raw))
    out = app_lib.create(app_lib.load_config(str(path)))()
    assert len(out["losses"]) == 2 and np.isfinite(out["losses"]).all()
    assert out["counters"]["moe_dropped_slots"] == 0
