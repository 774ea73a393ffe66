"""The window-and-full-attention body (``models/laguna.py``) where the
system takes it: ``parallel/tp.py``'s rules on a 1 x 4 mesh (layers of 8 and
12 heads over 4 key heads), a whole ``HybridLMTrainer`` step against the
plain reference (``models/laguna_ref.py``), steps through the PS plane, the
registered app.

Tolerances (CPU: every product float32, so what is left is summation
order): 1e-5 for the loss, 1e-4 of the largest entry for gradients, 2e-4
for the named leaves through the trainer's own ``loss_fn``."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parameter_server_tpu.models import laguna as lg
from parameter_server_tpu.models import laguna_ref as ref
from parameter_server_tpu.models import moe


def rel(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-30))


def test_a_1x4_mesh_gives_the_single_device_loss():
    from jax.sharding import PartitionSpec as P

    from parameter_server_tpu.parallel import mesh as mesh_lib
    from parameter_server_tpu.parallel.tp import (
        place_params, transformer_param_shardings,
    )

    cfg = lg.tiny_config(n_routed_experts=16, experts_held=4,
                         heads_per_layer=(8, 12, 12, 8),
                         num_key_value_heads=4, head_dim=8)
    params = lg.init_params(cfg, jax.random.PRNGKey(0))
    emb = 0.02 * jax.random.normal(jax.random.PRNGKey(1), (2, 40, cfg.hidden_size))
    tok = jax.random.randint(jax.random.PRNGKey(2), (2, 40), 0, cfg.vocab_size)
    mesh = mesh_lib.make_mesh((1, 4), devices=jax.devices()[:4])
    specs = transformer_param_shardings(params, mesh)
    for layer, heads in (("layer_0", 8), ("layer_1", 12)):
        attn = specs[layer]["attn"]
        assert params[layer]["attn"]["q"]["kernel"].shape == (64, heads, 8)
        assert attn["q"]["kernel"].spec == attn["k"]["kernel"].spec == P(
            None, "model", None
        )
        # the gate's kernel [D, H]: over the heads, beside q
        assert params[layer]["attn"]["o_gate"]["kernel"].shape == (64, heads)
        assert attn["o_gate"]["kernel"].spec == P(None, "model")
        assert attn["o"]["kernel"].spec == P("model", None, None)
    experts = specs["layer_1"]["moe"]
    assert experts["experts"]["gate"].spec == P("model", None, None)
    assert experts["router"]["kernel"].spec == P()
    assert experts["shared"]["gate"]["kernel"].spec == P(None, "model")
    assert experts["shared"]["down"]["kernel"].spec == P("model", None)
    f = jax.jit(jax.value_and_grad(
        lambda p, e: lg.loss_fn(cfg, p, e, tok, 16)[0], argnums=1
    ))
    loss, g = f(params, emb)
    loss4, g4 = f(place_params(params, mesh), emb)
    assert abs(float(loss) - float(loss4)) < 1e-5 and rel(g4, g) < 1e-4


@pytest.fixture()
def trainer():
    from parameter_server_tpu.core.postoffice import Postoffice
    from parameter_server_tpu.core.van import LoopbackVan
    from parameter_server_tpu.kv.server import KVServer
    from parameter_server_tpu.kv.worker import KVWorker
    from parameter_server_tpu.learner import hybrid
    from parameter_server_tpu.parallel import mesh as mesh_lib

    cfg = lg.tiny_config()  # the benchmark's dry-run size
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
        max_delay=1, seed=5, loss_chunk=16,
    )
    yield tr, servers
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
    tr.params, tr.opt_state, loss, g_emb, counters = tr._step(
        tr.params, tr.opt_state, emb, jnp.asarray(tokens)
    )
    assert abs(float(loss) - float(want)) < 1e-5
    assert rel(g_emb, ge_ref) < 1e-4
    assert set(counters) == set(moe.COUNTERS)
    # named leaves, through the trainer's own loss_fn (what the benchmark's
    # driver compares on the chip): one of each kind this body adds
    got = jax.jit(jax.grad(lambda p: tr.loss_fn(p, emb, tokens)[0]))(before)
    for path in (("layer_1", "attn", "q", "kernel"),
                 ("layer_3", "attn", "k", "kernel"),
                 ("layer_0", "attn", "o_gate", "kernel"),
                 ("layer_1", "attn", "o_gate", "kernel"),
                 ("layer_2", "moe", "router", "kernel"),
                 ("layer_2", "moe", "experts", "gate"),
                 ("layer_1", "moe", "shared", "up", "kernel"),
                 ("layer_0", "mlp", "down", "kernel"), ("lm_head", "kernel")):
        a, b = got, gp_ref
        for k in path:
            a, b = a[k], b[k]
        assert rel(a, b) < 2e-4, path
    # a body without buffers: the step gave every leaf an update
    moved = jax.tree.map(
        lambda was, now: bool((was != np.asarray(now)).any()), before, tr.params
    )
    assert not getattr(cfg, "buffers", ()) and all(jax.tree.leaves(moved))


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
    assert tr.logits(batches[0]).shape == (2, 64, tr.cfg.vocab_size)


def test_the_registered_app_trains(tmp_path):
    """``laguna_hybrid`` through ``app.create``: the loss falls over the
    app's steps and no held slot is dropped."""
    from parameter_server_tpu import app as app_lib

    raw = {"app": "laguna_hybrid", "steps": 6,
           "table": {"name": "emb", "rows": 256, "dim": 1,
                     "optimizer": {"kind": "adagrad"}},
           "data": {"kind": "synthetic", "key_space": 64, "nnz": 2,
                    "batch_size": 512, "seed": 0},
           "consistency": {"mode": "ssp", "max_delay": 1},
           "topology": {"num_servers": 2}}
    path = tmp_path / "cfg.json"
    path.write_text(json.dumps(raw))
    out = app_lib.create(app_lib.load_config(str(path)))()
    assert len(out["losses"]) == 6 and np.isfinite(out["losses"]).all()
    assert out["losses"][-1] < out["losses"][0]
    assert out["counters"]["moe_dropped_slots"] == 0
    assert out["counters"]["moe_held_slots"] > 0
