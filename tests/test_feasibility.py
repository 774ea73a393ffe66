"""Memory-feasibility machinery: chunked loss, trunk seam, FSDP shardings.

The 8B numbers themselves come from ``python -m
parameter_server_tpu.parallel.feasibility`` (minutes of XLA compile); these
tests prove the machinery at toy scale on the 8-device mesh.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from parameter_server_tpu.models import transformer as tfm
from parameter_server_tpu.parallel import mesh as mesh_lib
from parameter_server_tpu.parallel.feasibility import body_train_step_memory
from parameter_server_tpu.parallel.tp import transformer_param_shardings


def _cfg(**kw):
    defaults = dict(
        causal=True, tie_embeddings=False, d_model=64, n_layers=2,
        n_heads=4, n_kv_heads=4,
    )
    defaults.update(kw)
    return tfm.tiny_config(**defaults)


def test_chunked_loss_matches_full_logits_values_and_grads():
    rng = np.random.default_rng(0)
    B, S, d, V = 2, 33, 16, 50
    hidden = jnp.asarray(rng.normal(size=(B, S, d)).astype(np.float32))
    head = jnp.asarray(rng.normal(size=(d, V)).astype(np.float32))
    tokens = jnp.asarray(rng.integers(0, V, size=(B, S)).astype(np.int32))
    ref = tfm.causal_lm_loss(jnp.einsum("bsd,dv->bsv", hidden, head), tokens)
    for chunk in (1, 7, 32, 64):  # incl. non-dividing and > S
        got = tfm.chunked_causal_lm_loss(hidden, head, tokens, chunk)
        np.testing.assert_allclose(float(got), float(ref), rtol=2e-6)
    g_ref = jax.grad(
        lambda h, w: tfm.causal_lm_loss(
            jnp.einsum("bsd,dv->bsv", h, w), tokens
        ),
        argnums=(0, 1),
    )(hidden, head)
    g_chk = jax.grad(
        lambda h, w: tfm.chunked_causal_lm_loss(h, w, tokens, 8),
        argnums=(0, 1),
    )(hidden, head)
    for a, b in zip(g_ref, g_chk):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        )


def test_trunk_params_are_body_params_minus_head():
    """TransformerBody params minus lm_head apply directly through
    TransformerTrunk, and trunk_hidden @ head == body logits."""
    cfg = _cfg()
    body = tfm.TransformerBody(cfg)
    trunk = tfm.TransformerTrunk(cfg)
    x = jnp.asarray(
        np.random.default_rng(1).normal(size=(2, 8, cfg.d_model)).astype(
            np.float32
        )
    )
    params = body.init(jax.random.PRNGKey(0), x)["params"]
    trunk_params = {k: v for k, v in params.items() if k != "lm_head"}
    hidden = trunk.apply({"params": trunk_params}, x)
    want = body.apply({"params": params}, x)
    got = jnp.einsum(
        "bsd,dv->bsv", hidden, params["lm_head"]["kernel"],
        preferred_element_type=jnp.float32,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_fsdp_shardings_split_state_over_data_axis():
    cfg = _cfg()
    mesh = mesh_lib.make_mesh((2, 4))
    body = tfm.TransformerBody(cfg)
    x = jnp.zeros((1, 8, cfg.d_model), jnp.float32)
    params = body.init(jax.random.PRNGKey(0), x)["params"]
    tp = transformer_param_shardings(params, mesh)
    fsdp = transformer_param_shardings(params, mesh, fsdp=True)

    def per_device_bytes(shardings):
        total = 0
        for leaf, sh in zip(jax.tree.leaves(params), jax.tree.leaves(shardings)):
            shard_shape = sh.shard_shape(leaf.shape)
            total += int(np.prod(shard_shape)) * leaf.dtype.itemsize
        return total

    # FSDP state footprint per device must be ~half the TP-only footprint
    # on a data=2 mesh (small replicated leaves may not split)
    assert per_device_bytes(fsdp) < 0.6 * per_device_bytes(tp)
    # and every spec stays loadable (dims divide)
    for leaf, sh in zip(jax.tree.leaves(params), jax.tree.leaves(fsdp)):
        sh.shard_shape(leaf.shape)  # raises if not divisible


@pytest.mark.parametrize("fsdp", ["none", "state"])
def test_memory_analysis_runs_and_knobs_reduce_memory(fsdp):
    cfg_remat = _cfg(remat=True)
    mesh = mesh_lib.make_mesh((2, 4))
    r = body_train_step_memory(
        cfg_remat, mesh, 8, 32, loss_chunk=8, fsdp=fsdp
    )
    assert r["peak_bytes"] > 0 and r["n_body_params"] > 0
    assert r["fsdp"] == fsdp and r["loss_chunk"] == 8
    if fsdp == "state":
        # moments sharded over data too -> arguments shrink
        r_tp = body_train_step_memory(
            cfg_remat, mesh, 8, 32, loss_chunk=8, fsdp="none"
        )
        assert r["argument_bytes"] < r_tp["argument_bytes"]


def test_fsdp_training_still_converges():
    """FSDP shardings are a layout, not a math change: a few steps of the
    tiny body under fsdp param placement behave like the TP placement."""
    import optax

    cfg = _cfg()
    mesh = mesh_lib.make_mesh((2, 4))
    body = tfm.TransformerBody(cfg)
    rng = np.random.default_rng(2)
    tokens = rng.integers(0, cfg.vocab_size, size=(4, 16)).astype(np.int32)
    emb = rng.normal(size=(4, 16, cfg.d_model)).astype(np.float32)

    def losses_with(fsdp: bool):
        params = body.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8, cfg.d_model))
        )["params"]
        sh = transformer_param_shardings(params, mesh, fsdp=fsdp)
        params = jax.tree.map(jax.device_put, params, sh)
        tx = optax.adamw(1e-2)
        opt = tx.init(params)

        @jax.jit
        def step(p, o, e, t):
            def loss_fn(p_):
                logits = body.apply({"params": p_}, e)
                return tfm.causal_lm_loss(logits, t)

            l, g = jax.value_and_grad(loss_fn)(p)
            u, o = tx.update(g, o, p)
            return optax.apply_updates(p, u), o, l

        out = []
        e, t = jnp.asarray(emb), jnp.asarray(tokens)
        for _ in range(3):
            params, opt, l = step(params, opt, e, t)
            out.append(float(l))
        return out

    np.testing.assert_allclose(
        losses_with(True), losses_with(False), rtol=1e-4
    )
