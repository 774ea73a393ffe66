"""Pipeline parallelism: GPipe microbatch pipeline over the pp mesh axis.

Completes the parallelism inventory (SURVEY §2 deferred PP).  The pipeline
must be EXACT: the scanned ppermute schedule computes the same function as
applying the stages sequentially, losses match to float tolerance, and
training through reverse-AD of the pipeline converges.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from parameter_server_tpu.models import transformer as tfm
from parameter_server_tpu.parallel.pp import PipelinedLMTrainer


def _pp_mesh(n=4):
    devices = jax.devices()[:n]
    return Mesh(np.asarray(devices), ("pp",))


def _cfg():
    return tfm.tiny_config(causal=True)  # 2 layers


def _tokens(cfg, rng, batch=8, seq=16):
    base = rng.integers(0, cfg.vocab_size, size=(batch, 1))
    offs = np.arange(seq)[None, :]
    return ((base + offs) % cfg.vocab_size).astype(np.int32)


def _sequential_loss(trainer, tokens):
    """Oracle: same params, stages applied in order, no pipeline."""
    cfg = trainer.cfg
    micro = tokens.reshape(
        trainer.n_micro, tokens.shape[0] // trainer.n_micro, tokens.shape[1]
    )
    stages_host = jax.device_get(trainer.stage_params)
    embed = jax.device_get(trainer.embed)
    head = jax.device_get(trainer.head)
    norm = jax.device_get(trainer.norm)
    losses = []
    for mb in micro:
        x = jnp.asarray(embed)[jnp.asarray(mb)]
        for s in range(trainer.n_stages):
            params_s = jax.tree.map(lambda a: jnp.asarray(a[s]), stages_host)
            x = trainer.stage_module.apply({"params": params_s}, x)
        x = trainer.norm_module.apply({"params": jax.tree.map(jnp.asarray, norm)}, x)
        logits = jnp.einsum("bsd,dv->bsv", x, jnp.asarray(head))
        losses.append(tfm.causal_lm_loss(logits, jnp.asarray(mb)))
    return float(jnp.mean(jnp.asarray(losses)))


@pytest.mark.parametrize("n_stages,n_layers", [(2, 2), (4, 4)])
def test_pipeline_matches_sequential(n_stages, n_layers):
    cfg = tfm.tiny_config(causal=True, n_layers=n_layers)
    mesh = _pp_mesh(n_stages)
    trainer = PipelinedLMTrainer(cfg, mesh, n_micro=4, seed=1)
    rng = np.random.default_rng(0)
    tokens = _tokens(cfg, rng)
    got = trainer.loss(tokens)
    want = _sequential_loss(trainer, tokens)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_pipeline_trains():
    cfg = tfm.tiny_config(causal=True, n_layers=4)
    mesh = _pp_mesh(4)
    trainer = PipelinedLMTrainer(cfg, mesh, n_micro=4, learning_rate=3e-3)
    rng = np.random.default_rng(2)
    losses = [trainer.step(_tokens(cfg, rng)) for _ in range(12)]
    assert np.isfinite(losses).all()
    assert np.mean(losses[-3:]) < np.mean(losses[:3]) - 0.1, losses


def test_pipeline_stage_weights_are_sharded():
    cfg = tfm.tiny_config(causal=True, n_layers=4)
    mesh = _pp_mesh(4)
    trainer = PipelinedLMTrainer(cfg, mesh, n_micro=4)
    leaf = jax.tree.leaves(trainer.stage_params)[0]
    assert leaf.shape[0] == 4  # stage axis
    # one stage per device, not replicated
    assert len(leaf.addressable_shards) == 4
    assert leaf.addressable_shards[0].data.shape[0] == 1


def test_pipeline_rejects_bad_shapes():
    cfg = tfm.tiny_config(causal=True, n_layers=2)  # 2 layers, 4 stages
    with pytest.raises(ValueError, match="n_layers"):
        PipelinedLMTrainer(cfg, _pp_mesh(4), n_micro=2)
    # learned positional embeddings are stage-0-only state: unsupported
    bert_like = tfm.tiny_config(causal=False, n_layers=2)
    with pytest.raises(ValueError, match="rotary"):
        PipelinedLMTrainer(bert_like, _pp_mesh(2), n_micro=2)
    mesh = _pp_mesh(2)
    # microbatch stack shards over pp: n_micro must split across stages
    with pytest.raises(ValueError, match="n_micro"):
        PipelinedLMTrainer(cfg, mesh, n_micro=3)
    trainer = PipelinedLMTrainer(cfg, mesh, n_micro=4)
    with pytest.raises(ValueError, match="n_micro"):
        trainer.step(np.zeros((9, 16), np.int32))  # 9 % 2 != 0


def test_pipeline_gradients_match_sequential():
    """Backward exactness: reverse-AD through the scanned ppermute pipeline
    must produce the SAME gradients as the sequential stage application —
    forward parity alone would not catch a corrupted cotangent route."""
    cfg = tfm.tiny_config(causal=True, n_layers=2)
    mesh = _pp_mesh(2)
    trainer = PipelinedLMTrainer(cfg, mesh, n_micro=2, seed=3)
    rng = np.random.default_rng(4)
    tokens = _tokens(cfg, rng, batch=4, seq=8)
    micro = jnp.asarray(trainer._micro(tokens))
    params = trainer._params()

    pipe_grads = jax.grad(trainer._loss)(params, micro)

    def seq_loss(p):
        losses = []
        for mb in micro:
            x = p["embed"][mb]
            for s in range(trainer.n_stages):
                ps = jax.tree.map(lambda a: a[s], p["stages"])
                x = trainer.stage_module.apply({"params": ps}, x)
            x = trainer.norm_module.apply({"params": p["norm"]}, x)
            logits = jnp.einsum("bsd,dv->bsv", x, p["head"])
            losses.append(tfm.causal_lm_loss(logits, mb))
        return jnp.mean(jnp.asarray(losses))

    host = jax.device_get(params)
    seq_grads = jax.grad(seq_loss)(jax.tree.map(jnp.asarray, host))
    for pg, sg in zip(jax.tree.leaves(pipe_grads), jax.tree.leaves(seq_grads)):
        np.testing.assert_allclose(
            np.asarray(pg), np.asarray(sg), rtol=1e-4, atol=1e-5
        )


def test_pipeline_opt_state_stays_pp_sharded():
    """Adam moments for the stage stack must be pp-sharded from init —
    replicating them would cost 2x the full stack per device."""
    cfg = tfm.tiny_config(causal=True, n_layers=4)
    trainer = PipelinedLMTrainer(cfg, _pp_mesh(4), n_micro=4)
    mu = jax.tree.leaves(trainer.opt_state[0].mu["stages"])[0]
    assert mu.addressable_shards[0].data.shape[0] == 1  # 1 of 4 stages


def test_pipeline_per_device_memory_is_bounded_by_m_over_s_model():
    """VERDICT r3 #8: the injection/output buffers are pp-sharded (O(M/S)
    per device, was O(M) replicated) and the tick body is rematerialized.
    Assert XLA's compiled per-device temps against the analytic budget:
    2 x (M/S) microbatch buffers + (M+S-1) remat-saved tick inputs + a
    working-set allowance — a regression that re-replicates the stack or
    drops remat blows through the 3x headroom."""
    cfg = tfm.tiny_config(
        causal=True, n_layers=4, d_model=256, max_seq=256, vocab_size=512
    )
    S, M, mb, seq = 4, 16, 4, 256
    mesh = _pp_mesh(S)
    trainer = PipelinedLMTrainer(cfg, mesh, n_micro=M)
    micro = jnp.zeros((M, mb, seq), jnp.int32)
    ma = (
        trainer._loss.lower(trainer._params(), micro).compile()
        .memory_analysis()
    )
    act = mb * seq * cfg.d_model * 4  # one microbatch activation, f32
    logits_mb = mb * seq * cfg.vocab_size * 4
    budget = (
        2 * (M // S) * act  # x stack + out_buf shards
        + (M + S - 1) * 2 * act  # remat-saved tick inputs (fwd+bwd pair)
        + (M // S) * logits_mb * 2  # local head logits + softmax copy
        + 16 * act  # per-tick working set allowance
    )
    assert ma.temp_size_in_bytes <= 3 * budget, (
        ma.temp_size_in_bytes,
        budget,
    )


def test_pipeline_bubble_amortizes_with_microbatches():
    """GPipe bubble model: per-example step time ~ (M+S-1)/M at fixed
    microbatch size.  S=4: M=4 -> 1.75, M=16 -> 1.19 — raising M must cut
    per-example time measurably (the table VERDICT r3 #8 asked for prints
    to the log; the assert keeps only the robust monotonic claim)."""
    import time

    cfg = tfm.tiny_config(causal=True, n_layers=4, d_model=128, max_seq=64)
    S, mb, seq = 4, 2, 64
    mesh = _pp_mesh(S)
    rng = np.random.default_rng(11)
    rows = []
    for M in (4, 16):
        trainer = PipelinedLMTrainer(cfg, mesh, n_micro=M, seed=2)
        tokens = _tokens(cfg, rng, batch=M * mb, seq=seq)
        micro = jnp.asarray(trainer._micro(tokens))
        params = trainer._params()
        trainer._loss(params, micro)  # compile
        # the best of a few: a neighbour's compile on a shared host stretches
        # one repetition, not every one
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(trainer._loss(params, micro))
            best = min(best, time.perf_counter() - t0)
        per_example = best / (M * mb)
        rows.append((M, per_example, (M + S - 1) / M))
        print(
            f"pp bubble: M={M} per-example={per_example * 1e3:.3f} ms "
            f"(model {(M + S - 1) / M:.2f}x ideal)"
        )
    # M=16 has 1.19x bubble vs M=4's 1.75x: per-example time must drop
    assert rows[1][1] < rows[0][1], rows


def test_pipeline_composes_with_dp():
    """DP x PP on one (data, pp) mesh: same math as pure PP, batch rows
    sharded over data, loss/grads allreduced — the composability the module
    docstring promises, tested rather than asserted."""
    from jax.sharding import Mesh as _Mesh

    cfg = tfm.tiny_config(causal=True, n_layers=4)
    devices = np.asarray(jax.devices()[:8]).reshape(2, 4)
    mesh_dp_pp = _Mesh(devices, ("data", "pp"))
    rng = np.random.default_rng(7)
    tokens = _tokens(cfg, rng, batch=8, seq=16)

    dp_pp = PipelinedLMTrainer(cfg, mesh_dp_pp, n_micro=4, seed=5)
    pure = PipelinedLMTrainer(cfg, _pp_mesh(4), n_micro=4, seed=5)
    np.testing.assert_allclose(
        dp_pp.loss(tokens), pure.loss(tokens), rtol=2e-5, atol=2e-5
    )
    # and it trains
    losses = [dp_pp.step(_tokens(cfg, rng)) for _ in range(8)]
    assert np.isfinite(losses).all()
    assert np.mean(losses[-2:]) < np.mean(losses[:2])


def test_1f1b_matches_gpipe_trajectory():
    """schedule="1f1b" (manual interleaved backward) must produce the SAME
    training trajectory as the AD-through-scan GPipe schedule — identical
    math, different tick order (VERDICT r4 #9)."""
    cfg = tfm.tiny_config(
        causal=True, tie_embeddings=False, n_layers=4, n_kv_heads=4
    )
    mesh = _pp_mesh(4)
    rng = np.random.default_rng(0)
    toks = [_tokens(cfg, rng) for _ in range(3)]
    tg = PipelinedLMTrainer(cfg, mesh, n_micro=8, seed=0)
    t1 = PipelinedLMTrainer(cfg, mesh, n_micro=8, seed=0, schedule="1f1b")
    lg = [tg.step(t) for t in toks]
    l1 = [t1.step(t) for t in toks]
    np.testing.assert_allclose(lg, l1, rtol=2e-5, atol=1e-6)


def test_1f1b_composes_with_dp():
    """DP x PP with the manual 1F1B backward: the embedding gradient must
    carry the data-pmean scaling (a sum-scatter of per-replica dx would be
    n_data x too large — caught in review), so the trajectory must equal
    GPipe's on the same (data, pp) mesh and stream."""
    cfg = tfm.tiny_config(
        causal=True, tie_embeddings=False, n_layers=4, n_kv_heads=4
    )
    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "pp"))
    rng = np.random.default_rng(0)
    toks = [_tokens(cfg, rng, batch=16) for _ in range(3)]
    tg = PipelinedLMTrainer(cfg, mesh, n_micro=8, seed=0)
    t1 = PipelinedLMTrainer(cfg, mesh, n_micro=8, seed=0, schedule="1f1b")
    lg = [tg.step(t) for t in toks]
    l1 = [t1.step(t) for t in toks]
    np.testing.assert_allclose(lg, l1, rtol=2e-5, atol=1e-6)


def test_1f1b_memory_is_microbatch_independent():
    """1F1B's point: compiled temp memory stays ~flat as M grows (O(S)
    stash) while GPipe's saved residuals grow O(M).  Measured via XLA's
    own memory analysis of the compiled steps."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from parameter_server_tpu.parallel import pp as pp_lib

    cfg = tfm.tiny_config(
        causal=True, tie_embeddings=False, n_layers=4, n_kv_heads=4,
        d_model=128, d_ff=256, max_seq=128,
    )
    mesh = _pp_mesh(4)

    def temps(schedule, n_micro):
        step, _l, stage_module, norm_module, tx = pp_lib.make_pp_step(
            cfg, mesh, schedule=schedule
        )
        x0 = jnp.zeros((1, 8, cfg.d_model), jnp.float32)
        st_shapes = jax.eval_shape(
            lambda k: jax.vmap(
                lambda kk: stage_module.init(kk, x0)["params"]
            )(k),
            jax.ShapeDtypeStruct((4, 2), jnp.uint32),
        )
        st_shard = pp_lib.stage_sharding(mesh, st_shapes)
        repl = NamedSharding(mesh, P())
        params = {
            "stages": jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=sh
                ),
                st_shapes, st_shard,
            ),
            "embed": jax.ShapeDtypeStruct(
                (cfg.vocab_size, cfg.d_model), jnp.float32, sharding=repl
            ),
            "head": jax.ShapeDtypeStruct(
                (cfg.d_model, cfg.vocab_size), jnp.float32, sharding=repl
            ),
            "norm": jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=repl
                ),
                jax.eval_shape(
                    lambda: norm_module.init(
                        jax.random.PRNGKey(0), x0
                    )["params"]
                ),
            ),
        }
        import optax

        param_shardings = {
            "stages": st_shard,
            "embed": repl,
            "head": repl,
            "norm": jax.tree.map(lambda _: repl, params["norm"]),
        }
        opt = optax.tree_map_params(
            tx,
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            jax.eval_shape(tx.init, params),
            param_shardings,
        )
        tok = jax.ShapeDtypeStruct(
            (n_micro, 2, 128), jnp.int32,
            sharding=NamedSharding(mesh, P("pp")),
        )
        with mesh:
            c = step.lower(params, opt, tok).compile()
        return int(c.memory_analysis().temp_size_in_bytes)

    g_ratio = temps("gpipe", 32) / temps("gpipe", 8)
    f_ratio = temps("1f1b", 32) / temps("1f1b", 8)
    # measured: ~2.4x vs ~1.2x; margins generous against XLA version drift
    assert g_ratio > 1.7, g_ratio
    assert f_ratio < 1.45, f_ratio
    assert f_ratio < g_ratio - 0.4, (f_ratio, g_ratio)


def test_pp_composes_with_tp():
    """PP x TP (r5): stage weights shard over BOTH the stage and model
    axes via the partial-manual shard_map (only pp manual, model stays
    GSPMD) — same trajectory as the pp-only pipeline."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from parameter_server_tpu.parallel import pp as pp_lib

    cfg = tfm.tiny_config(
        causal=True, tie_embeddings=False, n_layers=4, n_kv_heads=2
    )

    def build(mesh, tp, schedule="gpipe"):
        step, _l, stage_module, norm_module, tx = pp_lib.make_pp_step(
            cfg, mesh, tp=tp, schedule=schedule
        )
        x0 = jnp.zeros((1, 8, cfg.d_model), jnp.float32)
        keys = jax.random.split(jax.random.PRNGKey(0), 2)
        init = lambda k: jax.vmap(  # noqa: E731
            lambda kk: stage_module.init(kk, x0)["params"]
        )(k)
        sh = pp_lib.stage_sharding(mesh, jax.eval_shape(init, keys), tp=tp)
        with mesh:
            stages = jax.jit(init, out_shardings=sh)(keys)
        repl = NamedSharding(mesh, P())
        rngs = jax.random.split(jax.random.PRNGKey(9), 3)
        params = {
            "stages": stages,
            "embed": jax.device_put(
                (jax.random.normal(rngs[0], (cfg.vocab_size, cfg.d_model))
                 * 0.02).astype(jnp.float32), repl),
            "head": jax.device_put(
                (jax.random.normal(rngs[1], (cfg.d_model, cfg.vocab_size))
                 * 0.02).astype(jnp.float32), repl),
            "norm": jax.device_put(
                norm_module.init(rngs[2], x0)["params"], repl),
        }
        return step, params, tx.init(params), mesh

    mesh_tp = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                   ("pp", "model"))
    mesh_pp = Mesh(np.asarray(jax.devices()[:2]), ("pp",))
    step_tp, p_tp, o_tp, _ = build(mesh_tp, True)
    step_1, p_1, o_1, _ = build(mesh_pp, False)
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(4, 1, 16)
    ).astype(np.int32)
    with mesh_tp:
        p_tp, o_tp, l_tp = step_tp(
            p_tp, o_tp,
            jax.device_put(jnp.asarray(toks),
                           NamedSharding(mesh_tp, P("pp"))),
        )
    with mesh_pp:
        p_1, o_1, l_1 = step_1(
            p_1, o_1,
            jax.device_put(jnp.asarray(toks),
                           NamedSharding(mesh_pp, P("pp"))),
        )
    np.testing.assert_allclose(float(l_tp), float(l_1), rtol=2e-5)
    # the TP sharding is real: a q kernel carries BOTH axes
    q_spec = str(
        p_tp["stages"]["Block_0"]["attn"]["q"]["kernel"].sharding.spec
    )
    assert "pp" in q_spec and "model" in q_spec, q_spec
    # ... and the manual-backward schedule composes with TP identically
    step_f, p_f, o_f, _ = build(mesh_tp, True, schedule="1f1b")
    with mesh_tp:
        _, _, l_f = step_f(
            p_f, o_f,
            jax.device_put(jnp.asarray(toks),
                           NamedSharding(mesh_tp, P("pp"))),
        )
    np.testing.assert_allclose(float(l_f), float(l_1), rtol=2e-5)
