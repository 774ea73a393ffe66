"""AOT memory feasibility: does a config FIT the target pod, per XLA itself?

SURVEY §7 step 7 / VERDICT r3 #3: before claiming the Llama-3-8B hybrid
(BASELINE config #5) runs on a v5e-16, prove the per-device compiled memory.
The technique is the one ``tests/test_seq_parallel.py`` uses for ring
attention, pointed at the flagship: AOT-compile the REAL body train step
(fwd + bwd + adamw update, the exact ``HybridLMTrainer`` step_fn math) over
a simulated N-device mesh from ``ShapeDtypeStruct``s — no parameter is ever
materialized, so a 7B-param program analyzes fine on a dev box — and read
XLA's own ``memory_analysis()`` for the per-device argument/temp/output
budget.

Run as a module, out of process (a 16-device virtual CPU topology must be
fixed before jax initializes):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=16 \
      python -m parameter_server_tpu.parallel.feasibility --preset llama3-8b
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Optional, Sequence

#: v5e HBM per chip (bytes) — the budget the flagship config must fit.
V5E_HBM_BYTES = 16 * 1024**3


def peak_bytes_from_analysis(ma) -> int:
    """Live-at-peak per device from XLA's ``memory_analysis()``.

    arguments (params+opt+batch; donation aliases the outputs onto them)
    + temps + generated code; alias_bytes is the donated overlap counted
    inside argument_bytes, not extra.  ONE definition, shared by the
    feasibility table and ``tools/validate_peak_bytes.py`` — the validator
    must calibrate the formula the table actually ships.
    """
    return (
        int(ma.argument_size_in_bytes)
        + int(ma.temp_size_in_bytes)
        + int(ma.generated_code_size_in_bytes)
        + max(int(ma.output_size_in_bytes) - int(ma.alias_size_in_bytes), 0)
    )


def compile_body_step(
    cfg,
    mesh,
    batch: int,
    seq: int,
    *,
    learning_rate: float = 1e-3,
    loss_chunk: int = 0,
    fsdp: str = "none",
):
    """AOT-compile one hybrid-body train step; returns (compiled, inputs).

    ``inputs`` is the (params, opt_state, emb, tokens) tuple of
    ``ShapeDtypeStruct``s (sharding-annotated) the compiled step expects —
    the validator tool materializes real arrays against them to compare
    ``memory_analysis()`` with the allocator's actual high-water
    (VERDICT r4 weak #7).
    """
    import jax
    import jax.numpy as jnp
    import optax

    from parameter_server_tpu.models import transformer as tfm
    from parameter_server_tpu.parallel import mesh as mesh_lib
    from parameter_server_tpu.parallel.tp import transformer_param_shardings

    body = tfm.TransformerBody(cfg)
    tx = optax.adamw(learning_rate)

    if fsdp not in ("none", "full", "state"):
        raise ValueError(f"fsdp must be none|full|state, got {fsdp!r}")
    x0 = jax.ShapeDtypeStruct((1, 8, cfg.d_model), jnp.float32)
    param_shapes = jax.eval_shape(
        lambda x: body.init(jax.random.PRNGKey(0), x)["params"], x0
    )
    p_shard = transformer_param_shardings(
        param_shapes, mesh, fsdp=fsdp == "full"
    )
    s_shard = (
        p_shard
        if fsdp == "none"
        else transformer_param_shardings(param_shapes, mesh, fsdp=True)
    )
    params_in = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        param_shapes,
        p_shard,
    )
    opt_shapes = jax.eval_shape(tx.init, params_in)
    # adamw moments mirror the param tree: give each param-like leaf its
    # param's (or, under fsdp="state", the further data-sharded) sharding
    # (non-param leaves — the int count — stay unsharded)
    opt_in = optax.tree_map_params(
        tx,
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        opt_shapes,
        s_shard,
    )
    emb_in = jax.ShapeDtypeStruct(
        (batch, seq, cfg.d_model), jnp.float32,
        sharding=mesh_lib.batch_sharding(mesh, 3),
    )
    tokens = jax.ShapeDtypeStruct(
        (batch, seq), jnp.int32, sharding=mesh_lib.batch_sharding(mesh, 2)
    )

    if loss_chunk > 0:
        trunk = tfm.TransformerTrunk(cfg)

        def loss_fn(params, emb, targets):
            hidden = trunk.apply(
                {"params": {k: v for k, v in params.items() if k != "lm_head"}},
                emb,
            )
            return tfm.chunked_causal_lm_loss(
                hidden, params["lm_head"]["kernel"], targets, loss_chunk
            )

    else:

        def loss_fn(params, emb, targets):
            logits = body.apply({"params": params}, emb)
            return tfm.causal_lm_loss(logits, targets)

    def step_fn(params, opt_state, emb, targets):
        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(
            params, emb, targets
        )
        g_params, g_emb = grads
        updates, opt_state = tx.update(g_params, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss, g_emb

    step = jax.jit(step_fn, donate_argnums=(0, 1))
    with mesh:
        compiled = step.lower(params_in, opt_in, emb_in, tokens).compile()
    return compiled, (params_in, opt_in, emb_in, tokens)


def body_train_step_memory(
    cfg,
    mesh,
    batch: int,
    seq: int,
    *,
    learning_rate: float = 1e-3,
    loss_chunk: int = 0,
    fsdp: str = "none",
) -> dict:
    """Per-device memory analysis of the hybrid body train step.

    Returns XLA's compiled memory breakdown (bytes, per device) for one
    ``HybridLMTrainer``-shaped step: loss+grads w.r.t. (params, emb_in),
    adamw update, batch sharded over ``data``, params TP-sharded over
    ``model`` (``parallel/tp.py`` rules).

    ``loss_chunk > 0`` fuses the lm_head into a rematerialized chunked loss
    (``chunked_causal_lm_loss``) instead of materializing full logits.
    ``fsdp``: ``"none"`` = TP shardings only; ``"full"`` = params AND
    moments data-sharded (measured: GSPMD hoists the param all-gather out
    of the layer scan, so the gathered stack reappears as a temp — little
    net win); ``"state"`` = moments-only data sharding (the elementwise
    adamw update needs no gather, so the saving is real).
    """
    import jax
    import numpy as np

    compiled, (params_in, _opt_in, _emb_in, _tokens) = compile_body_step(
        cfg, mesh, batch, seq,
        learning_rate=learning_rate, loss_chunk=loss_chunk, fsdp=fsdp,
    )
    ma = compiled.memory_analysis()
    n_params = sum(
        int(np.prod(s.shape)) for s in jax.tree.leaves(params_in)
    )
    out = {
        "n_body_params": n_params,
        "mesh": dict(mesh.shape),
        "batch": batch,
        "seq": seq,
        "remat": bool(cfg.remat),
        "scan_blocks": bool(cfg.scan_blocks),
        "loss_chunk": loss_chunk,
        "fsdp": fsdp,
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
    }
    out["peak_bytes"] = peak_bytes_from_analysis(ma)
    out["fits_v5e"] = out["peak_bytes"] <= V5E_HBM_BYTES
    return out


def llama3_8b_feasibility(
    *,
    mesh_shape: Sequence[int] = (2, 8),
    batch: int = 8,
    seq: int = 2048,
    remat: bool = True,
    loss_chunk: int = 512,
    fsdp: str = "state",
    scan_blocks: bool = True,
    dtype: Optional[str] = None,
) -> dict:
    """The flagship check: config #5's 8B body on a v5e-16-shaped mesh.

    Default knobs are the fitting recipe: (2, 8) mesh (TP capped at 8 by
    the 8 KV heads), scan-over-blocks with per-block remat (unrolled remat
    saves ~nothing — XLA's liveness only credits recompute inside scan),
    chunked fused-head loss, FSDP over the data axis.
    """
    import jax.numpy as jnp

    from parameter_server_tpu.models import transformer as tfm
    from parameter_server_tpu.parallel import mesh as mesh_lib

    kw = dict(remat=remat, scan_blocks=scan_blocks)
    if dtype:
        kw["dtype"] = jnp.dtype(dtype)
    cfg = tfm.llama3_8b(**kw)
    mesh = mesh_lib.make_mesh(tuple(mesh_shape))
    return body_train_step_memory(
        cfg, mesh, batch, seq, loss_chunk=loss_chunk, fsdp=fsdp
    )


def dlrm_feasibility(
    *,
    rows_log2: int = 30,
    dim: int = 16,
    mesh_shape: Sequence[int] = (1, 16),
    batch: int = 8192,
    n_sparse: int = 26,
    n_dense: int = 13,
    slots_log2: int = 18,
    optimizer: str = "adagrad",
    learning_rate: float = 0.01,
) -> dict:
    """Billion-row DLRM (config #3) per-device memory, per XLA (VERDICT r4 #3).

    AOT-compiles the REAL ``SpmdDLRMTrainer`` step (``make_dlrm_step``)
    from ShapeDtypeStructs over a simulated pod mesh: a 2^30-row x dim-16
    table + optimizer rows row-sharded over the ``model`` axis — value and
    state are 64 GB EACH at the default shape, analyzed without ever being
    materialized.  ``slots_log2`` is the bucketed unique-slot count the
    step is compiled for (``localize_to_slots``' min_bucket mechanics).
    """
    import jax
    import jax.numpy as jnp
    import optax

    from parameter_server_tpu.config import OptimizerConfig, TableConfig
    from parameter_server_tpu.kv.optim import make_optimizer
    from parameter_server_tpu.models.dlrm import DLRM, make_dlrm_step
    from parameter_server_tpu.parallel import mesh as mesh_lib

    mesh = mesh_lib.make_mesh(tuple(mesh_shape))
    rows = 1 << rows_log2
    cfg = TableConfig(
        name="emb", rows=rows, dim=dim,
        # the caller's learning_rate drives BOTH planes: the embedding
        # optimizer here and the MLP adam below (it was silently pinned to
        # 0.05 for the table — ADVICE r5 #2)
        optimizer=OptimizerConfig(kind=optimizer, learning_rate=learning_rate),
    )
    opt = make_optimizer(cfg.optimizer)
    model = DLRM(bottom_mlp=(64, 32), top_mlp=(64, 32), emb_dim=dim)
    tx = optax.adam(learning_rate)
    n_model = mesh.shape[mesh_lib.MODEL_AXIS]
    total_rows = ((rows + 1 + n_model - 1) // n_model) * n_model
    step, _sh = make_dlrm_step(cfg, mesh, model, opt, tx, n_sparse)

    t_f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    t_i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    emb_value = t_f32(total_rows, dim)
    emb_state = {k: t_f32(total_rows, dim) for k in opt.state_shapes()}
    mlp_shapes = jax.eval_shape(
        lambda: model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, n_dense), jnp.float32),
            jnp.zeros((1, n_sparse, dim), jnp.float32),
        )["params"]
    )
    opt_shapes = jax.eval_shape(tx.init, mlp_shapes)
    n_slots = 1 << slots_log2
    with mesh:
        compiled = step.lower(
            emb_value, emb_state, mlp_shapes, opt_shapes,
            t_i32(n_slots), t_i32(batch * n_sparse),
            t_f32(batch, n_dense), t_f32(batch),
        ).compile()
    ma = compiled.memory_analysis()
    table_bytes_per_dev = (
        (1 + len(emb_state)) * total_rows * dim * 4 // n_model
    )
    out = {
        "rows_log2": rows_log2,
        "dim": dim,
        "mesh": dict(mesh.shape),
        "batch": batch,
        "n_sparse": n_sparse,
        "slots_log2": slots_log2,
        "optimizer": optimizer,
        "table_bytes_per_device": table_bytes_per_dev,
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
    }
    out["peak_bytes"] = peak_bytes_from_analysis(ma)
    out["fits_v5e"] = out["peak_bytes"] <= V5E_HBM_BYTES
    return out


def sp_8b_feasibility(
    *,
    mesh_shape: Sequence[int] = (2, 8),
    batch: int = 1,
    seq: int = 16384,
    remat: bool = True,
    loss_chunk: int = 512,
    fsdp: str = "state",
    scan_blocks: bool = True,
    dtype: Optional[str] = None,
) -> dict:
    """The composed long-context 8B check (VERDICT r4 #5).

    AOT-compiles ``SpTpLMTrainer``'s REAL step — ring attention over the
    ``sp`` axis (partial shard_map), TP over ``model``, moments-FSDP over
    ``sp``, scan+remat+per-shard chunked fused loss — from
    ShapeDtypeStructs on a simulated (sp, model) v5e-16 and reads the
    per-device compiled memory at long sequence lengths.
    """
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from parameter_server_tpu.models import transformer as tfm
    from parameter_server_tpu.parallel.sp_fsdp import (
        MODEL_AXIS, SP_AXIS, make_sp_step,
    )
    from parameter_server_tpu.parallel.tp import transformer_param_shardings

    if fsdp not in ("none", "state"):
        raise ValueError(f"fsdp must be none|state, got {fsdp!r}")
    kw = dict(remat=remat, scan_blocks=scan_blocks)
    if dtype:
        # compute/activation dtype: bf16 halves the scan-saved residual
        # stack (params/moments stay fp32 — flax param_dtype default)
        kw["dtype"] = jnp.dtype(dtype)
    cfg = tfm.llama3_8b(**kw)
    devices = np.asarray(jax.devices()).reshape(mesh_shape)
    mesh = Mesh(devices, (SP_AXIS, MODEL_AXIS))
    cfg_run = dataclasses.replace(
        cfg, attn_impl="ring_spmd", sp_axis=SP_AXIS, spmd_mesh=mesh
    )
    cfg_dense = dataclasses.replace(cfg, attn_impl="dense")
    tx = optax.adamw(1e-3)
    step, _loss = make_sp_step(cfg_run, mesh, tx, loss_chunk)

    model_init = tfm.Transformer(cfg_dense)
    tokens0 = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    param_shapes = jax.eval_shape(
        lambda t: model_init.init(jax.random.PRNGKey(0), t)["params"], tokens0
    )
    p_shard = transformer_param_shardings(param_shapes, mesh)
    params_in = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        param_shapes,
        p_shard,
    )
    opt_shapes = jax.eval_shape(tx.init, params_in)
    s_shard = transformer_param_shardings(
        param_shapes, mesh,
        fsdp=fsdp == "state", fsdp_axis=SP_AXIS,
    )
    opt_in = optax.tree_map_params(
        tx,
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        opt_shapes,
        s_shard,
    )
    seq_sh = NamedSharding(mesh, P(None, SP_AXIS))
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=seq_sh)
    msk = jax.ShapeDtypeStruct((batch, seq), jnp.float32, sharding=seq_sh)
    with mesh:
        compiled = step.lower(params_in, opt_in, tok, tok, msk).compile()
    ma = compiled.memory_analysis()
    n_params = sum(
        int(np.prod(s.shape)) for s in jax.tree.leaves(param_shapes)
    )
    out = {
        "n_body_params": n_params,
        "mesh": {SP_AXIS: int(mesh_shape[0]), MODEL_AXIS: int(mesh_shape[1])},
        "batch": batch,
        "seq": seq,
        "remat": remat,
        "scan_blocks": scan_blocks,
        "loss_chunk": loss_chunk,
        "fsdp": fsdp,
        "attn": "ring_spmd",
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
    }
    out["peak_bytes"] = peak_bytes_from_analysis(ma)
    out["fits_v5e"] = out["peak_bytes"] <= V5E_HBM_BYTES
    return out


def _compile_pp_step_aot(cfg, mesh, *, tp, n_micro, micro_batch, seq):
    """AOT-compile one ``make_pp_step`` train step from ShapeDtypeStructs.

    Shared PP harness for the pp-vs-dp and pp-x-tp feasibility checks:
    stage stack sharded by ``stage_sharding(tp=...)``, embed/head
    replicated (tp=False) or TP-sharded per the PS/Megatron rules
    (tp=True), and the adamw moment shardings PINNED to the params' —
    ``eval_shape`` drops shardings, and a multi-GB moment tree left to
    GSPMD's discretion could replicate, which would make the per-device
    verdicts depend on compiler whim.  Returns XLA's memory_analysis and
    the body-stack param count.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from parameter_server_tpu.parallel.pp import (
        PP_AXIS, make_pp_step, stage_sharding,
    )

    del Mesh  # mesh comes in ready-made
    n_stages = mesh.shape[PP_AXIS]
    step, _loss, stage_module, norm_module, tx = make_pp_step(
        cfg, mesh, learning_rate=1e-3, tp=tp
    )
    x0 = jnp.zeros((1, 8, cfg.d_model), jnp.float32)
    stage_shapes = jax.eval_shape(
        lambda k: jax.vmap(
            lambda kk: stage_module.init(kk, x0)["params"]
        )(k),
        jax.ShapeDtypeStruct((n_stages, 2), jnp.uint32),
    )
    st_shard = stage_sharding(mesh, stage_shapes, tp=tp)
    repl = NamedSharding(mesh, P())
    emb_sh = NamedSharding(mesh, P("model", None)) if tp else repl
    head_sh = NamedSharding(mesh, P(None, "model")) if tp else repl
    vocab, d_model = cfg.vocab_size, cfg.d_model
    pp_params = {
        "stages": jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            stage_shapes, st_shard,
        ),
        "embed": jax.ShapeDtypeStruct(
            (vocab, d_model), jnp.float32, sharding=emb_sh
        ),
        "head": jax.ShapeDtypeStruct(
            (d_model, vocab), jnp.float32, sharding=head_sh
        ),
        "norm": jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=repl),
            jax.eval_shape(
                lambda: norm_module.init(jax.random.PRNGKey(0), x0)["params"]
            ),
        ),
    }
    param_shardings = {
        "stages": st_shard,
        "embed": emb_sh,
        "head": head_sh,
        "norm": jax.tree.map(lambda _: repl, pp_params["norm"]),
    }
    pp_opt = optax.tree_map_params(
        tx,
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(tx.init, pp_params),
        param_shardings,
    )
    tok = jax.ShapeDtypeStruct(
        (n_micro, micro_batch, seq), jnp.int32,
        sharding=NamedSharding(mesh, P(PP_AXIS)),
    )
    with mesh:
        compiled = step.lower(pp_params, pp_opt, tok).compile()
    n_stack = sum(
        int(np.prod(s.shape)) for s in jax.tree.leaves(stage_shapes)
    )
    return compiled.memory_analysis(), n_stack


def pp_vs_dp_feasibility(
    *,
    n_stages: int = 4,
    n_micro: int = 8,
    micro_batch: int = 1,
    seq: int = 1024,
    vocab: int = 32_768,
    n_layers: int = 24,
    d_model: int = 2304,
    d_ff: int = 8064,
    n_heads: int = 18,
    n_kv_heads: int = 6,
) -> dict:
    """Where PP beats DP (VERDICT r4 #9): a body DP cannot hold at all.

    Pure DP replicates the FULL train state per device; for this ~1.8B
    fp32 model, params + adamw moments alone are ~29 GB — over a v5e
    chip's 16 GB at ANY batch size, so data parallelism is infeasible,
    best memory knobs (scan+remat+chunked loss) notwithstanding.  The
    same model pipelined over ``pp`` stages (``make_pp_step``, the real
    GPipe schedule) holds 1/S of the stack + replicated embed/head per
    device.  Both sides are AOT-compiled from ShapeDtypeStructs and
    judged by XLA's own memory analysis.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from parameter_server_tpu.models import transformer as tfm
    from parameter_server_tpu.parallel import mesh as mesh_lib
    from parameter_server_tpu.parallel.pp import (
        PP_AXIS, make_pp_step, stage_sharding,
    )

    cfg = tfm.TransformerConfig(
        vocab_size=vocab, n_layers=n_layers, n_heads=n_heads,
        n_kv_heads=n_kv_heads, d_model=d_model, d_ff=d_ff,
        max_seq=seq, remat=True, scan_blocks=True,
    )

    # -- DP side: the full model on ONE device, best memory knobs ----------
    mesh1 = mesh_lib.make_mesh((1, 1), devices=jax.devices()[:1])
    body = tfm.Transformer(cfg)
    tx = optax.adamw(1e-3)
    tokens0 = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    p_shapes = jax.eval_shape(
        lambda t: body.init(jax.random.PRNGKey(0), t)["params"], tokens0
    )
    params_in = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), p_shapes
    )
    opt_in = jax.eval_shape(tx.init, params_in)
    trunk = tfm.TransformerTrunk(cfg)

    def dp_loss(params, tokens):
        x = jnp.take(params["embedding"], tokens, axis=0)
        trunk_params = {
            k: v for k, v in params.items()
            if k not in ("embedding", "lm_head")
        }
        hidden = trunk.apply({"params": trunk_params}, x)
        return tfm.chunked_causal_lm_loss(
            hidden, params["lm_head"]["kernel"], tokens, 512
        )

    def dp_step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(dp_loss)(params, tokens)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    batch = n_micro * micro_batch  # same global tokens/step as the PP side
    tok_dp = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    with mesh1:
        dp_compiled = (
            jax.jit(dp_step, donate_argnums=(0, 1))
            .lower(params_in, opt_in, tok_dp)
            .compile()
        )
    dp_ma = dp_compiled.memory_analysis()
    dp_peak = peak_bytes_from_analysis(dp_ma)

    # -- PP side: the same model over pp stages (shared AOT harness;
    # rotary has no positional params; untied embed/head like the trainer)
    devices = np.asarray(jax.devices()[:n_stages])
    mesh_pp = Mesh(devices.reshape(n_stages), (PP_AXIS,))
    pp_ma, _n_stack = _compile_pp_step_aot(
        cfg, mesh_pp, tp=False, n_micro=n_micro,
        micro_batch=micro_batch, seq=seq,
    )
    pp_peak = peak_bytes_from_analysis(pp_ma)

    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(p_shapes))
    return {
        "n_params": n_params,
        "seq": seq,
        "global_batch": batch,
        "dp": {
            "devices": 1,
            "argument_bytes": int(dp_ma.argument_size_in_bytes),
            "temp_bytes": int(dp_ma.temp_size_in_bytes),
            "peak_bytes": dp_peak,
            "fits_v5e": dp_peak <= V5E_HBM_BYTES,
        },
        "pp": {
            "devices": n_stages,
            "n_micro": n_micro,
            "argument_bytes": int(pp_ma.argument_size_in_bytes),
            "temp_bytes": int(pp_ma.temp_size_in_bytes),
            "peak_bytes": pp_peak,
            "fits_v5e": pp_peak <= V5E_HBM_BYTES,
        },
        "pp_beats_dp": (pp_peak <= V5E_HBM_BYTES) and (dp_peak > V5E_HBM_BYTES),
    }


def pp_tp_feasibility(
    *,
    n_stages: int = 8,
    tp: int = 8,
    n_micro: int = 8,
    micro_batch: int = 1,
    seq: int = 2048,
    vocab: int = 32_000,
    n_layers: int = 48,
    d_model: int = 7168,
    d_ff: int = 19_456,
    n_heads: int = 56,
    n_kv_heads: int = 8,
) -> dict:
    """Depth x width: PP x TP for a body TP+FSDP alone cannot hold.

    The ~26B fp32-adamw LM here carries ~420 GB of train state — far over
    a v5e-16 even fully sharded; a (pp=8, model=8) v5e-64 mesh splits the
    stack 64 ways (``stage_sharding(tp=True)``: stage axis x the TP rules)
    while the microbatch pipeline keeps activations O(M/S) per device.
    AOT-compiled from ShapeDtypeStructs; XLA's own per-device verdict.
    Needs ``n_stages * tp`` virtual devices
    (``--xla_force_host_platform_device_count=64`` at the defaults).
    """
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from parameter_server_tpu.models import transformer as tfm
    from parameter_server_tpu.parallel.pp import PP_AXIS

    n_dev = n_stages * tp
    if len(jax.devices()) < n_dev:
        raise RuntimeError(
            f"pp_tp_feasibility needs {n_dev} devices (pp={n_stages} x "
            f"tp={tp}), have {len(jax.devices())} — set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_dev}"
        )
    cfg = tfm.TransformerConfig(
        vocab_size=vocab, n_layers=n_layers, n_heads=n_heads,
        n_kv_heads=n_kv_heads, d_model=d_model, d_ff=d_ff, max_seq=seq,
    )
    devices = np.asarray(jax.devices()[:n_dev])
    mesh = Mesh(devices.reshape(n_stages, tp), (PP_AXIS, "model"))
    ma, n_stack = _compile_pp_step_aot(
        cfg, mesh, tp=True, n_micro=n_micro,
        micro_batch=micro_batch, seq=seq,
    )
    n_params = n_stack + vocab * d_model * 2 + d_model  # + final norm scale
    out = {
        "n_params": n_params,
        "mesh": {"pp": n_stages, "model": tp},
        "devices": n_dev,
        "n_micro": n_micro,
        "micro_batch": micro_batch,
        "seq": seq,
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
        "generated_code_bytes": int(ma.generated_code_size_in_bytes),
    }
    out["peak_bytes"] = peak_bytes_from_analysis(ma)
    out["fits_v5e"] = out["peak_bytes"] <= V5E_HBM_BYTES
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--preset", default="llama3-8b",
                   choices=["llama3-8b", "llama3-8b-sp", "dlrm-1b",
                            "pp-vs-dp", "pp-tp-26b"])
    p.add_argument("--mesh", default=None,
                   help="data,model mesh shape (product = device count); "
                   "default 2,8 (llama3-8b) / 1,16 (dlrm-1b)")
    p.add_argument("--batch", type=int, default=None,
                   help="default 8 (llama3-8b) / 8192 (dlrm-1b)")
    # dlrm-1b knobs
    p.add_argument("--rows-log2", type=int, default=30)
    p.add_argument("--dim", type=int, default=16)
    p.add_argument("--slots-log2", type=int, default=18,
                   help="bucketed unique-slot count the step compiles for")
    p.add_argument("--optimizer", default="adagrad")
    p.add_argument("--seq", type=int, default=None,
               help="default 2048 (llama presets) / 1024 (pp-vs-dp)")
    p.add_argument("--remat", action=argparse.BooleanOptionalAction,
                   default=True)
    p.add_argument("--loss-chunk", type=int, default=512,
                   help="0 = full logits; >0 = fused-head chunked loss")
    p.add_argument("--fsdp", default="state",
                   choices=["none", "full", "state"],
                   help="data-axis sharding of train state: none, full "
                   "(params+moments), state (moments only — the one whose "
                   "saving survives the scan, see body_train_step_memory)")
    p.add_argument("--scan-blocks", action=argparse.BooleanOptionalAction,
                   default=True)
    p.add_argument("--dtype", default=None, help="e.g. bfloat16")
    args = p.parse_args(argv)
    if args.preset in ("pp-tp-26b", "pp-vs-dp"):
        # these presets expose ONLY --seq; silently computing a fixed
        # config while echoing back a user's other knobs would label
        # numbers with a configuration that was never compiled
        ignored = {
            "--mesh": args.mesh, "--batch": args.batch, "--dtype": args.dtype
        }
        bad = [k for k, v in ignored.items() if v is not None]
        if bad:
            p.error(
                f"--preset {args.preset} supports only --seq; got {bad} "
                "(edit the feasibility function's keywords for other shapes)"
            )
    if args.preset == "pp-tp-26b":
        result = pp_tp_feasibility(
            seq=args.seq if args.seq is not None else 2048
        )
    elif args.preset == "pp-vs-dp":
        result = pp_vs_dp_feasibility(
            seq=args.seq if args.seq is not None else 1024
        )
    elif args.preset == "llama3-8b-sp":
        result = sp_8b_feasibility(
            mesh_shape=tuple(
                int(x) for x in (args.mesh or "2,8").split(",")
            ),
            batch=args.batch if args.batch is not None else 1,
            seq=args.seq if args.seq is not None else 2048,
            remat=args.remat,
            loss_chunk=args.loss_chunk,
            fsdp=args.fsdp,  # sp_8b_feasibility raises on "full" itself
            scan_blocks=args.scan_blocks,
            dtype=args.dtype,
        )
    elif args.preset == "dlrm-1b":
        result = dlrm_feasibility(
            rows_log2=args.rows_log2,
            dim=args.dim,
            mesh_shape=tuple(
                int(x) for x in (args.mesh or "1,16").split(",")
            ),
            batch=args.batch if args.batch is not None else 8192,
            slots_log2=args.slots_log2,
            optimizer=args.optimizer,
        )
    else:
        result = llama3_8b_feasibility(
            mesh_shape=tuple(
                int(x) for x in (args.mesh or "2,8").split(",")
            ),
            batch=args.batch if args.batch is not None else 8,
            seq=args.seq if args.seq is not None else 2048,
            remat=args.remat,
            loss_chunk=args.loss_chunk,
            fsdp=args.fsdp,
            scan_blocks=args.scan_blocks,
            dtype=args.dtype,
        )
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
