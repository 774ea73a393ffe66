"""The cell ``sdar_30b_a3b.blockdiff4k``: its configuration file against the
catalog row's keys, ``sdar_flops`` against hand counts (the ``L^2 + L b``
pairs), ``model_scopes`` on a made-up account, the generator's shapes and
mask share, what the gradient check refuses, and the command's dry run
(CPU, tiny sizes, float32: the reference comparison there holds to 1e-4 /
1e-3)."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmarks.harness import cell as cell_lib
from benchmarks.harness import model_scopes, sdar_flops
from benchmarks.harness.peaks import bounds_s

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "sdar_30b_a3b.blockdiff4k"
CFG = json.load(open(os.path.join(ROOT, "benchmarks/configs/sdar_30b_a3b.json")))
#: config.json's keys as the catalog's row holds them, value for value: a
#: width that moved here is a different model
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe", "moe_intermediate_size": 768,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}
PEAKS = {"flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_file_holds_the_row_s_keys_and_states_its_cut():
    for k, v in PUBLISHED.items():
        assert CFG[k] == v and type(CFG[k]) is type(v), k
    entry = next(c for c in BENCH["configs"] if c["name"] == "sdar_30b_a3b")
    assert entry["reduced"] == CFG["reduced"] == ["n_layers", "experts_held", "vocab_rows"]
    assert entry["source"] == CFG["source"]
    assert entry["file"] == "benchmarks/configs/sdar_30b_a3b.json"
    assert (CFG["n_layers"], CFG["layers_first"], CFG["experts_held"],
            CFG["experts_first"], CFG["vocab_rows"]) == (6, 0, 16, 0, 18992)
    assert CFG["vocab_rows"] * 8 == CFG["vocab_size"]
    assert CFG["rows_per_chip"] == CFG["vocab_rows"]
    assert CFG["table"]["dim"] == CFG["hidden_size"]
    assert CFG["table"]["localizer"] == "identity"
    assert set(CFG["reduced_why"]) == set(CFG["reduced"])
    assert "Eight chips share each layer" in CFG["deployment"]
    assert "expert parallel 8 x data parallel 8" in CFG["deployment"]
    assert CFG["generator"] == "lm_blockdiff"
    assert CFG["generator_params"] == {"sequences": 2, "zipf_a": 1.05, "block_length": 4}
    assert CFG["batch_per_worker"] == 8192
    assert CFG["model"]["attn_block"] % CFG["generator_params"]["block_length"] == 0
    for said in ("block_length", "noise_schedule", "loss", "positions", "qk_norm",
                 "router", "mlp", "mask_token", "head", "init", "optimizers",
                 "traffic", "schedule"):
        assert said in CFG["assumed"], said
    laguna = json.load(open(os.path.join(ROOT, "benchmarks/configs/laguna_xs2.json")))
    assert CFG["guarantees"] == laguna["guarantees"]
    assert CFG["consistency"] == laguna["consistency"] == {"mode": "ssp", "max_delay": 1}
    assert CFG["compute_precision"].startswith(laguna["compute_precision"][:200])
    # the comparison reads what this body adds: a head norm, the router, the
    # held experts' gate, the head; the driver names the leaves, the file
    # their limits
    drv = cell_lib.load_module("drivers", "hybrid_sdar")
    for group in (CFG["grad_check"], CFG["dry_run"]["grad_check"]):
        assert set(group["leaves"]) == set(drv.Driver.leaves)
    for part in ("q_norm", "router", "experts/gate", "lm_head"):
        assert any(part in p for p in drv.Driver.leaves), part


def test_the_cell_reports_all_four_end_to_end_metrics_on_one_chip():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "skew"
    assert [e["name"] for e in BENCH["end_to_end"]
            if cell_lib.reports(BENCH, CELL, e["name"])] == [
        "examples_per_s", "step_ms_p50", "step_ms_p95", "setup_s"
    ]
    run = cell_lib.resolve(BENCH, CELL, seed=1, seconds=1.0, trace=0, dry_run=False)
    assert run.sizes == {"workers": 1, "servers": 2, "rows": 18992,
                         "batch": 8192, "cycle": 32, "warmup": 32}
    assert {m["name"] for m in cell_lib.layer_metrics_for(run) if "workloads" in m} == {
        "mfu_pct", "body_ms_p50", "moe_experts_ms", "moe_experts_roofline",
        "moe_load_max_over_mean", "hybrid_pull_wait_ms_p50",
        "bd_attn_ms", "bd_attn_roofline",
    }


# -- the generator ----------------------------------------------------------------------
def test_the_generator_gives_masked_copies_beside_clean_sequences():
    gen = cell_lib.load_module("generators", "lm_blockdiff")
    mix = json.load(open(os.path.join(ROOT, "benchmarks/traffic/skew.json")))
    params = dict(CFG["generator_params"], key_space=18992)
    a = gen.make(params, mix, seed=3000000011, n_workers=1, cycle=4, batch=8192)
    b = gen.make(params, mix, seed=5, n_workers=1, cycle=4, batch=8192)
    pool = a[0]
    assert len(pool) == 4 and all(t.shape == (2, 8192) and t.dtype == np.int32 for t in pool)
    # the pool is the mix's, the order the seed's
    assert sorted(t.tobytes() for t in a[0]) == sorted(t.tobytes() for t in b[0])
    for t in pool:
        xt, x0 = t[:, :4096], t[:, 4096:]
        assert x0.max() < 18991 and x0.min() >= 0  # the mask row is never drawn clean
        masked = xt == 18991
        assert (xt[~masked] == x0[~masked]).all()
        m = masked.reshape(2, 1024, 4).sum(-1)
        assert m.min() == 1 and m.max() == 4
        # E[m] / b = 2.5 / 4: 5,120 of a step's 16,384 body positions
        assert abs(masked.mean() - 0.625) < 0.02
        assert abs(masked.sum() - 5120) < 160
    # the mask row is the hottest key the PS plane sees; the Zipf head ~5 %
    counts = np.bincount(pool[0].ravel(), minlength=18992)
    assert counts.argmax() == 18991 and counts[18991] / pool[0].size > 0.3
    assert np.sort(counts[:18991])[-1] / pool[0].size < 0.06
    with pytest.raises(ValueError, match="whole blocks"):
        gen.make(params, mix, seed=1, n_workers=1, cycle=1, batch=2 * 4098)


# -- sdar_flops against hand counts ------------------------------------------------
def test_active_parameters_by_hand():
    D, K = 2048, 128
    attn = 2 * D * 32 * K + 2 * D * 4 * K
    expert = 3 * D * 768
    per_layer = attn + D * 128 + expert * 8 * 16 / 128  # router, a slot's experts
    assert sdar_flops.attn_params(CFG) == attn == 18_874_368
    assert sdar_flops.body_matrix_params(CFG) == 6 * per_layer
    assert abs(6 * per_layer - 143.13e6) < 0.01e6


def test_kernel_operations_and_bytes_by_hand():
    # 6 layers x 2 sequences x 32 query heads x (4096^2 + 4096 x 4) pairs x
    # (128 + 128) x 2, forward + backward; q, o and gradients of 32 heads, k,
    # v and gradients of 4, over 8,192 body positions
    pairs = 4096 * 4096 + 4096 * 4
    assert sdar_flops.pairs(4096, 4) == pairs
    assert sdar_flops.bd_attn(CFG, 2, 4096) == {
        "flops": 6 * 3 * 2 * pairs * 2 * 128 * 32 * 2,
        "bytes": 6 * 4 * 8192 * 128 * (4 * 32 + 4 * 4) * 2,
    }
    # half of a causal pass over 2L
    assert abs(pairs / (8192 * 8193 / 2) - 0.5) < 0.002
    # 3 matrices of 2048 x 768 a slot, forward + backward; 6 x 16 held
    # experts' weights three times, a slot's row four times
    assert sdar_flops.moe_experts(CFG, 98304) == {
        "flops": 3 * 2 * 3 * 2048 * 768 * 98304,
        "bytes": 4 * (3 * 3 * 2048 * 768 * 96 + 4 * 2048 * 98304),
    }


def test_a_step_is_26_tflop_and_attention_is_two_fifths():
    flops = sdar_flops.step_flops(CFG, 2, 4096)
    body = 6 * sdar_flops.body_matrix_params(CFG) * 16384
    head = 6 * 2048 * 18992 * 8192
    attn = sdar_flops.bd_attn(CFG, 2, 4096)["flops"]
    assert flops == body + head + attn
    assert abs(body - 14.07e12) < 0.01e12 and abs(head - 1.91e12) < 0.01e12
    assert abs(attn - 9.90e12) < 0.01e12 and abs(flops - 25.88e12) < 0.01e12
    # attention is bound by its operations, and so are the experts at
    # 1,024 slots an expert (14.1 ms of products against 10.6 of bytes)
    b = bounds_s(sdar_flops.bd_attn(CFG, 2, 4096), PEAKS)
    assert b["flops"] > b["bytes"]
    experts = bounds_s(sdar_flops.moe_experts(CFG, 98304), PEAKS)
    assert experts["flops"] > experts["bytes"] > 0.7 * experts["flops"]


def _account(**scope_s):
    return types.SimpleNamespace(
        window=(0.0, 2.4), window_s=2.4, path="",
        durations_ms=lambda n: [3.0, 5.0], scope_s=scope_s,
    )


def test_model_scopes_reads_a_step_s_shares_from_an_account(monkeypatch):
    # ten steps of 1.2 s; a traced window of 2.4 s holds two of them
    steps = [(1.2 * i, 1.2 * (i + 1), True) for i in range(10)]
    acc = _account(**{
        "ps.model.sdar": 2.3, "ps.model.attn.bd": 1.2, "ps.model.attn.proj": 0.3,
        "ps.model.moe.experts": 0.1, "ps.model.moe.dispatch": 0.08,
        "ps.model.moe.combine": 0.06,
    })
    monkeypatch.setattr(model_scopes, "step_program_ms", lambda acc: [1140.0, 1150.0, 1160.0])
    moe = {"held_slots_mean": 98304.0, "load_max_over_mean_p50": 1.3}
    # the window's rate: a step of 8,192 clean positions in 1.2 s
    out = model_scopes.report(acc, steps, CFG, 8192, PEAKS, moe, 8192 / 1.2)
    mfu = 100 * sdar_flops.step_flops(CFG, 2, 4096) / 197e12 / 1.2
    assert abs(out["mfu_pct"] - mfu) < 1e-9 and 10 < mfu < 12
    assert out["body_ms_p50"] == 1150.0
    # a layer: 600 ms of block-diffusion attention a step over 6 layers
    assert abs(out["bd_attn_ms"] - 100.0) < 1e-6
    assert out["bd_attn_bound"] == "flops"
    assert abs(out["bd_attn_roofline"] - 100 * (
        sdar_flops.bd_attn(CFG, 2, 4096)["flops"] / 197e12) / 0.6) < 1e-9
    assert abs(out["moe_experts_ms"] - 120.0) < 1e-6
    assert 0 < out["moe_experts_roofline"] < 100
    assert out["moe_load_max_over_mean"] == 1.3
    assert out["hybrid_pull_wait_ms_p50"] == 4.0
    # a program without these scopes (the parent) gives nothing, and no error
    assert model_scopes.report(_account(), steps[:1], CFG, 8192, PEAKS) == {}
    nothing = model_scopes.report(_account(), steps, CFG, 8192, PEAKS)
    assert "bd_attn_ms" not in nothing and "mfu_pct" not in nothing
    reader = cell_lib.load_module("layer_metrics", "bd_attn_roofline")
    assert reader.check(50.0) == [] and reader.check(120.0)


# -- what the comparison that decides ``correct`` refuses ----------------------------
@pytest.fixture(scope="module")
def checked():
    """One dry-run cluster and trainer for every case below: a case hands
    the trainer its first parameters and a fresh optimizer state again, or,
    where it changes the body, a trainer of its own."""
    import jax
    import jax.numpy as jnp

    run = cell_lib.resolve(BENCH, CELL, seed=3000000011, seconds=1.0, trace=0,
                           dry_run=True)
    drv = cell_lib.load_module("drivers", "hybrid_sdar").Driver(run)
    drv.setup()
    first = jax.tree.map(jnp.copy, (drv.trainer.params, drv.trainer.opt_state))
    yield drv, drv.trainer, first
    drv.close()


@pytest.mark.parametrize("how,word", [
    ("sound", None),
    ("a_next_token_shift", "loss"),
    ("a_miscounted_mask", "bd_masked_positions"),
    ("another_rate", "parameters' change"),
    ("a_combine_that_keeps_one_duplicate", "mask row"),
])
def test_the_gradient_check_refuses_a_step_that_does_not_train(how, word, checked,
                                                               monkeypatch):
    """At the dry-run sizes, in this process: the sound step passes every
    limit of the file's ``dry_run.grad_check``; the loss read one position
    on, a step that miscounts the masked positions, an update at twice the
    stated rate and a combine that keeps one of the mask row's duplicates
    where it should sum them are refused.  (``xt`` queries that also see
    their own block's ``x0`` keys and a sigmoid router are held against the
    reference in ``tests/test_sdar_moe.py``, and against the cell's limits
    on the chip.)"""
    import jax
    import jax.numpy as jnp

    from parameter_server_tpu.kv import worker as kv_worker
    from parameter_server_tpu.learner.hybrid import HybridLMTrainer
    from parameter_server_tpu.models import sdar_moe
    from parameter_server_tpu.parallel import mesh as mesh_lib

    drv, base, first = checked
    if how == "a_next_token_shift":  # a new step program: a trainer of its own
        targets = sdar_moe.diffusion_targets
        monkeypatch.setattr(sdar_moe, "diffusion_targets", lambda cfg, t: (
            lambda x0, w, m: (jnp.roll(x0, -1, axis=1), w, m)
        )(*targets(cfg, t)))
        drv.trainer = HybridLMTrainer(
            drv.model, mesh_lib.make_mesh((1, 1), devices=[drv.kv.device]), drv.kv,
            table=drv.table.name, learning_rate=drv.peak_rate,
            warmup_steps=drv.warmup_steps, max_delay=1, seed=11,
            loss_chunk=drv.loss_chunk,
        )
    else:
        base.params, base.opt_state = jax.tree.map(jnp.copy, first)
    if how == "another_rate":  # the file states a rate the step does not take
        monkeypatch.setattr(drv, "learning_rate", drv.learning_rate * 2.0)
    elif how == "a_miscounted_mask":
        step = base._step

        def miscounting(*args):
            *out, counters = step(*args)
            return (*out, dict(counters, bd_masked_positions=0))
        monkeypatch.setattr(base, "_step", miscounting)
    elif how == "a_combine_that_keeps_one_duplicate":
        monkeypatch.setattr(kv_worker, "_segment_combine", jax.jit(
            lambda inverse, v, n: jnp.zeros((n, v.shape[-1]), v.dtype).at[inverse].set(v),
            static_argnums=2,
        ))
    try:
        fails = drv.grad_check()
    finally:
        drv.trainer = base
    if word is None:
        assert fails == []
    else:
        assert fails and any(word in f for f in fails), fails


# -- the command, dry -------------------------------------------------------------
def test_the_traced_dry_run_is_correct_and_reports_the_body(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))

    def dry(*extra):
        p = subprocess.run(
            [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
             "--workload", CELL, "--dry-run", "--seconds", "1.5",
             "--seed", "3000000007", *extra],
            capture_output=True, text=True, env=env, cwd=ROOT, timeout=500,
        )
        assert p.returncode == 0, p.stderr[-3000:]
        return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr

    out, err = dry("--trace", "1")
    assert out["correct"] is True, err[-3000:]
    check = json.loads(err.split("[grad_check] ")[-1].splitlines()[0])
    assert check["worst"] < 1e-4 and check["loss"] < 1e-5
    assert check["own_p25"] < check["own_median"] < 1e-5
    assert max(check["leaves"].values()) < 1e-4 and check["update"] < 1e-3
    assert check["mask_row"] < 1e-5
    assert check["counters"]["moe_dropped_slots"] == 0
    assert check["counters"]["bd_masked_positions"] > 0
    bd = json.loads(err.split("[bd] ")[-1].splitlines()[0])
    assert bd["masked_positions_min"] > 0
    assert "('bd', 'experts')" in err
    for name in ("pull_ms_p50", "grad_ms_p50", "push_ms_p50",
                 "hybrid_pull_wait_ms_p50", "moe_load_max_over_mean"):
        assert name in out["metrics"], name
    assert "mfu_pct" not in out["metrics"]  # no chip, no peak
