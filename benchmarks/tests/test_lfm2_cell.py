"""The cell ``lfm2_8b_a1b.pretrain8k``: its configuration file against the
catalog row's keys, ``lfm2_flops`` against hand counts, ``model_scopes`` on
a made-up account, what the gradient check refuses, and the command's dry run
(CPU, tiny sizes, float32: the reference comparison there holds to 1e-4 /
1e-3)."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmarks.harness import cell as cell_lib
from benchmarks.harness import lfm2_flops, model_scopes
from benchmarks.harness.peaks import bounds_s

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "lfm2_8b_a1b.pretrain8k"
CFG = json.load(open(os.path.join(ROOT, "benchmarks/configs/lfm2_8b_a1b.json")))
#: config.json's keys as the catalog's row holds them, value for value: a
#: width that moved here is a different model
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_dense_layers": 2,
    "num_experts": 32, "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
    "layer_types": [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv", "full_attention", "conv",
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "full_attention", "conv", "conv",
    ],
}
PEAKS = {"flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_file_holds_the_row_s_keys_and_states_its_cut():
    for k, v in PUBLISHED.items():
        assert CFG[k] == v and type(CFG[k]) is type(v), k
    entry = next(c for c in BENCH["configs"] if c["name"] == "lfm2_8b_a1b")
    assert entry["reduced"] == CFG["reduced"] == ["n_layers", "experts_held", "vocab_rows"]
    assert entry["source"] == CFG["source"]
    assert entry["file"] == "benchmarks/configs/lfm2_8b_a1b.json"
    assert (CFG["n_layers"], CFG["layers_first"], CFG["experts_held"],
            CFG["experts_first"], CFG["vocab_rows"]) == (5, 1, 8, 0, 16384)
    assert CFG["rows_per_chip"] == CFG["vocab_rows"]
    assert CFG["table"]["dim"] == CFG["hidden_size"]
    assert CFG["table"]["localizer"] == "identity"
    assert set(CFG["reduced_why"]) == set(CFG["reduced"])
    assert "Four chips share each layer" in CFG["deployment"]
    assert CFG["generator_params"] == {"sequences": 2, "zipf_a": 1.05}
    for said in ("head_dim", "rotary", "head", "expert_bias", "init", "traffic"):
        assert said in CFG["assumed"], said
    kimi = json.load(open(os.path.join(ROOT, "benchmarks/configs/kimi_linear_a3b.json")))
    assert CFG["guarantees"] == kimi["guarantees"]


def test_the_cell_reports_all_four_end_to_end_metrics_on_one_chip():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "skew"
    assert [e["name"] for e in BENCH["end_to_end"]
            if cell_lib.reports(BENCH, CELL, e["name"])] == [
        "examples_per_s", "step_ms_p50", "step_ms_p95", "setup_s"
    ]
    run = cell_lib.resolve(BENCH, CELL, seed=1, seconds=1.0, trace=0, dry_run=False)
    assert run.sizes == {"workers": 1, "servers": 2, "rows": 16384,
                         "batch": 16384, "cycle": 32, "warmup": 32}
    # its own entries: the body's, with its two kernels (PR 39)
    assert {m["name"] for m in cell_lib.layer_metrics_for(run) if "workloads" in m} == {
        "mfu_pct", "body_ms_p50", "moe_experts_ms", "moe_experts_roofline",
        "moe_load_max_over_mean", "hybrid_pull_wait_ms_p50",
        "short_conv_ms", "short_conv_roofline", "gqa_attn_ms", "gqa_attn_roofline",
    }


# -- lfm2_flops against hand counts ------------------------------------------------
def test_layer_kinds_are_one_dense_layer_and_one_period():
    assert lfm2_flops.layer_kinds(CFG) == [
        ("conv", "dense"), ("gqa", "experts"), ("conv", "experts"),
        ("conv", "experts"), ("conv", "experts"),
    ]


def test_active_parameters_by_hand():
    D = 2048
    conv = 3 * D * D + D * D
    gqa = D * 32 * 64 + 2 * D * 8 * 64 + 32 * 64 * D
    expert = 3 * D * 1792
    routed = D * 32 + expert * 4 * 8 / 32
    want = 4 * conv + gqa + 3 * D * 7168 + 4 * routed + D * 16384
    assert lfm2_flops.mixer_params(CFG) == {"conv": conv, "gqa": gqa}
    assert lfm2_flops.active_matrix_params(CFG) == want
    assert abs(want - 199.5e6) < 0.1e6  # ISSUE 33's 199.5 M


@pytest.mark.parametrize("what,flops,bytes_", [
    # 4 conv layers x 16384 tokens: two products of 12.6 M and 4.2 M
    # parameters and 7 operations a channel, forward + backward; 24 rows of
    # 2048 floats a token and the weights three times
    ("short_conv", 4 * 16384 * 3 * (2 * 16777216 + 7 * 2048),
     4 * 4 * (24 * 2048 * 16384 + 3 * (16777216 + 3 * 2048))),
    # 1 layer x 2 sequences x 32 query heads x the causal half x (64 + 64) x 2;
    # q, o and gradients of 32 heads, k, v and gradients of 8
    ("gqa_attn", 3 * 2 * (8192 * 8193 // 2) * 128 * 32 * 2,
     4 * 8192 * 64 * (4 * 32 + 4 * 8) * 2),
    # 3 matrices of 2048 x 1792 a slot, forward + backward; 32 held experts'
    # weights three times, a slot's row four times
    ("moe_experts", 3 * 2 * 3 * 2048 * 1792 * 16000,
     4 * (3 * 3 * 2048 * 1792 * 32 + 4 * 2048 * 16000)),
])
def test_kernel_operations_and_bytes_by_hand(what, flops, bytes_):
    got = {
        "short_conv": lambda: lfm2_flops.short_conv(CFG, 16384),
        "gqa_attn": lambda: lfm2_flops.gqa_attn(CFG, 2, 8192),
        "moe_experts": lambda: lfm2_flops.moe_experts(CFG, 16000),
    }[what]()
    assert got == {"flops": flops, "bytes": bytes_}


def test_a_step_is_21_tflop_and_a_kernel_has_two_bounds():
    flops = lfm2_flops.step_flops(CFG, 2, 8192)
    assert abs(flops - 21.26e12) < 0.01e12
    assert bounds_s({"flops": 197e12, "bytes": 819e9 / 2}, PEAKS) == {
        "flops": 1.0, "bytes": 0.5
    }
    # the whole conv mixer is bound by its products, the experts at a
    # quarter of their share by their weights' bytes
    conv = bounds_s(lfm2_flops.short_conv(CFG, 16384), PEAKS)
    experts = bounds_s(lfm2_flops.moe_experts(CFG, 16384), PEAKS)
    assert conv["flops"] > conv["bytes"] and experts["bytes"] > experts["flops"]


def _account(**scope_s):
    return types.SimpleNamespace(
        window=(0.0, 2.0), window_s=2.0, path="",
        durations_ms=lambda n: [3.0, 5.0], scope_s=scope_s,
    )


def test_lfm2_scopes_reads_a_step_s_shares_from_an_account(monkeypatch):
    # ten steps of 0.5 s; a traced window of 2 s holds four of them
    steps = [(0.5 * i, 0.5 * (i + 1), True) for i in range(10)]
    acc = _account(**{
        "ps.model.lfm2": 1.9, "ps.model.conv.proj": 0.2, "ps.model.conv.gate": 0.1,
        "ps.model.conv.out": 0.1, "ps.model.gqa.attn": 0.6,
        "ps.model.moe.experts": 0.05, "ps.model.moe.dispatch": 0.04,
        "ps.model.moe.combine": 0.03,
    })
    monkeypatch.setattr(model_scopes, "step_program_ms", lambda acc: [470.0, 480.0, 490.0])
    moe = {"held_slots_mean": 16000.0, "load_max_over_mean_p50": 1.4}
    # the window's rate: 2 steps of 16,384 positions a second
    out = model_scopes.report(acc, steps, CFG, 16384, PEAKS, moe, 32768.0)
    assert out["steps_per_s"] == 2.0 and out["body_ms"] == 475.0
    mfu = 100 * lfm2_flops.step_flops(CFG, 2, 8192) * 2 / 197e12
    assert abs(out["mfu_pct"] - mfu) < 1e-9 and 20 < mfu < 25
    assert out["body_ms_p50"] == 480.0
    assert out["short_conv_ms"] == 100.0  # proj + gate + out
    assert out["gqa_attn_ms"] == 150.0
    assert abs(out["moe_experts_ms"] - 30.0) < 1e-9  # dispatch + experts + combine
    assert out["short_conv_bound"] == "flops" and out["moe_experts_bound"] == "bytes"
    assert abs(out["gqa_attn_roofline"] - 100 * (
        lfm2_flops.gqa_attn(CFG, 2, 8192)["flops"] / 197e12) / 0.15) < 1e-9
    assert 0 < out["short_conv_roofline"] < 100 and 0 < out["moe_experts_roofline"] < 100
    assert out["moe_load_max_over_mean"] == 1.4
    assert out["hybrid_pull_wait_ms_p50"] == 4.0
    # without the driver's counts the experts' roofline is left out, not guessed
    bare = model_scopes.report(acc, steps, CFG, 16384, PEAKS)
    assert "moe_experts_roofline" not in bare and "moe_experts_ms" in bare
    # a program without these scopes (the parent) gives nothing, and no error
    assert model_scopes.report(_account(), steps[:1], CFG, 16384, PEAKS) == {}
    nothing = model_scopes.report(_account(), steps, CFG, 16384, PEAKS)
    assert "mfu_pct" not in nothing and "short_conv_ms" not in nothing


def test_a_share_over_100_is_an_error(monkeypatch):
    steps = [(0.5 * i, 0.5 * (i + 1), True) for i in range(10)]
    monkeypatch.setattr(model_scopes, "step_program_ms", lambda acc: [])
    acc = _account(**{"ps.model.lfm2": 1.9, "ps.model.gqa.attn": 0.02})
    out = model_scopes.report(acc, steps, CFG, 16384, PEAKS)
    (fail,) = model_scopes.above_100(out)
    assert "gqa_attn_roofline" in fail
    reader = cell_lib.load_module("layer_metrics", "gqa_attn_roofline")
    assert reader.check(out["gqa_attn_roofline"]) == [fail]


# -- what the comparison that decides ``correct`` refuses ----------------------------
def _copy(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(jnp.copy, tree)  # the step donates what it is given


def _frozen_leaf(step, path):
    """The step with one parameter leaf handed back as it came."""
    def broken(params, opt_state, emb, tok):
        node = params
        for key in path[:-1]:
            node = node[key]
        keep = _copy(node[path[-1]])
        params, opt_state, loss, g_emb, counters = step(params, opt_state, emb, tok)
        node = params
        for key in path[:-1]:
            node = node[key]
        node[path[-1]] = keep
        return params, opt_state, loss, g_emb, counters
    return broken


def _dropped_bias(step):
    """The step run as if the selection bias were not there."""
    import jax
    import jax.numpy as jnp

    def broken(params, opt_state, emb, tok):
        bare = jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.zeros_like(x)
            if path[-1].key == "expert_bias" else x, params,
        )
        biases = _copy(params)
        out, opt_state, loss, g_emb, counters = step(bare, opt_state, emb, tok)
        out = jax.tree_util.tree_map_with_path(
            lambda path, x, b: b if path[-1].key == "expert_bias" else x,
            out, biases,
        )
        return out, opt_state, loss, g_emb, counters
    return broken


def _decayed_bias(step):
    """The step with AdamW's weight decay let onto the selection bias."""
    import jax

    def broken(params, opt_state, emb, tok):
        out, opt_state, loss, g_emb, counters = step(params, opt_state, emb, tok)
        out = jax.tree_util.tree_map_with_path(
            lambda path, x: x * (1.0 - 1e-4) if path[-1].key == "expert_bias" else x,
            out,
        )
        return out, opt_state, loss, g_emb, counters
    return broken


@pytest.mark.parametrize("how,word", [
    ("sound", None),
    ("a_frozen_leaf", "parameters' change"),
    ("another_rate", "parameters' change"),
    ("a_dropped_bias", "embedding gradients"),
    ("a_decayed_bias", "buffer"),
    ("a_used_state", "not fresh"),
    ("a_tighter_quartile", "first-quartile position"),
])
def test_the_gradient_check_refuses_a_step_that_does_not_train(how, word):
    """At the dry-run sizes, in this process, under a seeded non-zero
    selection bias: the sound step passes every limit of the file's
    ``dry_run.grad_check``; a leaf the step leaves as it was, an update at
    twice the stated rate, a step that routes without the bias, a bias that
    weight decay moved and a state that has stepped before are refused, and
    so is a sound step whose first-quartile position reads over its limit."""
    import jax

    run = cell_lib.resolve(BENCH, CELL, seed=3000000011, seconds=1.0, trace=0,
                           dry_run=True)
    drv = cell_lib.load_module("drivers", "hybrid_lfm2").Driver(run)
    drv.setup()
    try:
        tr = drv.trainer
        tr.params = jax.tree_util.tree_map_with_path(
            lambda path, x: 0.05 * jax.random.normal(jax.random.PRNGKey(3), x.shape)
            if path[-1].key == "expert_bias" else x, tr.params,
        )
        if how == "a_frozen_leaf":
            tr._step = _frozen_leaf(tr._step, ("layer_0", "conv", "taps"))
        elif how == "another_rate":
            drv.learning_rate *= 2.0  # the file states a rate the step does not take
        elif how == "a_dropped_bias":
            tr._step = _dropped_bias(tr._step)
        elif how == "a_decayed_bias":
            tr._step = _decayed_bias(tr._step)
        elif how == "a_used_state":
            tr.step(drv.batches[0][0])
            tr.drain()
        elif how == "a_tighter_quartile":  # the limit that tells a precision
            run.config["dry_run"]["grad_check"]["own_p25"] = 1e-9
        fails = drv.grad_check()
        if word is None:
            assert fails == []
        else:
            assert fails and any(word in f for f in fails), fails
    finally:
        drv.close()


# -- the command, dry -------------------------------------------------------------
def dry(tmp_path, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--dry-run", "--seconds", "1.5",
         "--seed", "3000000007", *extra],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=500,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_the_dry_run_is_correct_and_traced_reports_the_hybrid_spans(tmp_path):
    out, err = dry(tmp_path)
    assert out["correct"] is True, err[-3000:]
    assert set(out["metrics"]) == {
        "examples_per_s", "step_ms_p50", "step_ms_p95", "setup_s"
    }
    check = json.loads(err.split("[grad_check] ")[-1].splitlines()[0])
    assert check["worst"] < 1e-4 and check["loss"] < 1e-5
    assert check["own_p25"] < check["own_median"] < 1e-5
    limits = CFG["dry_run"]["grad_check"]
    assert set(check["leaves"]) == set(limits["leaves"])
    assert max(check["leaves"].values()) < 1e-4 and check["update"] < 1e-3
    assert check["buffers"] == 2 and check["counters"]["moe_dropped_slots"] == 0
    assert '"dropped_slots": 0' in err.split("[moe] ")[-1].splitlines()[0]
    assert "'conv', 'dense'" in err and "'gqa', 'experts'" in err
    traced, err = dry(tmp_path, "--trace", "1")
    assert traced["correct"] is True, err[-3000:]
    # the readers find the worker's, the servers' and the trainer's spans
    # and the driver's counts here; the device's scopes and shares only a
    # chip's trace holds
    for name in ("pull_ms_p50", "grad_ms_p50", "push_ms_p50",
                 "worker_localize_ms_p50", "server_pull_busy_ms_p50",
                 "server_push_busy_ms_p50", "compiles_in_window",
                 "server_localize_ms_p50", "server_ack_ms_p50",
                 "server_self_ms_p50", "worker_submit_ms_p50",
                 "worker_combine_ms_p50", "worker_assemble_ms_p50",
                 "hybrid_pull_wait_ms_p50", "moe_load_max_over_mean"):
        assert name in traced["metrics"], name
    assert traced["metrics"]["turn_wait_ms_p50"]["value"] == 0.0  # no controller
    assert "mfu_pct" not in traced["metrics"]  # no chip, no peak
    for span in ("ps.hybrid.step", "ps.hybrid.pull_wait", "ps.hybrid.push_submit",
                 "ps.hybrid.prefetch", "ps.hybrid.body_dispatch"):
        assert span in err, span
