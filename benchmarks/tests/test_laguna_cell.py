"""The cell ``laguna_xs2.pretrain8k``: its configuration file against the
catalog row's keys, ``laguna_flops`` against hand counts, ``model_scopes``
on a made-up account, what the gradient check refuses, and the command's dry
run (CPU, tiny sizes, float32: the reference comparison there holds to 1e-4
/ 1e-3)."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmarks.harness import cell as cell_lib
from benchmarks.harness import laguna_flops, model_scopes
from benchmarks.harness.peaks import bounds_s

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "laguna_xs2.pretrain8k"
CFG = json.load(open(os.path.join(ROOT, "benchmarks/configs/laguna_xs2.json")))
#: config.json's keys as the catalog's row holds them, value for value: a
#: width that moved here is a different model
PUBLISHED = {
    "model_type": "laguna", "vocab_size": 100352, "hidden_size": 2048,
    "intermediate_size": 8192, "num_hidden_layers": 40,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5,
        },
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1,
        },
        "original_max_position_embeddings": 4096,
    },
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3,
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48, 64, 64, 64],
}
PUBLISHED["layer_types"] *= 10
PUBLISHED["num_attention_heads_per_layer"] *= 10
PEAKS = {"flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_file_holds_the_row_s_keys_and_states_its_cut():
    for k, v in PUBLISHED.items():
        assert CFG[k] == v and type(CFG[k]) is type(v), k
    entry = next(c for c in BENCH["configs"] if c["name"] == "laguna_xs2")
    assert entry["reduced"] == CFG["reduced"] == ["n_layers", "experts_held", "vocab_rows"]
    assert entry["source"] == CFG["source"]
    assert entry["file"] == "benchmarks/configs/laguna_xs2.json"
    assert (CFG["n_layers"], CFG["layers_first"], CFG["experts_held"],
            CFG["experts_first"], CFG["vocab_rows"]) == (5, 0, 32, 0, 12544)
    assert CFG["vocab_rows"] * 8 == CFG["vocab_size"]
    assert CFG["rows_per_chip"] == CFG["vocab_rows"]
    assert CFG["table"]["dim"] == CFG["hidden_size"]
    assert CFG["table"]["localizer"] == "identity"
    assert set(CFG["reduced_why"]) == set(CFG["reduced"])
    assert "Eight chips share each layer" in CFG["deployment"]
    assert "expert parallel 8 x data parallel 8" in CFG["deployment"]
    assert CFG["generator_params"] == {"sequences": 2, "zipf_a": 1.05}
    assert CFG["model"]["moe_block"] == 512 and CFG["model"]["attn_block"] == 256
    for said in ("gate", "router", "qk_norm", "mlp", "rotary", "head", "init",
                 "optimizers", "traffic", "schedule"):
        assert said in CFG["assumed"], said
    lfm2 = json.load(open(os.path.join(ROOT, "benchmarks/configs/lfm2_8b_a1b.json")))
    assert CFG["guarantees"] == lfm2["guarantees"]
    assert CFG["consistency"] == lfm2["consistency"] == {"mode": "ssp", "max_delay": 1}
    # what the comparison reads between them covers what this body adds
    leaves = CFG["grad_check"]["leaves"]
    kinds = laguna_flops.layer_kinds(CFG)
    of = lambda part: [  # noqa: E731
        kinds[int(p.split("/")[0].removeprefix("layer_"))][0]
        for p in leaves if part in p
    ]
    assert of("attn/q/") == ["window"] and of("attn/k/") == ["full"]
    assert sorted(of("o_gate")) == ["full", "window"]
    for part in ("router", "experts/gate", "shared/", "mlp/", "lm_head"):
        assert any(part in p for p in leaves), part


def test_the_cell_reports_all_four_end_to_end_metrics_on_one_chip():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "skew"
    assert [e["name"] for e in BENCH["end_to_end"]
            if cell_lib.reports(BENCH, CELL, e["name"])] == [
        "examples_per_s", "step_ms_p50", "step_ms_p95", "setup_s"
    ]
    run = cell_lib.resolve(BENCH, CELL, seed=1, seconds=1.0, trace=0, dry_run=False)
    assert run.sizes == {"workers": 1, "servers": 2, "rows": 12544,
                         "batch": 16384, "cycle": 32, "warmup": 32}
    # its own entries: the body's, with its two kernels (PR 39)
    assert {m["name"] for m in cell_lib.layer_metrics_for(run) if "workloads" in m} == {
        "mfu_pct", "body_ms_p50", "moe_experts_ms", "moe_experts_roofline",
        "moe_load_max_over_mean", "hybrid_pull_wait_ms_p50",
        "full_attn_ms", "full_attn_roofline", "window_attn_ms", "window_attn_roofline",
    }


# -- laguna_flops against hand counts ----------------------------------------------
def test_layer_kinds_are_the_dense_full_layer_and_one_period():
    assert laguna_flops.layer_kinds(CFG) == [
        ("full", "dense"), ("window", "experts"), ("window", "experts"),
        ("window", "experts"), ("full", "experts"),
    ]
    assert laguna_flops.layer_heads(CFG) == [48, 64, 64, 64, 48]


def test_active_parameters_by_hand():
    D, K = 2048, 128
    full = 2 * D * 48 * K + 2 * D * 8 * K + D * 48
    window = 2 * D * 64 * K + 2 * D * 8 * K + D * 64
    expert = 3 * D * 512
    routed = D * 256 + expert + expert * 8 * 32 / 256  # router, shared, a slot's
    want = 2 * full + 3 * window + 3 * D * 8192 + 4 * routed + D * 12544
    assert laguna_flops.attn_params(CFG, 48) == full == 29_458_432
    assert laguna_flops.attn_params(CFG, 64) == window == 37_879_808
    assert laguna_flops.active_matrix_params(CFG) == want
    assert abs(want - 275.84e6) < 0.01e6  # count_params' 275.86 M less the norms


@pytest.mark.parametrize("what,flops,bytes_", [
    # 2 layers x 2 sequences x 48 query heads x the causal half x (128 + 128) x 2;
    # q, o and gradients of 48 heads, k, v and gradients of 8
    ("full_attn", 2 * 3 * 2 * (8192 * 8193 // 2) * 2 * 128 * 48 * 2,
     2 * 4 * 8192 * 128 * (4 * 48 + 4 * 8) * 2),
    # 3 layers x 2 sequences x 64 heads; a query sees min(t + 1, 512) keys:
    # 512 x 513 / 2 for the first 512 queries, 512 each for the other 7,680
    ("window_attn", 3 * 3 * 2 * (512 * 513 // 2 + 7680 * 512) * 2 * 128 * 64 * 2,
     3 * 4 * 8192 * 128 * (4 * 64 + 4 * 8) * 2),
    # 3 matrices of 2048 x 512 a slot, forward + backward; 4 x 32 held
    # experts' weights three times, a slot's row four times
    ("moe_experts", 3 * 2 * 3 * 2048 * 512 * 65000,
     4 * (3 * 3 * 2048 * 512 * 128 + 4 * 2048 * 65000)),
])
def test_kernel_operations_and_bytes_by_hand(what, flops, bytes_):
    got = {
        "full_attn": lambda: laguna_flops.full_attn(CFG, 2, 8192),
        "window_attn": lambda: laguna_flops.window_attn(CFG, 2, 8192),
        "moe_experts": lambda: laguna_flops.moe_experts(CFG, 65000),
    }[what]()
    assert got == {"flops": flops, "bytes": bytes_}


def test_a_step_is_39_tflop_and_a_window_is_a_quarter_of_a_full_layer():
    flops = laguna_flops.step_flops(CFG, 2, 8192)
    assert abs(flops - 39.41e12) < 0.01e12  # ISSUE 35 reckoned 39.5
    assert laguna_flops.window_pairs(8192, 512) == 512 * 513 // 2 + 7680 * 512
    assert laguna_flops.window_pairs(300, 512) == 300 * 301 // 2  # all causal
    # a layer: 64 heads x 504 keys a query against 48 x 4,096.5
    a_window = laguna_flops.window_attn(CFG, 2, 8192)["flops"] / 3
    a_full = laguna_flops.full_attn(CFG, 2, 8192)["flops"] / 2
    assert 0.16 < a_window / a_full < 0.165
    # both kinds are bound by their operations, the experts at an eighth of
    # their share by their weights' bytes
    for work in (laguna_flops.full_attn(CFG, 2, 8192),
                 laguna_flops.window_attn(CFG, 2, 8192)):
        b = bounds_s(work, PEAKS)
        assert b["flops"] > b["bytes"]
    experts = bounds_s(laguna_flops.moe_experts(CFG, 65536), PEAKS)
    assert experts["bytes"] > experts["flops"]


def _account(**scope_s):
    return types.SimpleNamespace(
        window=(0.0, 2.4), window_s=2.4, path="",
        durations_ms=lambda n: [3.0, 5.0], scope_s=scope_s,
    )


def test_laguna_scopes_reads_a_step_s_shares_from_an_account(monkeypatch):
    # ten steps of 1.2 s; a traced window of 2.4 s holds two of them
    steps = [(1.2 * i, 1.2 * (i + 1), True) for i in range(10)]
    acc = _account(**{
        "ps.model.laguna": 2.3, "ps.model.attn.full": 1.0,
        "ps.model.attn.window": 0.36, "ps.model.attn.proj": 0.3,
        "ps.model.attn.gate": 0.02, "ps.model.moe.experts": 0.1,
        "ps.model.moe.dispatch": 0.08, "ps.model.moe.combine": 0.06,
    })
    monkeypatch.setattr(model_scopes, "step_program_ms", lambda acc: [1140.0, 1150.0, 1160.0])
    moe = {"held_slots_mean": 65000.0, "load_max_over_mean_p50": 1.3}
    # the window's rate: a step of 16,384 positions in 1.2 s
    out = model_scopes.report(acc, steps, CFG, 16384, PEAKS, moe, 16384 / 1.2)
    assert abs(out["steps_per_s"] - 1 / 1.2) < 1e-9 and abs(out["body_ms"] - 1150.0) < 1e-6
    mfu = 100 * laguna_flops.step_flops(CFG, 2, 8192) / 197e12 / 1.2
    assert abs(out["mfu_pct"] - mfu) < 1e-9 and 15 < mfu < 20
    assert out["body_ms_p50"] == 1150.0
    # a layer: 500 ms of full attention over 2 layers, 180 of window over 3
    assert abs(out["full_attn_ms"] - 250.0) < 1e-6
    assert abs(out["window_attn_ms"] - 60.0) < 1e-6
    assert abs(out["moe_experts_ms"] - 120.0) < 1e-6  # dispatch + experts + combine
    assert out["full_attn_bound"] == out["window_attn_bound"] == "flops"
    assert out["moe_experts_bound"] == "bytes"
    # the roofline is the kind's whole work over the kind's whole time
    assert abs(out["full_attn_roofline"] - 100 * (
        laguna_flops.full_attn(CFG, 2, 8192)["flops"] / 197e12) / 0.5) < 1e-9
    assert abs(out["window_attn_roofline"] - 100 * (
        laguna_flops.window_attn(CFG, 2, 8192)["flops"] / 197e12) / 0.18) < 1e-9
    assert 0 < out["moe_experts_roofline"] < 100
    assert out["moe_load_max_over_mean"] == 1.3
    assert out["hybrid_pull_wait_ms_p50"] == 4.0
    assert out["scope_ms"]["ps.model.attn.gate"] == pytest.approx(10.0)
    # without the driver's counts the experts' roofline is left out, not guessed
    bare = model_scopes.report(acc, steps, CFG, 16384, PEAKS)
    assert "moe_experts_roofline" not in bare and "moe_experts_ms" in bare
    # a program without these scopes (the parent) gives nothing, and no error
    assert model_scopes.report(_account(), steps[:1], CFG, 16384, PEAKS) == {}
    nothing = model_scopes.report(_account(), steps, CFG, 16384, PEAKS)
    assert "mfu_pct" not in nothing and "window_attn_ms" not in nothing


def test_a_share_over_100_is_an_error(monkeypatch):
    steps = [(1.2 * i, 1.2 * (i + 1), True) for i in range(10)]
    monkeypatch.setattr(model_scopes, "step_program_ms", lambda acc: [])
    acc = _account(**{"ps.model.laguna": 2.3, "ps.model.attn.window": 0.02})
    out = model_scopes.report(acc, steps, CFG, 16384, PEAKS)
    (fail,) = model_scopes.above_100(out)
    assert "window_attn_roofline" in fail
    reader = cell_lib.load_module("layer_metrics", "window_attn_roofline")
    assert reader.check(out["window_attn_roofline"]) == [fail]


# -- what the comparison that decides ``correct`` refuses ----------------------------
def _frozen_leaf(step, path):
    """The step with one parameter leaf handed back as it came."""
    import jax
    import jax.numpy as jnp

    def broken(params, opt_state, emb, tok):
        node = params
        for key in path[:-1]:
            node = node[key]
        keep = jax.tree.map(jnp.copy, node[path[-1]])  # the step donates
        params, opt_state, loss, g_emb, counters = step(params, opt_state, emb, tok)
        node = params
        for key in path[:-1]:
            node = node[key]
        node[path[-1]] = keep
        return params, opt_state, loss, g_emb, counters
    return broken


@pytest.mark.parametrize("how,word", [
    ("sound", None),
    ("a_frozen_leaf", "parameters' change"),
    ("another_rate", "parameters' change"),
    ("a_used_state", "not fresh"),
    ("a_tighter_quartile", "first-quartile position is off"),
    ("a_dropped_slot", "moe_dropped_slots = 1"),
    ("a_window_layer_without_its_window", "gradient of layer_1/attn/q/kernel"),
    ("a_window_layer_with_the_full_table", "gradient of layer_1/attn/q/kernel"),
    ("an_ungated_output", "gradient of layer_0/attn/o_gate/kernel"),
])
def test_the_gradient_check_refuses_a_step_that_does_not_train(how, word, monkeypatch):
    """At the dry-run sizes, in this process: the sound step passes every
    limit of the file's ``dry_run.grad_check``; a leaf the step leaves as it
    was, an update at twice the stated rate, a state that has stepped
    before, a sound step whose first-quartile position reads over its limit,
    a step that counts a held expert's token slot as dropped, and a body whose window layers see every key, or are turned by the full
    layers' rotary table, or whose attention output is not gated, are
    refused."""
    import dataclasses

    from parameter_server_tpu.models import laguna

    run = cell_lib.resolve(BENCH, CELL, seed=3000000011, seconds=1.0, trace=0,
                           dry_run=True)
    drv = cell_lib.load_module("drivers", "hybrid_laguna").Driver(run)
    sound = drv.model_config
    # a body built from another config than the file's (the reference is
    # told the file's below)
    if how == "a_window_layer_without_its_window":
        drv.model_config = lambda: dataclasses.replace(
            sound(), sliding_window=1 << 20
        )
    elif how == "a_window_layer_with_the_full_table":
        def full_table_everywhere():
            cfg = sound()
            (full, table), (window, _own) = cfg.rotary
            assert (full, window) == ("full_attention", "sliding_attention")
            return dataclasses.replace(
                cfg, rotary=((full, table), (window, table))
            )
        drv.model_config = full_table_everywhere
    elif how == "an_ungated_output":
        monkeypatch.setattr(laguna, "_gated", lambda o, gate_in: o)
    drv.setup()
    try:
        tr = drv.trainer
        drv.model = sound()
        if how == "a_frozen_leaf":
            tr._step = _frozen_leaf(tr._step, ("layer_1", "attn", "o_gate", "kernel"))
        elif how == "another_rate":
            drv.learning_rate *= 2.0  # the file states a rate the step does not take
        elif how == "a_used_state":
            tr.step(drv.batches[0][0])
            tr.drain()
        elif how == "a_tighter_quartile":
            run.config["dry_run"]["grad_check"]["own_p25"] = 1e-9
        elif how == "a_dropped_slot":
            step = tr._step

            def dropping(*args):
                *out, counters = step(*args)
                return (*out, dict(counters, moe_dropped_slots=1))
            tr._step = dropping
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
    assert check["counters"]["moe_dropped_slots"] == 0
    assert '"dropped_slots": 0' in err.split("[moe] ")[-1].splitlines()[0]
    assert "'full', 'dense'" in err and "'window', 'experts'" in err
    moe = os.path.join(ROOT, "benchmarks", "out", "series",
                       f"{CELL}.seed3000000007.trace0.moe.json")
    assert json.load(open(moe))["held_slots_mean"] > 0
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
