"""The cell ``kimi_linear_a3b.pretrain8k``: its configuration file against
the published keys, its generator, ``flops_model`` against hand counts,
``model_scopes`` on a made-up account, and the command's dry run (CPU, tiny
sizes, float32: the reference comparison there holds to 1e-4 / 1e-3)."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

from benchmarks.harness import cell as cell_lib
from benchmarks.harness import flops_model, model_scopes
from benchmarks.harness.peaks import bounds_s

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "kimi_linear_a3b.pretrain8k"
CFG = json.load(open(os.path.join(ROOT, "benchmarks/configs/kimi_linear_a3b.json")))
#: config.json's numbers as the catalog holds them: a width that moved here
#: is a different model
PUBLISHED = {
    "hidden_size": 2304, "intermediate_size": 9216, "moe_intermediate_size": 1024,
    "num_experts": 256, "num_experts_per_token": 8, "num_shared_experts": 1,
    "routed_scaling_factor": 2.446, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "num_attention_heads": 32,
    "num_hidden_layers": 27, "vocab_size": 163840, "rms_norm_eps": 1e-5,
    "first_k_dense_replace": 1, "head_dim": 72, "rope_theta": 10000,
}


def test_the_file_holds_the_published_keys_and_states_its_cut():
    for k, v in PUBLISHED.items():
        assert CFG[k] == v, k
    lin = CFG["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert len(lin["kda_layers"]) == 20 and len(lin["full_attn_layers"]) == 7
    entry = next(c for c in BENCH["configs"] if c["name"] == "kimi_linear_a3b")
    assert entry["reduced"] == CFG["reduced"] == ["n_layers", "experts_held", "vocab_rows"]
    assert entry["source"] == CFG["source"]
    assert (CFG["n_layers"], CFG["experts_held"], CFG["vocab_rows"]) == (5, 8, 20480)
    assert CFG["rows_per_chip"] == CFG["vocab_rows"]
    assert CFG["table"]["dim"] == CFG["hidden_size"]
    assert CFG["table"]["localizer"] == "identity"
    assert set(CFG["reduced_why"]) == set(CFG["reduced"])


def test_the_cell_reports_all_four_end_to_end_metrics_on_one_chip():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "skew"
    assert [e["name"] for e in BENCH["end_to_end"]
            if cell_lib.reports(BENCH, CELL, e["name"])] == [
        "examples_per_s", "step_ms_p50", "step_ms_p95", "setup_s"
    ]
    run = cell_lib.resolve(BENCH, CELL, seed=1, seconds=1.0, trace=0, dry_run=False)
    assert run.sizes == {"workers": 1, "servers": 2, "rows": 20480,
                         "batch": 16384, "cycle": 32, "warmup": 32}
    # its own entries: the body's, with its two kernels (PR 39)
    assert {m["name"] for m in cell_lib.layer_metrics_for(run) if "workloads" in m} == {
        "mfu_pct", "body_ms_p50", "moe_experts_ms", "moe_experts_roofline",
        "moe_load_max_over_mean", "hybrid_pull_wait_ms_p50",
        "kda_scan_ms", "kda_scan_roofline", "mla_attn_ms", "mla_attn_roofline",
    }


def test_the_generator_makes_whole_sequences_of_the_slice():
    gen = cell_lib.load_module("generators", "lm")
    mix = cell_lib.load_json(os.path.join(ROOT, "benchmarks/traffic/skew.json"))
    params = dict(CFG["generator_params"], key_space=20480)
    a = gen.make(params, mix, seed=1, n_workers=1, cycle=4, batch=1024)
    b = gen.make(params, mix, seed=2, n_workers=1, cycle=4, batch=1024)
    assert len(a) == 1 and len(a[0]) == 4
    for tokens in a[0]:
        assert tokens.shape == (2, 512) and tokens.dtype == np.int32
        assert 0 <= tokens.min() and tokens.max() < 20480
        assert gen.keys_of(tokens) is tokens
    # the pool is the mix's, the order the seed's
    key = lambda pool: sorted(t.tobytes() for t in pool)  # noqa: E731
    assert key(a[0]) == key(b[0])
    # Zipf: a few tokens take most positions
    _ids, counts = np.unique(np.concatenate([t.ravel() for t in a[0]]), return_counts=True)
    assert counts.max() > 0.05 * 4096


# -- flops_model against hand counts ---------------------------------------------
def test_layer_kinds_are_one_dense_layer_and_one_period():
    assert flops_model.layer_kinds(CFG) == [
        ("kda", "dense"), ("kda", "experts"), ("kda", "experts"),
        ("mla", "experts"), ("kda", "experts"),
    ]


def test_active_parameters_by_hand():
    D, HK, R = 2304, 4096, 128
    kda = 3 * D * HK + HK * D + 2 * (D * R + R * HK) + D * 32
    mla = D * 32 * 192 + D * 576 + 512 * 32 * 256 + 32 * 128 * D
    expert = 3 * D * 1024
    moe = D * 256 + expert + expert * 8 * 8 / 256
    want = 4 * kda + mla + 3 * D * 9216 + 4 * moe + D * 20480
    assert flops_model.mixer_params(CFG) == {"kda": kda, "mla": mla}
    assert flops_model.active_matrix_params(CFG) == want
    assert abs(want - 335.6e6) < 0.1e6  # ISSUE 28's 336 M


@pytest.mark.parametrize("what,want", [
    # 4 KDA layers x 16384 tokens x 32 heads x 7 x 128 x 128, forward + backward
    ("kda_scan", 3 * 7 * 128 * 128 * 4 * 16384 * 32),
    # 1 MLA layer x 2 sequences x 32 heads x the causal half x (192 + 128) x 2
    ("mla_attn", 3 * 2 * (8192 * 8193 // 2) * 320 * 2 * 32),
    # 3 matrices of 2304 x 1024 a slot, forward + backward
    ("moe_experts", 3 * 2 * 3 * 2304 * 1024 * 19200),
])
def test_kernel_operations_by_hand(what, want):
    got = {
        "kda_scan": lambda: flops_model.kda_scan(CFG, 16384),
        "mla_attn": lambda: flops_model.mla_attn(CFG, 2, 8192),
        "moe_experts": lambda: flops_model.moe_experts(CFG, 19200),
    }[what]()
    assert got["flops"] == want and got["bytes"] > 0


def test_a_step_is_38_tflop_and_a_roofline_takes_the_larger_bound():
    flops = flops_model.step_flops(CFG, 2, 8192)
    assert abs(flops - 37.8e12) < 0.1e12
    peaks = {"flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert bounds_s({"flops": 197e12, "bytes": 1}, peaks)["flops"] == 1.0
    assert bounds_s({"flops": 1, "bytes": 819e9}, peaks)["bytes"] == 1.0


def test_model_scopes_reads_a_step_s_share_from_an_account(monkeypatch):
    peaks = {"flops": 197e12, "hbm_bytes_per_s": 819e9}
    # ten steps of 0.5 s; a traced window of 2 s holds four of them
    steps = [(0.5 * i, 0.5 * (i + 1), True) for i in range(10)]
    acc = types.SimpleNamespace(
        window=(0.0, 2.0), window_s=2.0, path="", durations_ms=lambda n: [3.0, 5.0],
        scope_s={"ps.model.kimi": 1.9, "ps.model.kda.scan": 0.4,
                 "ps.model.mla.attn": 0.2, "ps.model.moe.experts": 0.02,
                 "ps.model.moe.dispatch": 0.016, "ps.model.moe.combine": 0.004},
    )
    monkeypatch.setattr(model_scopes, "step_program_ms", lambda acc: [470.0, 480.0, 490.0])
    # the window's rate: 2 steps of 16,384 positions a second
    out = model_scopes.report(acc, steps, CFG, 16384, peaks,
                              {"held_slots_mean": 19200.0}, 32768.0)
    assert out["steps_per_s"] == 2.0 and out["body_ms"] == 475.0
    # the whole step's share: the step's operations twice a second
    mfu = 100 * flops_model.step_flops(CFG, 2, 8192) * 2 / 197e12
    assert abs(out["mfu_pct"] - mfu) < 1e-9 and 30 < mfu < 50
    assert "body_mfu_pct" not in out
    assert out["body_ms_p50"] == 480.0 and out["kda_scan_ms"] == 100.0
    assert abs(out["mla_attn_roofline"]
               - 100 * flops_model.mla_attn(CFG, 2, 8192)["flops"] / 197e12 / 0.05) < 1e-9
    assert out["moe_experts_ms"] == 10.0  # dispatch + experts + combine
    assert 0 < out["moe_experts_roofline"] < 100 and 0 < out["kda_scan_roofline"] < 100
    assert out["hybrid_pull_wait_ms_p50"] == 4.0


# -- what the comparison that decides ``correct`` refuses ----------------------------
def _same_state(step):
    """The step with the optimizer state handed back as it came."""
    def broken(params, opt_state, emb, tok):
        keep = jax_tree_copy(opt_state)
        params, _state, loss, g_emb, counters = step(params, opt_state, emb, tok)
        return params, keep, loss, g_emb, counters
    return broken


def _same_params(step):
    """The step with the parameters handed back as they came."""
    def broken(params, opt_state, emb, tok):
        keep = jax_tree_copy(params)
        _params, opt_state, loss, g_emb, counters = step(params, opt_state, emb, tok)
        return keep, opt_state, loss, g_emb, counters
    return broken


def jax_tree_copy(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(jnp.copy, tree)  # the step donates what it is given


@pytest.mark.parametrize("how,word", [
    ("sound", None),
    ("state_left_unchanged", "gradient of"),
    ("parameters_left_unchanged", "parameters' change"),
    ("another_rate", "parameters' change"),
    ("a_used_state", "not fresh"),
])
def test_the_gradient_check_refuses_a_step_that_does_not_train(how, word):
    """At the dry-run sizes, in this process: the sound step passes every
    limit of the file's ``dry_run.grad_check``; a step that leaves the
    optimizer state or the parameters as they were, or an update at twice
    the stated rate, is refused by the named leaves' gradients (read from
    the step's first moments) or by their change (held to AdamW in NumPy);
    a state that has stepped before is refused unread."""
    run = cell_lib.resolve(BENCH, CELL, seed=3000000011, seconds=1.0, trace=0,
                           dry_run=True)
    drv = cell_lib.load_module("drivers", "hybrid_lm").Driver(run)
    drv.setup()
    try:
        tr = drv.trainer
        if how == "state_left_unchanged":
            tr._step = _same_state(tr._step)
        elif how == "parameters_left_unchanged":
            tr._step = _same_params(tr._step)
        elif how == "another_rate":
            drv.learning_rate *= 2.0  # the file states a rate the step does not take
        elif how == "a_used_state":
            tokens = drv.batches[0][0]
            tr.step(tokens)
            tr.drain()
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
    limits = CFG["dry_run"]["grad_check"]
    assert set(check["leaves"]) == set(limits["leaves"])
    assert max(check["leaves"].values()) < 1e-4 and check["update"] < 1e-3
    assert check["counters"]["moe_dropped_slots"] == 0
    assert '"dropped_slots": 0' in err.split("[moe] ")[-1].splitlines()[0]
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
