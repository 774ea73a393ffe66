"""The cell ``qwen3_next_80b_a3b.pretrain8k``: its configuration file against
the catalog row's keys, ``qwen3_next_flops`` against hand counts (the
recurrence's ``7 d_k d_v`` a token and value head, the causal half at head
size 256), ``model_scopes`` on a made-up account, and the command's dry run
(CPU, tiny sizes, float32: the reference comparison there holds to 1e-4 /
1e-3)."""

import json
import os
import subprocess
import sys
import types

import pytest

from benchmarks.harness import cell as cell_lib
from benchmarks.harness import model_scopes, qwen3_next_flops
from benchmarks.harness.peaks import bounds_s

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELL = "qwen3_next_80b_a3b.pretrain8k"
CFG = json.load(open(os.path.join(ROOT, "benchmarks/configs/qwen3_next_80b_a3b.json")))
#: config.json's keys as the catalog's row holds them, value for value: a
#: width that moved here is a different model
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next", "moe_intermediate_size": 512,
    "norm_topk_prob": True, "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "use_sliding_window": False, "vocab_size": 151936,
}
PEAKS = {"flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_the_file_holds_the_row_s_keys_and_states_its_cut():
    for k, v in PUBLISHED.items():
        assert CFG[k] == v and type(CFG[k]) is type(v), k
    entry = next(c for c in BENCH["configs"] if c["name"] == "qwen3_next_80b_a3b")
    assert entry["reduced"] == CFG["reduced"] == ["n_layers", "experts_held", "vocab_rows"]
    assert entry["source"] == CFG["source"]
    assert entry["file"] == "benchmarks/configs/qwen3_next_80b_a3b.json"
    assert (CFG["n_layers"], CFG["layers_first"], CFG["experts_held"],
            CFG["experts_first"], CFG["vocab_rows"]) == (4, 0, 32, 0, 18992)
    assert CFG["vocab_rows"] * 8 == CFG["vocab_size"]
    assert CFG["experts_held"] * 16 == CFG["num_experts"]
    assert CFG["rows_per_chip"] == CFG["vocab_rows"]
    assert CFG["table"]["dim"] == CFG["hidden_size"]
    assert set(CFG["reduced_why"]) == set(CFG["reduced"])
    assert "Sixteen chips share each layer" in CFG["deployment"]
    assert CFG["generator"] == "lm"
    assert CFG["generator_params"] == {"sequences": 2, "zipf_a": 1.1}
    assert CFG["batch_per_worker"] == 16384
    for said in ("layer_pattern", "gated_deltanet", "gated_attention", "norms",
                 "router", "shared_expert", "mtp", "head", "init", "optimizers",
                 "traffic", "schedule"):
        assert said in CFG["assumed"], said
    kimi = json.load(open(os.path.join(ROOT, "benchmarks/configs/kimi_linear_a3b.json")))
    for k in ("consistency", "topology_by_chips", "table"):
        assert CFG[k] == dict(kimi[k], **({"dim": 2048} if k == "table" else {})), k
    sdar = json.load(open(os.path.join(ROOT, "benchmarks/configs/sdar_30b_a3b.json")))
    assert CFG["guarantees"] == sdar["guarantees"]
    # the comparison reads what this body adds: the driver names the
    # leaves, the file their limits
    drv = cell_lib.load_module("drivers", "hybrid_qwen3_next")
    for group in (CFG["grad_check"], CFG["dry_run"]["grad_check"]):
        assert set(group["leaves"]) == set(drv.Driver.leaves)
    for part in ("A_log", "gdn/ba", "gdn/qkvz", "full/q", "router", "experts/gate",
                 "shared_gate", "lm_head"):
        assert any(part in p for p in drv.Driver.leaves), part
    assert drv.body is qwen3_next_flops


def test_the_cell_reports_its_metrics_on_one_chip():
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "skew" and len(cell["why"]) <= 200
    assert [e["name"] for e in BENCH["end_to_end"]
            if cell_lib.reports(BENCH, CELL, e["name"])] == [
        "examples_per_s", "step_ms_p50", "step_ms_p95", "setup_s"
    ]
    run = cell_lib.resolve(BENCH, CELL, seed=1, seconds=1.0, trace=0, dry_run=False)
    assert run.sizes == {"workers": 1, "servers": 2, "rows": 18992,
                         "batch": 16384, "cycle": 32, "warmup": 32}
    assert {m["name"] for m in cell_lib.layer_metrics_for(run) if "workloads" in m} == {
        "mfu_pct", "body_ms_p50", "moe_experts_ms", "moe_experts_roofline",
        "moe_load_max_over_mean", "hybrid_pull_wait_ms_p50",
        "gdn_scan_ms", "gdn_scan_roofline", "gated_attn_ms", "gated_attn_roofline",
    }


# -- qwen3_next_flops against hand counts ------------------------------------------
def test_active_parameters_by_hand():
    D = 2048
    gdn = D * 12288 + D * 64 + 4096 * D  # W_qkvz, W_ba, W_o
    full = D * 8192 + 2 * D * 512 + 4096 * D  # W_q with its gate, W_k, W_v, W_o
    expert = 3 * D * 512
    per_moe = D * 512 + expert + D + expert * 10 * 32 / 512
    assert qwen3_next_flops.mixer_params(CFG) == {"gdn": gdn, "full": full}
    assert qwen3_next_flops.layer_kinds(CFG) == [("gdn", "experts")] * 3 + [("full", "experts")]
    total = 3 * gdn + full + 4 * per_moe + D * 18992
    assert qwen3_next_flops.body_matrix_params(CFG) == total
    assert abs(total - 191.86e6) < 0.01e6


def test_kernel_operations_and_bytes_by_hand():
    # 3 layers x 16,384 tokens x 32 value heads x 7 x 128 x 128, forward +
    # backward
    scan = qwen3_next_flops.gdn_scan(CFG, 16384)
    assert scan["flops"] == 3 * 7 * 128 * 128 * 32 * 16384 * 3
    inputs = 2 * 16 * 128 + 32 * 130
    assert scan["bytes"] == 4 * (3 * inputs + 2 * 32 * 128) * 16384 * 3
    # 1 layer x 2 sequences x 16 heads x the causal half of 8,192 x (256 +
    # 256) x 2, forward + backward
    pairs = 8192 * 8193 // 2
    attn = qwen3_next_flops.gated_attn(CFG, 2, 8192)
    assert attn["flops"] == 2 * 3 * 2 * pairs * 2 * 256 * 16
    assert attn["bytes"] == 2 * 4 * 8192 * 256 * (4 * 16 + 4 * 2)
    # the scan is bound by its bytes (the recurrence's count is 83
    # operations a byte, the chip's ridge 240), attention by its operations
    assert bounds_s(scan, PEAKS)["bytes"] > bounds_s(scan, PEAKS)["flops"]
    assert bounds_s(attn, PEAKS)["flops"] > bounds_s(attn, PEAKS)["bytes"]


def test_a_step_is_22_7_tflop():
    flops = qwen3_next_flops.step_flops(CFG, 2, 8192)
    body = 6 * qwen3_next_flops.body_matrix_params(CFG) * 16384
    assert abs(body - 18.86e12) < 0.01e12
    assert abs(qwen3_next_flops.gated_attn(CFG, 2, 8192)["flops"] - 3.30e12) < 0.01e12
    assert abs(qwen3_next_flops.gdn_scan(CFG, 16384)["flops"] - 0.54e12) < 0.01e12
    assert abs(flops - 22.70e12) < 0.01e12


def _account(**scope_s):
    return types.SimpleNamespace(
        window=(0.0, 2.4), window_s=2.4, path="",
        durations_ms=lambda n: [3.0, 5.0], scope_s=scope_s,
    )


def test_model_scopes_reads_a_step_s_shares_from_an_account(monkeypatch):
    # ten steps of 0.8 s; a traced window of 2.4 s holds three of them
    steps = [(0.8 * i, 0.8 * (i + 1), True) for i in range(10)]
    acc = _account(**{
        "ps.model.qwen3_next": 2.3, "ps.model.gdn.scan": 0.9,
        "ps.model.attn.full": 0.3,
        "ps.model.moe.experts": 0.1, "ps.model.moe.dispatch": 0.08,
        "ps.model.moe.combine": 0.06,
    })
    monkeypatch.setattr(model_scopes, "step_program_ms", lambda acc: [760.0, 770.0, 780.0])
    moe = {"held_slots_mean": 40960.0, "load_max_over_mean_p50": 2.5}
    out = model_scopes.report(acc, steps, CFG, 16384, PEAKS, moe, 16384 / 0.8)
    mfu = 100 * qwen3_next_flops.step_flops(CFG, 2, 8192) / 197e12 / 0.8
    assert abs(out["mfu_pct"] - mfu) < 1e-9 and 14 < mfu < 15
    assert out["body_ms_p50"] == 770.0
    # a layer: 300 ms of the scan a step over 3 DeltaNet layers, 100 ms of
    # gated attention over its one full layer
    assert abs(out["gdn_scan_ms"] - 100.0) < 1e-6
    assert abs(out["gated_attn_ms"] - 100.0) < 1e-6
    assert out["gdn_scan_bound"] == "bytes" and out["gated_attn_bound"] == "flops"
    assert abs(out["gdn_scan_roofline"] - 100 * (
        qwen3_next_flops.gdn_scan(CFG, 16384)["bytes"] / 819e9) / 0.3) < 1e-9
    assert abs(out["gated_attn_roofline"] - 100 * (
        qwen3_next_flops.gated_attn(CFG, 2, 8192)["flops"] / 197e12) / 0.1) < 1e-9
    assert 0 < out["moe_experts_roofline"] < 100
    assert out["moe_load_max_over_mean"] == 2.5
    assert out["hybrid_pull_wait_ms_p50"] == 4.0
    # a program without these scopes (the parent) gives nothing, and no error
    nothing = model_scopes.report(_account(), steps, CFG, 16384, PEAKS)
    assert "gdn_scan_ms" not in nothing and "gated_attn_roofline" not in nothing
    for name in ("gdn_scan_roofline", "gated_attn_roofline"):
        reader = cell_lib.load_module("layer_metrics", name)
        assert reader.check(50.0) == [] and reader.check(120.0)


# -- the command, dry -------------------------------------------------------------
def test_the_traced_dry_run_is_correct_and_reports_the_body(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--dry-run", "--seconds", "1.5",
         "--seed", "3000000019", "--trace", "1"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=500,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    out, err = json.loads(p.stdout.strip().splitlines()[-1]), p.stderr
    assert out["correct"] is True, err[-3000:]
    check = json.loads(err.split("[grad_check] ")[-1].splitlines()[0])
    assert check["worst"] < 1e-4 and check["loss"] < 1e-5
    assert check["own_p25"] < check["own_median"] < 1e-5
    assert check["update"] < 1e-3 and check["hot_row"] < 1e-5
    assert check["hot_row_positions"] > 1
    assert check["counters"]["moe_dropped_slots"] == 0
    assert "('gdn', 'experts')" in err and "('full', 'experts')" in err
    for name in ("pull_ms_p50", "grad_ms_p50", "push_ms_p50",
                 "hybrid_pull_wait_ms_p50", "moe_load_max_over_mean"):
        assert name in out["metrics"], name
    assert "mfu_pct" not in out["metrics"]  # no chip, no peak


@pytest.mark.parametrize("tree", ["grad_check", "dry_run"])
def test_the_limits_name_what_the_shared_comparison_reads(tree):
    limits = CFG["grad_check"] if tree == "grad_check" else CFG["dry_run"]["grad_check"]
    for key in ("median", "worst", "loss", "own_p25", "leaves", "update", "mask_row"):
        assert key in limits, key
