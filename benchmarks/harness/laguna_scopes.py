"""The model's own layers in a traced run of ``laguna_xs2.pretrain8k``:
device time under the ``ps.model.*`` scopes a step, the step's model FLOP/s
utilization and each kernel's share of its roofline (``laguna_flops``'s work
over the chip's peaks, over that time).

    python3 -m benchmarks.harness.laguna_scopes <series.json> <file.xplane.pb> [<moe.json>]

prints them as one JSON object; ``<moe.json>`` is what the driver left beside
the series (held slots a step, the fullest expert over the mean).  They are
not per-layer metrics of ``BENCHMARK.json`` yet, for ``model_scopes``'s
reason (``PERF.md``, section 7); each key below is what such an entry's
``read(run)`` would return.  The reading of a trace (steps a second, device
ms of a scope a step, the step program's executions) is ``model_scopes``'s.

The two kinds of attention are read apart, **a layer**: ``full_attn_ms`` and
``window_attn_ms`` are the scope's device time a step over the layers of
that kind, and ``window_over_full`` their ratio (a window that is skipped,
not masked, reads 0.2-0.4; masked it would read 1.3, a window layer's 64
heads against a full layer's 48).  A kernel's roofline is the larger of its
two bounds (operations over peak FLOP/s, bytes over peak bytes/s) over its
device time; ``<kernel>_bound`` says which.  A share above 100 means the
count or the time is wrong, and is an error here.  A program without these
scopes gives nothing, and no error."""

from __future__ import annotations

import json
import os
import statistics
import sys
from typing import Optional

from benchmarks.harness import laguna_flops, model_scopes, program_spans
from benchmarks.harness.lfm2_flops import bounds_s
from benchmarks.harness.peaks import peaks_for

STEP_SCOPE = "ps.model.laguna"
KERNELS = {  # name -> the scopes that hold it
    "full_attn": ("ps.model.attn.full",),
    "window_attn": ("ps.model.attn.window",),
    # the grouped product with the gather that feeds it and the scatter that
    # takes its rows back (``model_scopes.KERNELS`` says why)
    "moe_experts": ("ps.model.moe.dispatch", "ps.model.moe.experts",
                    "ps.model.moe.combine"),
}
#: kernels reported a layer: name -> the mixer whose layers share the time
PER_LAYER = {"full_attn": "full", "window_attn": "window"}


def report(acc, steps, cfg: dict, batch: int, peaks: dict,
           moe: Optional[dict] = None) -> dict:
    """``cfg``: the configuration file's dict; ``batch``: token positions a
    step; ``moe``: the driver's ``held_slots_mean`` and
    ``load_max_over_mean_p50``, where known."""
    rate = model_scopes.steps_per_s(steps)
    if rate is None:
        return {}
    sequences = cfg["generator_params"]["sequences"]
    seq_len = batch // sequences
    work = {
        "full_attn": laguna_flops.full_attn(cfg, sequences, seq_len),
        "window_attn": laguna_flops.window_attn(cfg, sequences, seq_len),
    }
    out = {"steps_per_s": rate, "traced_window_s": acc.window_s}
    if moe:
        work["moe_experts"] = laguna_flops.moe_experts(cfg, moe["held_slots_mean"])
        out["moe_load_max_over_mean"] = moe["load_max_over_mean_p50"]
    body = model_scopes.scope_ms_per_step(acc, rate, STEP_SCOPE)
    if body is not None:
        out["body_ms"] = body
        out["body_mfu_pct"] = 100.0 * laguna_flops.step_flops(
            cfg, sequences, seq_len
        ) / peaks["flops"] / (1e-3 * body)
    programs = model_scopes.step_program_ms(acc)
    if programs:
        out["body_ms_p50"] = statistics.median(programs)
    kinds = [mixer for mixer, _mlp in laguna_flops.layer_kinds(cfg)]
    for name, scopes in KERNELS.items():
        parts = [model_scopes.scope_ms_per_step(acc, rate, s) for s in scopes]
        if None in parts:
            continue
        ms = sum(parts)
        out[f"{name}_ms"] = ms / kinds.count(PER_LAYER[name]) if name in PER_LAYER else ms
        if name in work:
            bounds = bounds_s(work[name], peaks)
            out[f"{name}_bound"] = max(bounds, key=bounds.get)
            out[f"{name}_roofline"] = 100.0 * max(bounds.values()) / (1e-3 * ms)
    if "full_attn_ms" in out and "window_attn_ms" in out:
        out["window_over_full"] = out["window_attn_ms"] / out["full_attn_ms"]
    out["scope_ms"] = {
        k: model_scopes.scope_ms_per_step(acc, rate, k)
        for k in sorted(acc.scope_s) if k.startswith("ps.model.")
    }
    waits = acc.durations_ms("ps.hybrid.pull_wait")
    if waits:
        out["hybrid_pull_wait_ms_p50"] = statistics.median(waits)
    over = [k for k, v in out.items()
            if k.endswith(("_roofline", "_mfu_pct")) and v > 100.0]
    if over:
        raise ValueError(f"a share of a peak above 100: {over}: the count is "
                         f"too high or the time leaves out work ({out})")
    return out


def main(argv) -> int:
    from benchmarks.harness.cell import BENCH_DIR, load_json

    series = load_json(argv[0])
    acc = program_spans.load(argv[1])
    root = os.path.dirname(BENCH_DIR)
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == series["cell"])
    cfg = load_json(os.path.join(root, next(
        c["file"] for c in bench["configs"] if c["name"] == cell["config"]
    )))
    steps = [(a, b, ok) for _w, _i, a, b, ok, _spans in series["steps"]]
    moe = load_json(argv[2]) if len(argv) > 2 else None
    print(json.dumps(report(
        acc, steps, cfg, cfg["batch_per_worker"], peaks_for("TPU v5 lite"), moe
    ), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
