"""The model's own layers in a traced run of a cell on the hybrid path:
device time under the ``ps.model.*`` scopes a step, the step's model FLOP/s
utilization and each kernel's share of its roofline (``flops_model``'s work
over the chip's peaks, over that time).

    python3 -m benchmarks.harness.model_scopes <series.json> <file.xplane.pb> [held slots a step]

prints them as one JSON object.  They are not per-layer metrics of
``BENCHMARK.json`` yet: ``benchmarks/tests/test_cell_metrics.py`` holds every cell to
the same 22 quantities, so an entry of a cell's own waits for a ``benchmark``
PR (``PERF.md``, section 7); each function below is what such an entry's
``read(run)`` would call.

A traced window holds a few steps and cuts the first and the last, so "a
step" is the traced window's length times the steps a second that the whole
window's step series reads (the cell is a closed loop on one device: the
device's step is the host's).  A share above 100 means the count or the time
is wrong."""

from __future__ import annotations

import json
import os
import statistics
import sys
from typing import Optional

from benchmarks.harness import flops_model, program_spans, trace_reduce
from benchmarks.harness.peaks import peaks_for

STEP_PROGRAM = "jit_step_fn"
STEP_SCOPE = "ps.model.kimi"
KERNELS = {  # name -> the scopes that hold it
    "kda_scan": ("ps.model.kda.scan",),
    "mla_attn": ("ps.model.mla.attn",),
    # the grouped product with the gather that feeds it and the scatter that
    # takes its rows back: ``flops_model.moe_experts`` counts a slot's row in
    # and out, and the products alone read above their roofline (the
    # compiler fuses row traffic into ``.dispatch``; my chip run, PR 28)
    "moe_experts": ("ps.model.moe.dispatch", "ps.model.moe.experts",
                    "ps.model.moe.combine"),
}


def steps_per_s(steps) -> Optional[float]:
    """``steps``: ``(start, end, ok)`` of the window's steps."""
    done = [(a, b) for a, b, ok in steps if ok]
    if len(done) < 2:
        return None
    return len(done) / (max(b for _a, b in done) - min(a for a, _b in done))


def scope_ms_per_step(acc, rate: float, scope: str) -> Optional[float]:
    if not acc.scope_s.get(scope) or not acc.window_s:
        return None
    return 1e3 * acc.scope_s[scope] / (acc.window_s * rate)


def step_program_ms(acc) -> list:
    """Device ms of every execution of the jitted step that lies wholly in
    the traced window (the ``XLA Modules`` line of each device plane)."""
    from jax.profiler import ProfileData

    w0, w1 = acc.window
    out = []
    for p in ProfileData.from_file(acc.path).planes:
        if not trace_reduce._DEVICE.match(p.name or ""):
            continue
        for ln in p.lines:
            if ln.name != "XLA Modules":
                continue
            for ev in ln.events:
                a = ev.start_ns * 1e-9
                b = a + ev.duration_ns * 1e-9
                if (ev.name or "").startswith(STEP_PROGRAM) and a >= w0 and b <= w1:
                    out.append(1e3 * (b - a))
    return out


def report(acc, steps, cfg: dict, batch: int, peaks: dict,
           held_slots: Optional[float] = None) -> dict:
    """``cfg``: the configuration file's dict; ``batch``: token positions a
    step; ``held_slots``: mean ``moe_held_slots`` a step, where known."""
    rate = steps_per_s(steps)
    if rate is None:
        return {}
    sequences = cfg["generator_params"]["sequences"]
    seq_len = batch // sequences
    work = {
        "kda_scan": flops_model.kda_scan(cfg, batch),
        "mla_attn": flops_model.mla_attn(cfg, sequences, seq_len),
    }
    if held_slots is not None:
        work["moe_experts"] = flops_model.moe_experts(cfg, held_slots)
    out = {"steps_per_s": rate, "traced_window_s": acc.window_s}
    body = scope_ms_per_step(acc, rate, STEP_SCOPE)
    if body is not None:
        out["body_ms"] = body
        out["body_mfu_pct"] = 100.0 * flops_model.step_flops(
            cfg, sequences, seq_len
        ) / peaks["flops"] / (1e-3 * body)
    programs = step_program_ms(acc)
    if programs:
        out["body_ms_p50"] = statistics.median(programs)
    for name, scopes in KERNELS.items():
        parts = [scope_ms_per_step(acc, rate, scope) for scope in scopes]
        if None in parts:
            continue
        ms = sum(parts)
        out[f"{name}_ms"] = ms
        if name in work:
            out[f"{name}_roofline"] = (
                100.0 * flops_model.roofline_s(work[name], peaks) / (1e-3 * ms)
            )
    out["scope_ms"] = {
        k: scope_ms_per_step(acc, rate, k)
        for k in sorted(acc.scope_s) if k.startswith("ps.model.")
    }
    waits = acc.durations_ms("ps.hybrid.pull_wait")
    if waits:
        out["hybrid_pull_wait_ms_p50"] = statistics.median(waits)
    return out


def main(argv) -> int:
    from benchmarks.harness.cell import BENCH_DIR, load_json

    series = load_json(argv[0])
    acc = program_spans.load(argv[1])
    bench = load_json(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == series["cell"])
    cfg = load_json(os.path.join(
        os.path.dirname(BENCH_DIR),
        next(c["file"] for c in bench["configs"] if c["name"] == cell["config"]),
    ))
    steps = [(a, b, ok) for _w, _i, a, b, ok, _spans in series["steps"]]
    held = float(argv[2]) if len(argv) > 2 else None
    print(json.dumps(report(
        acc, steps, cfg, cfg["batch_per_worker"], peaks_for("TPU v5 lite"), held
    ), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
