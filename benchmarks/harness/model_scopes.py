"""The model body's own layers in a traced run of a cell on the hybrid path:
the step's model FLOP/s utilization, device time under the ``ps.model.*``
scopes a step, each kernel's share of its roofline, the step program's
executions, the experts' load and the trainer's wait for its rows.  One
reader for every body: what differs lives in the body's module of
operations and bytes, which the configuration's driver names as ``body``
(``drivers/hybrid_lm.py``: ``flops_model``; ``hybrid_lfm2``: ``lfm2_flops``;
``hybrid_laguna``: ``laguna_flops``).  That module gives

- ``ROOT_SCOPE``: the device scope of the whole jitted step;
- ``KERNELS``: kernel -> the scopes that hold it; ``PER_LAYER``: kernel ->
  the mixer whose layers share its time, for kernels read a layer;
- ``layer_kinds(cfg)``, ``work(cfg, sequences, seq_len, held_slots)`` and
  ``step_flops(cfg, sequences, seq_len)``.

A new body adds that module, its driver, and for each new reading a file
``layer_metrics/<reading>.py`` of four lines over ``read`` below.

    python3 -m benchmarks.harness.model_scopes <series.json> <file.xplane.pb> [<moe.json> | held slots]

prints every reading as one JSON object, and exits 1 where a share of a
peak is above 100; ``<moe.json>`` is what the driver left beside the series
(``lfm2_scopes`` and ``laguna_scopes`` are the same command).

``mfu_pct`` is the whole step's: model operations a step (``step_flops``:
the held share's forward and backward) x the window's steps a second (its
``examples_per_s`` over the batch) over the chip's peak, host stalls
included.  A traced window holds a few steps and cuts the first and the
last, so a scope's "ms a step" is its device seconds over the traced
window's length times the steps a second of the whole window's step series
(the cell is a closed loop on one device: the device's step is the host's).
A kernel's roofline is the larger of its two bounds (operations over peak
FLOP/s, bytes over peak bytes/s) over its device time; ``<kernel>_bound``
says which.  A share above 100 means the count or the time is wrong."""

from __future__ import annotations

import json
import os
import statistics
import sys
from typing import Optional

from benchmarks.harness import cell as cell_lib
from benchmarks.harness import program_spans, trace_reduce
from benchmarks.harness.peaks import bounds_s, peaks_for

STEP_PROGRAM = "jit_step_fn"
#: readings that are a share of a peak: none may pass 100
SHARES = ("_roofline", "mfu_pct")


def body_of(cfg: dict, bench_dir: str = cell_lib.BENCH_DIR):
    """The body module the configuration's driver names, or ``None`` (a
    driver that runs no model)."""
    return getattr(cell_lib.load_module("drivers", cfg["driver"], bench_dir),
                   "body", None)


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


def report(acc, steps, cfg: dict, batch: int, peaks: Optional[dict],
           moe: Optional[dict] = None, examples_per_s: Optional[float] = None,
           bench_dir: str = cell_lib.BENCH_DIR) -> dict:
    """``cfg``: the configuration file's dict; ``batch``: token positions a
    step; ``peaks``: the chip's (``None``: no share is read); ``moe``: the
    driver's ``held_slots_mean`` and ``load_max_over_mean_p50``, where
    known; ``examples_per_s``: the window's."""
    body = body_of(cfg, bench_dir)
    rate = steps_per_s(steps)
    if body is None or rate is None:
        return {}
    sequences = cfg["generator_params"]["sequences"]
    seq_len = batch // sequences
    moe = moe or {}
    work = body.work(cfg, sequences, seq_len, moe.get("held_slots_mean"))
    out = {"steps_per_s": rate, "traced_window_s": acc.window_s}
    if moe.get("load_max_over_mean_p50") is not None:
        out["moe_load_max_over_mean"] = moe["load_max_over_mean_p50"]
    if peaks is not None and examples_per_s:
        out["mfu_pct"] = 100.0 * body.step_flops(cfg, sequences, seq_len) * (
            examples_per_s / batch
        ) / peaks["flops"]
    root = scope_ms_per_step(acc, rate, body.ROOT_SCOPE)
    if root is not None:
        out["body_ms"] = root
    programs = step_program_ms(acc)
    if programs:
        out["body_ms_p50"] = statistics.median(programs)
    mixers = [mixer for mixer, _mlp in body.layer_kinds(cfg)]
    for name, scopes in body.KERNELS.items():
        parts = [scope_ms_per_step(acc, rate, s) for s in scopes]
        if None in parts:
            continue
        ms = sum(parts)
        layers = mixers.count(body.PER_LAYER[name]) if name in body.PER_LAYER else 1
        out[f"{name}_ms"] = ms / layers
        if name in work and peaks is not None:
            bounds = bounds_s(work[name], peaks)
            out[f"{name}_bound"] = max(bounds, key=bounds.get)
            out[f"{name}_roofline"] = 100.0 * max(bounds.values()) / (1e-3 * ms)
    out["scope_ms"] = {
        k: scope_ms_per_step(acc, rate, k)
        for k in sorted(acc.scope_s) if k.startswith("ps.model.")
    }
    waits = acc.durations_ms("ps.hybrid.pull_wait")
    if waits:
        out["hybrid_pull_wait_ms_p50"] = statistics.median(waits)
    return out


def above_100(out: dict) -> list:
    return [
        f for k, v in out.items() if k.endswith(SHARES)
        for f in program_spans.above_100(k, v)
    ]


# -- what the per-layer metrics read -------------------------------------------
_CACHE: dict = {}


def for_run(run) -> dict:
    """``report`` of ``run``'s trace, once a process: ``{}`` where there is
    nothing to read (no trace, no ``ps.`` spans, a driver with no body)."""
    acc = program_spans.for_run(run)
    if acc is None:
        return {}
    if acc.path not in _CACHE:
        _CACHE[acc.path] = report(
            acc, [(s.start, s.end, s.ok) for s in run.steps], run.config,
            run.sizes["batch"], run.peaks, run.moe,
            run.window.get("examples_per_s"), run.bench_dir,
        )
    return _CACHE[acc.path]


def read(run, name: str) -> Optional[float]:
    return for_run(run).get(name)


def main(argv) -> int:
    root = os.path.dirname(cell_lib.BENCH_DIR)
    series = cell_lib.load_json(argv[0])
    acc = program_spans.load(argv[1])
    bench = cell_lib.load_json(os.path.join(root, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == series["cell"])
    cfg = cell_lib.load_json(os.path.join(root, next(
        c["file"] for c in bench["configs"] if c["name"] == cell["config"]
    )))
    steps = [(a, b, ok) for _w, _i, a, b, ok, _spans in series["steps"]]
    moe = None
    if len(argv) > 2:  # the driver's file, or held slots a step as a number
        moe = (cell_lib.load_json(argv[2]) if argv[2].endswith(".json")
               else {"held_slots_mean": float(argv[2])})
    out = report(
        acc, steps, cfg, cfg["batch_per_worker"],
        peaks_for(series["result"]["device"]["kind"]), moe,
        series["window"]["examples_per_s"],
    )
    print(json.dumps(out, indent=1))
    fails = above_100(out)
    for f in fails:
        print(f"{f}: the count is too high or the time leaves out work",
              file=sys.stderr)
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
