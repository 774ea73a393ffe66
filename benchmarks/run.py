#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmarks/run.py --workload <cell> --dry-run     # CPU, tiny sizes

The cell, its configuration, its traffic mix, its driver, its generator and
its per-layer metrics are found by name (``benchmarks/README.md``).  The
last line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``.  Everything
else goes to standard error.  Without a TPU (and without ``--dry-run``) the
run fails and prints no result.

The command is a parent that never touches jax and a child that does the
run (``--started``).  A child whose set-up had to compile a program (a first
run in a checkout) stops before its window with ``RERUN``, and the parent
starts a second child, which finds every program in the persistent cache:
every window is measured in a process that loaded its programs, because a
process that compiled them itself steps faster (``PERF.md``, section 6,
study 1).  ``setup_s`` runs from the parent's start, the first child
included.
"""

import time

T_PERF = time.perf_counter()  # this process's start, on the step clock
T_MONO = time.monotonic()  # the same moment on the clock processes share

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: host environment, the same in every cell, set before jax is imported:
#: the native BLAS / OpenMP pools of NumPy would otherwise start one thread
#: per core beside the cluster's own threads.
HOST_ENV = {"OMP_NUM_THREADS": "1", "OPENBLAS_NUM_THREADS": "1",
            "MKL_NUM_THREADS": "1"}

TRACE_DELAY_S, TRACE_SECONDS = 2.0, 4.0  # traced slice of the window
RERUN = 75  # a child's exit code: its set-up compiled, nothing was measured


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _trace_thread(clock, logdir, seconds, out):
    """Trace a few seconds of the window: the profiler's start and stop are
    slow and stay off the workers' threads."""
    import jax

    from benchmarks.harness import trace_reduce

    while clock.t0 is None:
        time.sleep(0.01)
    delay = min(TRACE_DELAY_S, seconds / 4)
    time.sleep(max(0.0, clock.t0 + delay - clock.now()))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # no per-call Python events: host-bound cells
    opts.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=opts)
    out["t_start"] = clock.now()
    # the traced window, on the trace's own clock: idle before the first
    # device operation and after the last one counts
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        time.sleep(min(TRACE_SECONDS, seconds / 2))
    out["t_end"] = clock.now()
    jax.profiler.stop_trace()
    out["t_stopped"] = clock.now()


def main(argv=None, bench_dir=BENCH_DIR) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-run", action="store_true",
                    help="tiny sizes on CPU devices; proves nothing")
    # the parent's own: its start on time.monotonic(), and whether a
    # set-up that compiles stops with RERUN
    ap.add_argument("--started", type=float, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--rerun-if-compiled", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    # the result line is the only thing standard output ever carries
    result_out, sys.stdout = sys.stdout, sys.stderr
    try:
        return _run(args, bench_dir, result_out)
    finally:
        sys.stdout = result_out


def _run(args, bench_dir, result_out) -> int:
    for k, v in HOST_ENV.items():
        os.environ.setdefault(k, v)
    # seconds the command had run before this process started
    before = 0.0 if args.started is None else T_MONO - args.started

    from benchmarks.harness import cell as cell_lib

    root = os.path.dirname(bench_dir)
    bench = cell_lib.load_json(os.path.join(root, "BENCHMARK.json"))
    seconds = args.seconds
    if seconds is None:
        seconds = 2.0 if args.dry_run else float(bench["run_seconds"])
    run = cell_lib.resolve(
        bench, args.workload, seed=args.seed, seconds=seconds,
        trace=args.trace, dry_run=args.dry_run, bench_dir=bench_dir,
    )

    from parameter_server_tpu import native
    from parameter_server_tpu.utils import platform

    if args.dry_run:
        platform.force_cpu(run.chips)
    cache_dir = platform.enable_compile_cache()
    import jax

    from benchmarks.harness import cluster as cluster_lib
    from benchmarks.harness import correctness, trace_reduce
    from benchmarks.harness.compile_meter import CompileMeter
    from benchmarks.harness.peaks import peaks_for
    from benchmarks.harness.stats import hist_delta
    from benchmarks.harness.window import StepClock, window_metrics

    run.device = (
        platform.device_stamp() if args.dry_run else platform.require_tpu()
    )
    if jax.local_device_count() < run.chips:
        log(f"run.py: cell {run.name} needs {run.chips} chips, jax found "
            f"{jax.local_device_count()}; nothing was run")
        return 1
    if not args.dry_run:
        run.peaks = peaks_for(run.device["kind"])
    # the chip path takes no quiet Python fallback for key localization
    native.load("keymap", required=True)
    meter = CompileMeter()
    log(json.dumps({
        "cell": run.name, "sizes": run.sizes, "device": run.device,
        "compile_cache": cache_dir, "native": native.loaded(),
        "seed": run.seed, "seconds": seconds, "trace": run.trace,
    }))

    driver = cell_lib.load_module(
        "drivers", run.config["driver"], bench_dir
    ).Driver(run)
    fails = []
    cluster = driver.setup()
    try:
        run.planes = next(iter(cluster.placement.values()))["planes"]
        log(f"[setup] cluster and batches at {time.perf_counter() - T_PERF + before:.1f} s: "
            f"{json.dumps(cluster.placement)}")
        ref_fails, ref_info = correctness.push_pull_check(cluster, run.seed)
        fails += ref_fails
        fails += driver.grad_check()
        log(f"[setup] reference checks at {time.perf_counter() - T_PERF + before:.1f} s: "
            f"{json.dumps(ref_info)} {fails}")

        state = {}

        def on_open():
            state["warm"] = meter.snapshot()
            if args.rerun_if_compiled and state["warm"]["cache_misses"]:
                log(f"[setup] compiled in set-up {json.dumps(state['warm'])}: "
                    "the window is left to a process that loads its programs")
                os._exit(RERUN)  # nothing to save; the cache is on disk
            gc.collect()
            gc.freeze()  # set-up's objects leave the collector's sight
            state["before"] = cluster_lib.cluster_counters(cluster)
            state["h_before"] = cluster_lib.ledger_digests(cluster)
            state["loss_n0"] = driver.loss_count()
            meter.reset()

        clock = StepClock(
            run.sizes["workers"], run.sizes["warmup"], seconds, on_open
        )
        tracing, tracer = {}, None
        logdir = os.path.join(bench_dir, "out", "trace", run.name)
        if run.trace:
            shutil.rmtree(logdir, ignore_errors=True)  # an older run's trace
            tracer = threading.Thread(
                target=_trace_thread, args=(clock, logdir, seconds, tracing),
                name="bench-tracer", daemon=True,
            )
            tracer.start()
        driver.train(clock)
        run.compiles_in_window = meter.events()
        after = cluster_lib.cluster_counters(cluster)
        h_after = cluster_lib.ledger_digests(cluster)
        if tracer is not None:
            tracer.join(timeout=300)
        if clock.t0 is None or clock.t_stop is None:
            raise RuntimeError("the run ended before the window closed")
        setup_s = clock.t0 - T_PERF + before

        run.steps = clock.window_steps()
        run.window = window_metrics(
            run.steps, clock.t0, seconds, run.sizes["batch"]
        )
        run.counters = {
            k: after[k] - state["before"].get(k, 0) for k in after
        }
        run.hists = {}
        for sid, digests in h_after.items():
            for name, d in digests.items():
                delta = hist_delta(d, state["h_before"].get(sid, {}).get(name))
                acc = run.hists.setdefault(name.split(".")[0], {})
                for b, c in delta.items():
                    acc[b] = acc.get(b, 0) + c
        losses = driver.losses()[state["loss_n0"]:]
        cycle_steps = run.sizes["cycle"] * run.sizes["workers"]
        log(f"[loss] first and last stretch of {len(losses)}: "
            f"{correctness.loss_stretches(losses, cycle_steps)}")
        fails += correctness.window_checks(
            after, losses, driver.retired(), run.compiles_in_window,
            cycle_steps=cycle_steps,
        )
        if run.window["failed"]:
            fails.append(f"{run.window['failed']} steps failed")
        import numpy as np

        sample = np.unique(np.concatenate(
            [driver.keys_of(mine[0]).ravel() for mine in driver.batches]
        ))[:4096]
        acked = sum(s.ok for s in clock.steps) + ref_info.get("pushes", 0)
        fails += correctness.after_checks(cluster, sample, acked)
        run.memory_peak_bytes = max(
            ((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()[: run.chips]), default=0,
        )
    finally:
        driver.close()

    device = dict(run.device, memory_peak_bytes=run.memory_peak_bytes)
    out = {"correct": True, "attempted": run.window["attempted"],
           "failed": run.window["failed"], "metrics": {}, "device": device}
    if run.trace:
        path = trace_reduce.find_xplane(logdir)
        if path is None:
            fails.append("the traced run left no .xplane.pb")
        else:
            run.trace_reduced = trace_reduce.reduce_trace(path)
            if run.trace_reduced["window_from"] != "span":
                fails.append("the trace holds no span of the traced window")
            red = run.trace_reduced
            log(f"[trace] {path}: planes {red['planes']} lines {red['lines']} "
                f"host span threads {red['host_span_threads']}")
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            out["breakdown"] = {"device_ops": red["device_ops"],
                                "idle_gaps": red["idle_gaps"]}
            if not args.dry_run and not red["busy_s"] > 0:
                fails.append("no operation ran on the device in the trace")
        run.unique_rows_per_step = correctness.unique_rows_per_step(
            cluster, driver.batches[0], driver.keys_of
        )
        for entry in cell_lib.layer_metrics_for(run):
            mod = cell_lib.load_module("layer_metrics", entry["name"], bench_dir)
            value = mod.read(run)
            if value is None:
                continue  # nothing to read: the metric is left out
            out["metrics"][entry["name"]] = {
                "value": float(value), "unit": entry["unit"],
            }
            check = getattr(mod, "check", None)
            if check is not None:
                fails += check(float(value))
    else:
        e2e = dict(run.window, setup_s=setup_s)
        for entry in bench["end_to_end"]:
            if "workloads" in entry and run.name not in entry["workloads"]:
                continue
            value = e2e.get(entry["name"])
            if value is None:
                fails.append(f"no value for {entry['name']}")
                continue
            out["metrics"][entry["name"]] = {
                "value": float(value), "unit": entry["unit"],
            }

    out["correct"] = not fails
    series = {
        "cell": run.name, "seed": run.seed, "seconds": seconds,
        "trace": run.trace, "t0": clock.t0, "setup_s": setup_s,
        "window": run.window, "compile": {"warm": state["warm"],
                                          "in_window": run.compiles_in_window},
        "counters": run.counters, "fails": fails, "tracing": tracing,
        "steps": [
            [s.worker, s.index, s.start - clock.t0, s.end - clock.t0, s.ok,
             [[n, a - clock.t0, b - clock.t0] for n, a, b in s.spans]]
            for s in run.steps
        ],
        "result": out,
    }
    os.makedirs(os.path.join(bench_dir, "out", "series"), exist_ok=True)
    stamp = time.strftime("%Y%m%dT%H%M%S")
    with open(os.path.join(
        bench_dir, "out", "series",
        f"{run.name}.seed{run.seed}.trace{run.trace}.{stamp}.json",
    ), "w") as f:
        json.dump(series, f)
    log(f"[window] {json.dumps(run.window)} setup_s {setup_s:.2f} "
        f"compiles_in_window {run.compiles_in_window} "
        f"warm {json.dumps(state['warm'])} wall "
        f"{time.perf_counter() - T_PERF + before:.1f} s")
    for f_ in fails:
        log(f"[incorrect] {f_}")
    print(json.dumps(out), file=result_out, flush=True)
    return 0


def _child(cmd) -> int:
    """Run one child to its end; a signal that ends the parent ends it."""
    p = subprocess.Popen(cmd)
    signals = (signal.SIGTERM, signal.SIGINT)
    old = [signal.signal(s, lambda *_: p.terminate()) for s in signals]
    try:
        return p.wait()
    finally:
        for s, handler in zip(signals, old):
            signal.signal(s, handler)


def command(argv) -> int:
    """The parent: at most two children, the second only after a first
    whose set-up compiled.  It never imports jax, so the chip is the
    child's."""
    child = [sys.executable, os.path.abspath(__file__), *argv,
             "--started", repr(T_MONO)]
    rc = _child(child + ["--rerun-if-compiled"])
    if rc == RERUN:
        rc = _child(child)
    return rc


if __name__ == "__main__":
    if "--started" in sys.argv:
        sys.exit(main())
    sys.exit(command(sys.argv[1:]))
