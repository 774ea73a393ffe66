"""``step_ms_p50`` of the window (``harness/window.py::window_metrics``, the same
arithmetic as the end-to-end metric of that name) in the cells where it
spreads too widely between runs to be held to a bound (``PERF.md``, section
2): reported per layer, without one.  In a traced run the profiler session
is inside the window, so compare traced with traced."""

NAME, UNIT, LAYER, MOVES = "step_ms_p50.p95only", "ms", "worker step", "step_ms_p95"


def read(run):
    return run.window.get("step_ms_p50")
