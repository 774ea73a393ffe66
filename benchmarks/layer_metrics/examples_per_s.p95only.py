"""``examples_per_s`` of the window (``harness/window.py::window_metrics``, the same
arithmetic as the end-to-end metric of that name) in the cells where it
spreads too widely between runs to be held to a bound (``PERF.md``, section
2): reported per layer, without one.  In a traced run the profiler session
is inside the window, so compare traced with traced."""

NAME, UNIT, LAYER, MOVES = "examples_per_s.p95only", "examples/s", "worker step", "step_ms_p95"


def read(run):
    return run.window.get("examples_per_s")
