"""Median of the benchmark's span around the gradient call (ending in
``block_until_ready``), over the window's steps of the traced run."""

from benchmarks.harness.stats import percentile, span_ms

NAME, UNIT, LAYER, MOVES = "grad_ms_p50", "ms", "worker step", "step_ms_p50"


def read(run):
    return percentile(span_ms(run.steps, "grad"), 50)
