"""Median of the benchmark's span around ``KVWorker.pull_sync`` on the
instances the harness built, over the window's steps of the traced run."""

from benchmarks.harness.stats import percentile, span_ms

NAME, UNIT, LAYER, MOVES = "pull_ms_p50", "ms", "worker wire", "step_ms_p50"


def read(run):
    return percentile(span_ms(run.steps, "pull"), 50)
