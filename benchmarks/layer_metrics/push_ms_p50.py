"""Median of the benchmark's span around ``KVWorker.push_sync`` (in the
``emb_tier`` driver the D2H of the gradient rows is inside it), over the
window's steps of the traced run."""

from benchmarks.harness.stats import percentile, span_ms

NAME, UNIT, LAYER, MOVES = "push_ms_p50", "ms", "worker wire", "step_ms_p50"


def read(run):
    return percentile(span_ms(run.steps, "push"), 50)
