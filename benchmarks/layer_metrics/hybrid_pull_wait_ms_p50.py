"""Median of the trainer's ``ps.hybrid.pull_wait`` spans in the traced
window: how long a step waits for its (prefetched) embedding rows.
``harness/model_scopes.py`` reads it; a cell whose driver runs no such
body reads nothing."""

from benchmarks.harness import model_scopes

NAME, UNIT, LAYER, MOVES = "hybrid_pull_wait_ms_p50", "ms", "model body", "step_ms_p50"


def read(run):
    return model_scopes.read(run, NAME)
