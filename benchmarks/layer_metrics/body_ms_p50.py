"""Median device time of the jitted step's executions (``jit_step_fn`` on
the trace's ``XLA Modules`` line) that lie wholly in the traced window.
``harness/model_scopes.py`` reads it; a cell whose driver runs no such
body reads nothing."""

from benchmarks.harness import model_scopes

NAME, UNIT, LAYER, MOVES = "body_ms_p50", "ms", "model body", "step_ms_p50"


def read(run):
    return model_scopes.read(run, NAME)
