"""The whole step's share of the chip's peak: model operations a step (the
body module's ``step_flops``: the held share's forward and backward, nothing
recomputed) x the window's steps a second (``examples_per_s`` over the
batch) / 197 TFLOP/s.  Host stalls and idle device time count against it.
Above 100 the count is wrong: the traced run fails.
``harness/model_scopes.py`` reads it; a cell whose driver runs no such
body reads nothing."""

from benchmarks.harness import model_scopes, program_spans

NAME, UNIT, LAYER, MOVES = "mfu_pct", "%", "model body", "examples_per_s"


def read(run):
    return model_scopes.read(run, NAME)


def check(value):
    return program_spans.above_100(NAME, value)
