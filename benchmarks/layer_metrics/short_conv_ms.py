"""Device ms a step of the gated short convolutions: ``ps.model.conv.proj``
+ ``.gate`` + ``.out``.
``harness/model_scopes.py`` reads it; a cell whose driver runs no such
body reads nothing."""

from benchmarks.harness import model_scopes

NAME, UNIT, LAYER, MOVES = "short_conv_ms", "ms", "model kernels", "step_ms_p50"


def read(run):
    return model_scopes.read(run, NAME)
