"""Device ms a step under the expert layer's dispatch, grouped products and
combine (``ps.model.moe.dispatch`` / ``.experts`` / ``.combine``).
``harness/model_scopes.py`` reads it; a cell whose driver runs no such
body reads nothing."""

from benchmarks.harness import model_scopes

NAME, UNIT, LAYER, MOVES = "moe_experts_ms", "ms", "model kernels", "step_ms_p50"


def read(run):
    return model_scopes.read(run, NAME)
