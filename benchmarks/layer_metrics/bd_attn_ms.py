"""Device ms a step and layer under ``ps.model.attn.bd``: block-diffusion
attention over ``[xt ; x0]``, the scope's time over the layers held.
``harness/model_scopes.py`` reads it; a cell whose driver runs no such
body reads nothing."""

from benchmarks.harness import model_scopes

NAME, UNIT, LAYER, MOVES = "bd_attn_ms", "ms", "model kernels", "step_ms_p50"


def read(run):
    return model_scopes.read(run, NAME)
