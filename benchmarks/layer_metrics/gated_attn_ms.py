"""Device ms a step and layer under ``ps.model.attn.full``: the causal tiles
at head size 256 of the gated attention layers (the element-wise gate on
their output fuses into its neighbours on the chip), the scope's time over
those layers.  ``harness/model_scopes.py`` reads it; a cell whose driver
runs no such body reads nothing."""

from benchmarks.harness import model_scopes

NAME, UNIT, LAYER, MOVES = "gated_attn_ms", "ms", "model kernels", "step_ms_p50"


def read(run):
    return model_scopes.read(run, NAME)
