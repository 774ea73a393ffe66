"""Device ms a step and layer under ``ps.model.gdn.scan``: the chunked gated
delta rule of the DeltaNet layers, the scope's time over those layers.
``harness/model_scopes.py`` reads it; a cell whose driver runs no such
body reads nothing."""

from benchmarks.harness import model_scopes

NAME, UNIT, LAYER, MOVES = "gdn_scan_ms", "ms", "model kernels", "step_ms_p50"


def read(run):
    return model_scopes.read(run, NAME)
