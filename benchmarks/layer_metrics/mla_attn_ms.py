"""Device ms a step under ``ps.model.mla.attn``: blocked causal attention of
the latent-attention layer, forward and backward.
``harness/model_scopes.py`` reads it; a cell whose driver runs no such
body reads nothing."""

from benchmarks.harness import model_scopes

NAME, UNIT, LAYER, MOVES = "mla_attn_ms", "ms", "model kernels", "step_ms_p50"


def read(run):
    return model_scopes.read(run, NAME)
