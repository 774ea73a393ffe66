"""Share of its roofline the latent layer's attention reaches (the causal
half).  Above 100 the traced run fails.
``harness/model_scopes.py`` reads it; a cell whose driver runs no such
body reads nothing."""

from benchmarks.harness import model_scopes, program_spans

NAME, UNIT, LAYER, MOVES = "mla_attn_roofline", "%", "model kernels", "step_ms_p50"


def read(run):
    return model_scopes.read(run, NAME)


def check(value):
    return program_spans.above_100(NAME, value)
