"""Median over the run's steps of the fullest held expert's slots over the
held experts' mean (the driver's counts, ``Run.moe``): 1 is an even load.
``harness/model_scopes.py`` reads it; a cell whose driver runs no such
body reads nothing."""

from benchmarks.harness import model_scopes

NAME, UNIT, LAYER, MOVES = "moe_load_max_over_mean", "ratio", "model body", "step_ms_p50"


def read(run):
    return model_scopes.read(run, NAME)
