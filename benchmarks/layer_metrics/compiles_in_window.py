"""Backend compiles plus persistent-cache loads between window open and
close, from the benchmark's copy of ``chip_smoke.py::CompileMeter``.  Must
be 0: a run with any, traced or not, is ``correct: false``
(``harness/correctness.py::window_checks``)."""

NAME, UNIT, LAYER, MOVES = "compiles_in_window", "count", "compile cache", "examples_per_s"


def read(run):
    return run.compiles_in_window

