"""Median of the program's ``ps.worker.localize`` spans in the traced window:
``KVWorker._localize``, opened only where a localization is computed (PR 32:
once a step in a pull-then-push loop, under the pull; the push reuses it),
and since PR 34 one native pass (``engine`` ``native``) wherever the keymap
library loaded."""

from benchmarks.harness import program_spans

NAME, UNIT, LAYER, MOVES = "worker_localize_ms_p50", "ms", "worker wire", "step_ms_p50"


def read(run):
    return program_spans.span_ms_p50(run, "ps.worker.localize")
