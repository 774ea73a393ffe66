"""Median of the program's ``ps.worker.localize`` spans in the traced window:
``localize_to_slots`` (hash, two ``np.unique``, bucket pad) in
``KVWorker.pull`` and in ``_prepare_push``, once each a step."""

from benchmarks.harness import program_spans

NAME, UNIT, LAYER, MOVES = "worker_localize_ms_p50", "ms", "worker wire", "step_ms_p50"


def read(run):
    return program_spans.span_ms_p50(run, "ps.worker.localize")
