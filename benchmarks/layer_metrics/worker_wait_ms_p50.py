"""Median of the program's ``ps.worker.wait`` spans in the traced window: from
all legs of a pull or a push submitted to all replies in
(``KVWorker._wait_traced``)."""

from benchmarks.harness import program_spans

NAME, UNIT, LAYER, MOVES = "worker_wait_ms_p50", "ms", "worker wire", "step_ms_p50"


def read(run):
    return program_spans.span_ms_p50(run, "ps.worker.wait")
