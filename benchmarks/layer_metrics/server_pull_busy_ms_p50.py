"""Median of the program's ``ps.server.pull`` spans in the traced window: from
validation to the reply built, the D2H of the rows included, so it ends at
completion and not at the enqueue."""

from benchmarks.harness import program_spans

NAME, UNIT, LAYER, MOVES = "server_pull_busy_ms_p50", "ms", "server apply", "step_ms_p50"


def read(run):
    return program_spans.span_ms_p50(run, "ps.server.pull")
