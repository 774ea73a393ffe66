"""Median of the program's ``ps.server.push`` spans in the traced window: from
validation to the acknowledgement built.  The acknowledgement leaves once the
apply is dispatched (the configurations' guarantee), so no device time is in
it: that is ``apply_kernel_ms``."""

from benchmarks.harness import program_spans

NAME, UNIT, LAYER, MOVES = "server_push_busy_ms_p50", "ms", "server apply", "step_ms_p50"


def read(run):
    return program_spans.span_ms_p50(run, "ps.server.push")
