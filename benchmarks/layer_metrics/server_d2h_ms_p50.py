"""Median of the program's ``ps.server.d2h`` spans in the traced window: the
``np.asarray`` of a pull's rows on the server's recv thread, which waits for
the gather behind whatever the device had queued."""

from benchmarks.harness import program_spans

NAME, UNIT, LAYER, MOVES = "server_d2h_ms_p50", "ms", "server apply", "step_ms_p50"


def read(run):
    return program_spans.span_ms_p50(run, "ps.server.d2h")
