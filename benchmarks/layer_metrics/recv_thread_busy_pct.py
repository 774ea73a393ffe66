"""Share of the traced window in which a server's one recv thread was inside
a delivery (the union of its ``ps.van.deliver`` spans: handler, D2H and reply
included), mean over the servers' recv threads."""

import statistics

from benchmarks.harness import program_spans

NAME, UNIT, LAYER, MOVES = "recv_thread_busy_pct", "%", "van", "examples_per_s"


def read(run):
    acc = program_spans.for_run(run)
    shares = acc.recv_threads() if acc is not None else {}
    return 100.0 * statistics.mean(shares.values()) if shares else None
