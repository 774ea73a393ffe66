"""Median time a PUSH or PULL request stood in a server's inbox before its
recv thread took it: ``wait_us`` of the ``ps.van.deliver`` spans that deliver
such a request (``MeteredVan``'s send stamp to dequeue, an attribute because
the wait began on the sender's thread)."""

import statistics

from benchmarks.harness import program_spans

NAME, UNIT, LAYER, MOVES = "server_queue_wait_ms_p50", "ms", "van", "step_ms_p50"


def read(run):
    acc = program_spans.for_run(run)
    if acc is None:
        return None
    waits = [
        sp.attrs["wait_us"] / 1e3
        for sp in acc.by_name.get("ps.van.deliver", [])
        if sp.attrs.get("is_request") and sp.attrs.get("verb") in ("PUSH", "PULL")
        and "wait_us" in sp.attrs
    ]
    return statistics.median(waits) if waits else None
