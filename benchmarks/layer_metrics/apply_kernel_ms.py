"""Device time of one push's apply on one shard: device seconds under the
scope ``ps.table.apply`` (``KVTable._apply_core``) in the traced window,
summed over the chips, over the window's ``ps.server.dispatch`` spans with an
``op`` of push."""

from benchmarks.harness import program_spans

NAME, UNIT, LAYER, MOVES = "apply_kernel_ms", "ms", "kernels", "step_ms_p50"


def read(run):
    return program_spans.kernel_ms(run, "ps.table.apply", "push")
