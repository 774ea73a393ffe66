"""Device time of one pull's gather on one shard: device seconds under the
scope ``ps.table.pull`` (``KVTable._pull_impl``) in the traced window, summed
over the chips, over the window's ``ps.server.dispatch`` spans with ``op``
pull."""

from benchmarks.harness import program_spans

NAME, UNIT, LAYER, MOVES = "gather_kernel_ms", "ms", "kernels", "step_ms_p50"


def read(run):
    return program_spans.kernel_ms(run, "ps.table.pull", "pull")
