"""Share of the HBM roofline the gather reaches: the bytes the window's pulls
need (unique rows a step touches x row bytes x 1 read, ``bytes_model``) over
the chip's peak bandwidth, over the device seconds under ``ps.table.pull``.
Above 100 the byte count or the device time is wrong: the traced run
fails."""

from benchmarks.harness import program_spans
from benchmarks.harness.bytes_model import pull_bytes

NAME, UNIT, LAYER, MOVES = "gather_kernel_roofline", "%", "kernels", "step_ms_p50"


def read(run):
    if run.unique_rows_per_step is None:
        return None
    return program_spans.kernel_roofline_pct(
        run, "ps.table.pull", "pull",
        pull_bytes(run.unique_rows_per_step, run.config["table"]["dim"]),
    )


def check(value):
    return program_spans.above_100(NAME, value)
