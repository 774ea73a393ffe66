"""Share of the HBM roofline the apply reaches: the bytes the window's pushes
need (unique rows a step touches x row bytes x 2 x (1 + optimizer planes),
``bytes_model``) over the chip's peak bandwidth, over the device seconds under
``ps.table.apply``.  Above 100 the byte count or the device time is wrong:
the traced run fails."""

from benchmarks.harness import program_spans
from benchmarks.harness.bytes_model import apply_bytes

NAME, UNIT, LAYER, MOVES = "apply_kernel_roofline", "%", "kernels", "step_ms_p50"


def read(run):
    if run.unique_rows_per_step is None:
        return None
    return program_spans.kernel_roofline_pct(
        run, "ps.table.apply", "push",
        apply_bytes(
            run.unique_rows_per_step, run.config["table"]["dim"], run.planes - 1
        ),
    )


def check(value):
    return program_spans.above_100(NAME, value)
