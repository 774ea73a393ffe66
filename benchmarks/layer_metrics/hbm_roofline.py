"""Share of the HBM roofline the table kernels reach, taken from the whole
device-busy time (no kernel names are needed): the bytes the algorithm
needs per second of the window (steps per second x unique rows a step
touches x row bytes x (1 read for the pull + (1 + planes) reads + (1 +
planes) writes for the apply)) over the chips' peak bandwidth, over the
share of the traced window in which the device was busy.  Above 100 the
byte count or the busy time is wrong: the traced run fails."""

from benchmarks.harness.bytes_model import hbm_roofline_pct, step_hbm_bytes

NAME, UNIT, LAYER, MOVES = "hbm_roofline", "%", "kernels", "step_ms_p50"


def read(run):
    red, done = run.trace_reduced, [s for s in run.steps if s.ok]
    if not red or not red.get("busy_s") or run.peaks is None or not done:
        return None
    span = max(s.end for s in done) - min(s.start for s in done)
    steps_per_s = len(done) / span
    per_step = step_hbm_bytes(
        run.unique_rows_per_step, run.config["table"]["dim"], run.planes - 1
    )
    busy_share = red["busy_s"] / red["window_s"]  # mean over the chips
    return hbm_roofline_pct(
        steps_per_s * per_step,
        busy_share * red["chips"],
        run.peaks["hbm_bytes_per_s"],
    )


def check(value):
    return [f"hbm_roofline = {value:.3f} % is above 100"] if value > 100 else []
