"""Median host-to-device phase of a server apply: the ``ApplyLedger``'s
``apply_h2d`` histogram, window delta, all servers pooled.  The histogram
resolves 25 %; the value is interpolated inside the bucket."""

from benchmarks.harness.stats import hist_percentile_ms

NAME, UNIT, LAYER, MOVES = "apply_h2d_ms_p50", "ms", "server apply", "step_ms_p50"


def read(run):
    return hist_percentile_ms(run.hists.get("apply_h2d", {}), 50)
