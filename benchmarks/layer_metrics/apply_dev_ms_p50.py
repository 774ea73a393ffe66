"""Median device phase of a server apply: the ``ApplyLedger``'s
``apply_dev`` histogram, window delta, all servers pooled.  It runs from
dispatch to ready, so the wait in the device's queue is in it.  The
histogram resolves 25 %; the value is interpolated inside the bucket."""

from benchmarks.harness.stats import hist_percentile_ms

NAME, UNIT, LAYER, MOVES = "apply_dev_ms_p50", "ms", "server apply", "step_ms_p50"


def read(run):
    return hist_percentile_ms(run.hists.get("apply_dev", {}), 50)
