"""1 - union of the device operations' intervals over the traced window,
per chip, mean over the cell's chips."""

NAME, UNIT, LAYER, MOVES = "device_idle_pct", "%", "device", "examples_per_s"


def read(run):
    return (run.trace_reduced or {}).get("idle_pct")
