"""Logical wire bytes (``MeteredVan`` via ``transport_counters``) between
window open and the last step's end, over the examples of the steps that
started and ended in between.  Heartbeats are in it."""

NAME, UNIT, LAYER, MOVES = "wire_bytes_per_example", "B/example", "van", "examples_per_s"


def read(run):
    examples = sum(1 for s in run.steps if s.ok) * run.sizes["batch"]
    if not examples or "wire_bytes" not in run.counters:
        return None
    return run.counters["wire_bytes"] / examples
