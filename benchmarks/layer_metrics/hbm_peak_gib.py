"""``memory_stats()["peak_bytes_in_use"]``, maximum over the cell's chips."""

NAME, UNIT, LAYER, MOVES = "hbm_peak_gib", "GiB", "device", "examples_per_s"


def read(run):
    return run.memory_peak_bytes / 2**30 if run.memory_peak_bytes else None
