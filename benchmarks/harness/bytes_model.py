"""Bytes and operations the algorithm needs, from shapes alone.

A worker step moves 5 x 4 B at the table for a dim-1 row with AdaGrad (the
pull reads the weight; the apply reads the weight and its accumulator and
writes both back); this counts rows of any ``dim`` and any number of
optimizer planes, per UNIQUE row touched: the server applies pre-combined
rows, so duplicates cost the table nothing."""


def row_bytes(dim, itemsize=4):
    return dim * itemsize


def pull_bytes(unique_rows, dim, itemsize=4):
    """A pull reads each unique row's value once."""
    return unique_rows * row_bytes(dim, itemsize)


def apply_bytes(unique_rows, dim, planes, itemsize=4):
    """An apply reads value + ``planes`` optimizer planes and writes them
    back: (1 + planes) reads and (1 + planes) writes per unique row."""
    return 2 * (1 + planes) * unique_rows * row_bytes(dim, itemsize)


def step_hbm_bytes(unique_rows, dim, planes, itemsize=4):
    """HBM bytes one worker step needs at the table: 1 read for the pull,
    (1 + planes) reads and (1 + planes) writes for the apply."""
    return pull_bytes(unique_rows, dim, itemsize) + apply_bytes(
        unique_rows, dim, planes, itemsize
    )


def adagrad_flops(unique_rows, dim):
    """square, accumulate, sqrt, add eps, divide, multiply, subtract."""
    return 7 * unique_rows * dim


def hbm_roofline_pct(bytes_needed, busy_s, hbm_bytes_per_s):
    """Share of the HBM roofline: least time the bytes could take over the
    time the device was busy.  Never clamped: above 100 the byte count or the
    busy time is wrong, and the traced run fails on it."""
    if busy_s <= 0:
        return None
    return 100.0 * (bytes_needed / hbm_bytes_per_s) / busy_s
