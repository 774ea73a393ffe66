"""Published per-chip peaks, keyed by ``device_kind`` as the chip reports it.

Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB of HBM
at 819 GB/s), as quoted in the on-chip-measurement guide.  The benchmark keeps
its own copy so that no later change to the program can move a roofline share.
A kind that is not in the table is an error, never a default."""

PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r}: add it to "
            "benchmarks/harness/peaks.py with its source"
        ) from None


def bounds_s(work: dict, peaks: dict) -> dict:
    """The least time the chip could take for ``work`` (``{"flops",
    "bytes"}``) by each of its two peaks; a roofline is the larger."""
    return {"flops": work["flops"] / peaks["flops"],
            "bytes": work["bytes"] / peaks["hbm_bytes_per_s"]}
