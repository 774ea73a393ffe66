"""Median of the workers' ``ps.worker.assemble`` spans (PR 37): a pull's
legs put back in the batch's order.
``harness/host_cpu.py::METRICS`` holds its reading, unit, layer and
``moves``."""

from benchmarks.harness import host_cpu

NAME = "worker_assemble_ms_p50"
_M = host_cpu.METRICS[NAME]
UNIT, LAYER, MOVES = _M.unit, _M.layer, _M.moves


def read(run):
    return host_cpu.read(run, NAME)
