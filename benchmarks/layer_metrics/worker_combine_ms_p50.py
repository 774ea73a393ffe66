"""Median of the workers' ``ps.worker.combine`` spans (PR 37): a push's
duplicate keys' gradients summed.
``harness/host_cpu.py::METRICS`` holds its reading, unit, layer and
``moves``."""

from benchmarks.harness import host_cpu

NAME = "worker_combine_ms_p50"
_M = host_cpu.METRICS[NAME]
UNIT, LAYER, MOVES = _M.unit, _M.layer, _M.moves


def read(run):
    return host_cpu.read(run, NAME)
