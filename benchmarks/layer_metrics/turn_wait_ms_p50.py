"""Median of the workers' ``ps.worker.turn`` spans (PR 37), the
bounded-delay wait; 0.0 where the trainer holds no controller.
``harness/host_cpu.py::METRICS`` holds its reading, unit, layer and
``moves``."""

from benchmarks.harness import host_cpu

NAME = "turn_wait_ms_p50"
_M = host_cpu.METRICS[NAME]
UNIT, LAYER, MOVES = _M.unit, _M.layer, _M.moves


def read(run):
    return host_cpu.read(run, NAME)
