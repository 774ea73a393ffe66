"""CPU under every thread's outermost ``ps.`` spans over the window's
``ps.worker.push`` spans, one a worker-step (PR 37).  Left out under
``host_cpu.MIN_TICKS`` ticks of the CPU clock.
``harness/host_cpu.py::METRICS`` holds its reading, unit, layer and
``moves``."""

from benchmarks.harness import host_cpu

NAME = "host_cpu_ms_per_step"
_M = host_cpu.METRICS[NAME]
UNIT, LAYER, MOVES = _M.unit, _M.layer, _M.moves


def read(run):
    return host_cpu.read(run, NAME)
