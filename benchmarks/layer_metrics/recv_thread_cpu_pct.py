"""CPU the servers' recv threads used inside their deliveries over the wall
time the deliveries cover (PR 37): whether a busy recv thread computes or
queues for the GIL.  Left out under ``host_cpu.MIN_TICKS`` ticks of the
CPU clock; above 105 the traced run fails.
``harness/host_cpu.py::METRICS`` holds its reading, unit, layer and
``moves``."""

from benchmarks.harness import host_cpu

NAME = "recv_thread_cpu_pct"
_M = host_cpu.METRICS[NAME]
UNIT, LAYER, MOVES = _M.unit, _M.layer, _M.moves


def read(run):
    return host_cpu.read(run, NAME)


def check(value):
    return host_cpu.checks({NAME: value})
