"""Median over the servers' ``ps.server.pull`` / ``.push`` spans of what
their stages leave uncovered (PR 37): the coverage of the server's host
path.
``harness/host_cpu.py::METRICS`` holds its reading, unit, layer and
``moves``."""

from benchmarks.harness import host_cpu

NAME = "server_self_ms_p50"
_M = host_cpu.METRICS[NAME]
UNIT, LAYER, MOVES = _M.unit, _M.layer, _M.moves


def read(run):
    return host_cpu.read(run, NAME)
