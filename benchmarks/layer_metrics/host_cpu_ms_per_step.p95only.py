"""``host_cpu_ms_per_step`` in the cells that hold only ``step_ms_p95`` end to end
(``PERF.md``, section 2): the same reader, split because those cells report
another end-to-end metric for it to move."""

from benchmarks.harness.cell import base_reader

_BASE = base_reader(__file__)
NAME, UNIT, LAYER, MOVES = "host_cpu_ms_per_step.p95only", _BASE.UNIT, _BASE.LAYER, "step_ms_p95"
read = _BASE.read
check = getattr(_BASE, "check", None)
