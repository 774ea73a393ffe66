"""Shared code of the benchmark.  Nothing in this package knows a cell, a
configuration, a traffic mix, a driver or a per-layer metric by name: those
are files found through ``BENCHMARK.json`` (see ``benchmarks/README.md``)."""
