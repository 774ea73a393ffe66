"""The reduction from ``.xplane.pb`` to busy, idle, top operations and idle
gaps, on the small recorded file ``data/small.xplane.pb`` (its text is in
``data/make_fixture.py``).  By hand, in milliseconds from the window's start:

chip 0 operations: while.3 [0, 4] with fusion.2 [1, 2] inside it, fusion.2
[5, 7], copy.1 [9, 9.05]: union 6.05.  Chip 1: fusion.2 [2, 4]: union 2.
Window 10: mean busy 4.025, idle 59.75 %.  Host threads: worker 0 pulls
[0, 4], grad [4, 5], pushes [5, 9]; worker 1 pulls [0, 1], pushes [4, 10];
the tracing thread holds ``bench.traced_window`` open over [0, 10].
"""

import importlib.util
import os

import pytest

from benchmarks.harness.trace_reduce import reduce_trace, union_seconds

PB = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small.xplane.pb")
WINDOW = (0.001, 0.011)  # the lines' timestamp_ns is 1 ms


def test_union_counts_nested_and_overlapping_once():
    total, merged = union_seconds([(0, 4), (1, 2), (3, 6), (8, 9)])
    assert total == 7 and merged == [[0, 6], [8, 9]]


def test_busy_idle_and_top_operations():
    r = reduce_trace(PB, window=WINDOW)
    assert r["chips"] == 2 and r["host_span_threads"] == 2
    assert r["busy_s_per_chip"] == pytest.approx([6.05e-3, 2e-3])
    assert r["busy_s"] == pytest.approx(4.025e-3)
    assert r["window_s"] == pytest.approx(10e-3)
    assert r["idle_pct"] == pytest.approx(59.75)
    ops = dict(r["device_ops"])
    # seconds per chip, a `while` beside the body it contains
    assert list(ops) == ["fusion.2", "while.3", "copy.1"]
    assert ops["fusion.2"] == pytest.approx(2.5e-3)
    assert ops["while.3"] == pytest.approx(2e-3)
    assert ops["copy.1"] == pytest.approx(25e-6)


def test_idle_gaps_by_the_host_span_in_flight():
    gaps = dict(reduce_trace(PB, window=WINDOW)["idle_gaps"])
    assert gaps["push"] == pytest.approx(4.4875e-3)
    assert gaps["between_steps"] == pytest.approx(0.7375e-3)
    assert gaps["pull"] == pytest.approx(0.5e-3)
    assert gaps["grad"] == pytest.approx(0.25e-3)
    # the gaps add up to one chip's mean idle time
    assert sum(gaps.values()) == pytest.approx(10e-3 - 4.025e-3)


def test_window_is_the_tracing_thread_s_span():
    """Idle before the first operation and after the last one counts, and
    the tracing thread is not one of the host threads gaps are laid to."""
    r = reduce_trace(PB)
    assert r["window_from"] == "span" and r["host_span_threads"] == 2
    assert r["window_s"] == pytest.approx(10e-3)
    assert r["idle_pct"] == pytest.approx(59.75)


def test_window_without_a_span_is_first_start_to_last_end(tmp_path):
    spec = importlib.util.spec_from_file_location(
        "make_fixture", os.path.join(os.path.dirname(PB), "make_fixture.py")
    )
    fixture = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fixture)
    path = str(tmp_path / "no_span.xplane.pb")
    fixture.write(path, fixture.TEXT.replace("bench.traced_window", "other"))
    r = reduce_trace(path)
    assert r["window_from"] == "operations"
    assert r["window_s"] == pytest.approx(9.05e-3)
    assert r["busy_s"] == pytest.approx(4.025e-3)
