"""The nine readings of PR 37 (``harness/host_cpu.py::METRICS``) on an
account written out by hand.  In milliseconds
from the window's start (window 100), ``cpu`` in microseconds:

- worker thread ``W``: turn [0, 2]; ``ps.worker.pull`` [2, 30] cpu 8,000
  holds submit [3, 7], wait [7, 25], assemble [25, 29]; ``ps.worker.push``
  [40, 70] cpu 9,000 holds combine [41, 47], submit [47, 49], wait [49, 69];
  turn [70, 74]; a second pull [74, 98] cpu 7,000 holds submit [75, 81], wait
  [81, 90], assemble [90, 96].  One push: one worker-step.
- server 0's recv thread delivers a PULL [8, 20] cpu 6,000 whose
  ``ps.server.pull`` [9, 19] holds localize [9, 11], h2d [11, 12], dispatch
  [12, 13], d2h [13, 17], ack [17, 18.5]: self 0.5; and a PUSH [50, 60] cpu
  9,000 whose ``ps.server.push`` [50.5, 59.5] holds localize [51, 54], h2d
  [54, 56], dispatch [56, 57], ack [57, 59]: self 1.0.
- server 1's recv thread: a PUSH delivery [-5, 5] that crosses the window's
  edge (not in the account) holds ``ps.server.push`` [1, 4] cpu 2,000 with
  ack [3, 3.5]: self 2.5, and outermost in the window; a PULL [82, 88] cpu
  3,000 whose ``ps.server.pull`` [82.5, 87.5] holds localize [83, 84] and d2h
  [84, 87]: self 1.0.
- the worker's recv thread delivers a reply [21, 22] cpu 500.
"""

import json
import os

import pytest

from benchmarks.harness import host_cpu
from benchmarks.harness import program_spans as ps

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e-3
WANT = {
    "server_localize_ms_p50": 2.0,  # of 2, 3, 1
    "server_ack_ms_p50": 1.5,  # of 1.5, 2, 0.5
    "server_self_ms_p50": 1.0,  # of 0.5, 1.0, 2.5, 1.0
    # 15 ms of CPU in 22 ms of deliveries, 3 in 6: the delivery that
    # crosses the edge is in neither
    "recv_thread_cpu_pct": 100 * 18 / 28,
    # W 50 + 8,000 + 9,000 + 30 + 7,000; S0 15,000; S1 2,000 + 3,000; R 500
    "host_cpu_ms_per_step": 44.58,  # one ps.worker.push in the window
    "turn_wait_ms_p50": 3.0,  # of 2, 4
    "worker_submit_ms_p50": 4.0,  # of 4, 2, 6
    "worker_combine_ms_p50": 6.0,
    "worker_assemble_ms_p50": 5.0,  # of 4, 6
}


def _sp(name, thread, a, b, cpu=10, **attrs):
    return ps.Span(name, thread, a * MS, b * MS, dict(attrs, cpu_us=cpu))


def _account(spans, window=(0.0, 100 * MS)):
    """An ``Account`` of ``spans`` the way ``program_spans._load`` makes
    one: nested over the whole trace, then cut to the window."""
    ps._nest(spans)
    w0, w1 = window
    inside = [sp for sp in spans if sp.start >= w0 and sp.end <= w1]
    by_name = {}
    for sp in inside:
        by_name.setdefault(sp.name, []).append(sp)
    return ps.Account(
        "by hand", window, 1, inside, by_name, {}, {}, 0.0, {}, {}, {}, {}
    )


def _spans():
    request = dict(is_request=1, wait_us=0)
    return [
        _sp("ps.worker.turn", "W", 0, 2, 50, worker=0, t=7, lead=1, blocked=1),
        _sp("ps.worker.pull", "W", 2, 30, 8000),
        _sp("ps.worker.submit", "W", 3, 7),
        _sp("ps.worker.wait", "W", 7, 25),
        _sp("ps.worker.assemble", "W", 25, 29),
        _sp("ps.worker.push", "W", 40, 70, 9000),
        _sp("ps.worker.combine", "W", 41, 47),
        _sp("ps.worker.submit", "W", 47, 49),
        _sp("ps.worker.wait", "W", 49, 69),
        _sp("ps.worker.turn", "W", 70, 74, 30, worker=0, t=8, lead=0, blocked=0),
        _sp("ps.worker.pull", "W", 74, 98, 7000),
        _sp("ps.worker.submit", "W", 75, 81),
        _sp("ps.worker.wait", "W", 81, 90),
        _sp("ps.worker.assemble", "W", 90, 96),
        _sp("ps.van.deliver", "S0", 8, 20, 6000, verb="PULL", **request),
        _sp("ps.server.pull", "S0", 9, 19),
        _sp("ps.server.localize", "S0", 9, 11),
        _sp("ps.server.h2d", "S0", 11, 12),
        _sp("ps.server.dispatch", "S0", 12, 13),
        _sp("ps.server.d2h", "S0", 13, 17),
        _sp("ps.server.ack", "S0", 17, 18.5),
        _sp("ps.van.deliver", "S0", 50, 60, 9000, verb="PUSH", **request),
        _sp("ps.server.push", "S0", 50.5, 59.5),
        _sp("ps.server.localize", "S0", 51, 54),
        _sp("ps.server.h2d", "S0", 54, 56),
        _sp("ps.server.dispatch", "S0", 56, 57),
        _sp("ps.server.ack", "S0", 57, 59),
        _sp("ps.van.deliver", "S1", -5, 5, 4000, verb="PUSH", **request),
        _sp("ps.server.push", "S1", 1, 4, 2000),
        _sp("ps.server.ack", "S1", 3, 3.5),
        _sp("ps.van.deliver", "S1", 82, 88, 3000, verb="PULL", **request),
        _sp("ps.server.pull", "S1", 82.5, 87.5),
        _sp("ps.server.localize", "S1", 83, 84),
        _sp("ps.server.d2h", "S1", 84, 87),
        _sp("ps.van.deliver", "R", 21, 22, 500, verb="PULL", is_request=0),
    ]


def test_the_nine_are_those_the_issue_names():
    assert sorted(host_cpu.METRICS) == sorted(WANT)
    bench = json.load(open(os.path.join(os.path.dirname(os.path.dirname(HERE)), "BENCHMARK.json")))
    layers = {m["layer"] for m in bench["per_layer"]} | {"consistency"}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for name, m in host_cpu.METRICS.items():
        assert m.layer in layers and m.moves in e2e, name
        assert m.better in ("lower", "higher") and m.unit in ("ms", "%")


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reading_on_the_account_by_hand(name):
    acc = _account(_spans())
    assert host_cpu.METRICS[name].read(acc) == pytest.approx(WANT[name])


def test_outermost_spans_are_those_no_span_of_the_window_holds():
    acc = _account(_spans())
    got = sorted((sp.thread, sp.start, sp.name) for sp in host_cpu.outermost(acc))
    assert [(t, n) for t, _, n in got] == [
        ("R", "ps.van.deliver"),
        ("S0", "ps.van.deliver"), ("S0", "ps.van.deliver"),
        ("S1", "ps.server.push"),  # its delivery began before the window
        ("S1", "ps.van.deliver"),
        ("W", "ps.worker.turn"), ("W", "ps.worker.pull"),
        ("W", "ps.worker.push"), ("W", "ps.worker.turn"),
        ("W", "ps.worker.pull"),
    ]
    assert host_cpu.cpu_s(host_cpu.outermost(acc)) == pytest.approx(44.58 * MS)


@pytest.mark.parametrize("how,want", [
    ("no_controller", 0.0),  # worker steps and no turn: nobody to wait for
    ("no_steps", None),  # nothing of a worker in the window
    ("program_without_the_span", None),  # the parent of PR 37
])
def test_a_window_without_turns(monkeypatch, how, want):
    spans = [sp for sp in _spans() if sp.name != "ps.worker.turn"]
    if how == "no_steps":
        spans = [sp for sp in spans if sp.thread != "W"]
    if how == "program_without_the_span":
        monkeypatch.setattr(host_cpu, "SPANS", host_cpu.SPANS - {"ps.worker.turn"})
    assert host_cpu.turn_wait_ms_p50(_account(spans)) == want


def test_a_cpu_sum_of_a_few_ticks_is_not_read():
    """The chip machine's CPU clock ticks every 10 ms: two recv threads
    busy for 40 ms in all read 0, 10,000 or 20,000 us a delivery."""
    spans = _spans()
    for sp in spans:  # the same spans under a clock of 10 ms ticks
        sp.attrs["cpu_us"] = 10_000 if sp.attrs["cpu_us"] >= 3000 else 0
    acc = _account(spans)
    assert not host_cpu.enough_cpu(acc, host_cpu.cpu_s(host_cpu.outermost(acc)))
    got = host_cpu.read_all(acc)
    assert got["recv_thread_cpu_pct"] is None and got["host_cpu_ms_per_step"] is None
    assert got["server_self_ms_p50"] == pytest.approx(WANT["server_self_ms_p50"])
    # a hundred ticks and more are read
    assert host_cpu.enough_cpu(acc, 1.0) and not host_cpu.enough_cpu(acc, 0.99)


def test_a_recv_thread_cannot_use_more_cpu_than_wall():
    assert host_cpu.checks({"recv_thread_cpu_pct": 104.9}) == []
    assert host_cpu.checks({"recv_thread_cpu_pct": None}) == []
    (fail,) = host_cpu.checks({"recv_thread_cpu_pct": 105.1})
    assert "recv_thread_cpu_pct" in fail and "105" in fail


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reading_with_nothing_to_read_is_left_out(name):
    """An account without servers or worker steps, and the trace of a
    program that has no ``ps.`` spans, read ``None`` and raise nothing."""
    assert host_cpu.METRICS[name].read(_account([_sp("ps.van.send", "W", 1, 2)])) is None
    old = ps.load(os.path.join(HERE, "data", "small.xplane.pb"))
    assert host_cpu.METRICS[name].read(old) is None


def test_the_stored_trace_of_pr_25_reads_what_it_holds(capsys):
    """``data/program.xplane.pb`` was written before the new spans: the
    readers of the spans it has read them, the new spans' nothing, and the
    command prints the account and the readings."""
    path = os.path.join(HERE, "data", "program.xplane.pb")
    got = host_cpu.read_all(ps.load(path))
    assert got["server_localize_ms_p50"] is None and got["server_ack_ms_p50"] is None
    assert got["worker_submit_ms_p50"] == pytest.approx(0.45)  # of 0.5, 0.4
    assert got["worker_assemble_ms_p50"] == pytest.approx(0.4)
    assert got["server_self_ms_p50"] == pytest.approx(0.16)
    # 2.5 ms of CPU in all, written in ticks of 100 us and more: 25 ticks
    assert got["host_cpu_ms_per_step"] is None
    assert host_cpu.main([path]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("[host_cpu]") and "ps.server.pull" in "\n".join(out)
    assert json.loads(out[-1]) == {"metrics": got, "fails": []}
