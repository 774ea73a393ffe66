import threading
import time

import numpy as np
import pytest

from parameter_server_tpu.config import ConsistencyConfig, ConsistencyMode
from parameter_server_tpu.core.clock import ConsistencyController, VectorClock
from parameter_server_tpu.core.messages import (
    Message,
    Task,
    TaskKind,
    node_role,
    server_id,
    worker_id,
)
from parameter_server_tpu.core.postoffice import Customer, Postoffice
from parameter_server_tpu.core.van import LoopbackVan


class EchoServer(Customer):
    def handle_request(self, msg):
        return msg.reply(values=[v * 2 for v in msg.values])


def _make_pair():
    van = LoopbackVan()
    server_post = Postoffice("S0", van)
    worker_post = Postoffice("W0", van)
    server = EchoServer("echo", server_post)
    client = Customer("echo", worker_post)
    return van, server, client


def test_node_ids():
    assert node_role("H").value == "scheduler"
    assert node_role(server_id(3)).value == "server"
    assert node_role(worker_id(0)).value == "worker"
    with pytest.raises(ValueError):
        node_role("X9")


def test_request_response_roundtrip():
    van, server, client = _make_pair()
    try:
        msg = Message(
            task=Task(TaskKind.PUSH, "echo"),
            recver="S0",
            values=[np.array([1.0, 2.0])],
        )
        ts = client.submit([msg], keep_responses=True)
        assert client.wait(ts, timeout=5)
        (resp,) = client.take_responses(ts)
        np.testing.assert_allclose(resp.values[0], [2.0, 4.0])
        # drained: fire-and-forget semantics afterwards (no retention leak)
        assert client.responses(ts) == []
    finally:
        van.close()


def test_multiple_outstanding_and_callbacks():
    van, server, client = _make_pair()
    try:
        fired = []
        tss = []
        for i in range(10):
            msg = Message(
                task=Task(TaskKind.PUSH, "echo"),
                recver="S0",
                values=[np.array([float(i)])],
            )
            tss.append(client.submit([msg], callback=lambda r, i=i: fired.append(i)))
        for ts in tss:
            assert client.wait(ts, timeout=5)
        deadline = time.time() + 5
        while len(fired) < 10 and time.time() < deadline:
            time.sleep(0.01)
        assert sorted(fired) == list(range(10))
        # timestamps strictly increasing
        assert tss == sorted(tss) and len(set(tss)) == 10
    finally:
        van.close()


def test_dead_receiver_does_not_hang_wait():
    van, server, client = _make_pair()
    try:
        van.disconnect("S0")
        msg = Message(task=Task(TaskKind.PUSH, "echo"), recver="S0")
        ts = client.submit([msg])
        assert client.wait(ts, timeout=5)  # completes (with zero responses)
        assert client.responses(ts) == []
        assert van.dropped_messages == 1
    finally:
        van.close()


def test_vector_clock():
    vc = VectorClock(3)
    assert vc.min() == 0
    vc.advance(0)
    vc.advance(0)
    vc.advance(1)
    assert vc.min() == 0 and vc.snapshot() == [2, 1, 0]
    done = []
    t = threading.Thread(target=lambda: done.append(vc.wait_until_min(1, timeout=5)))
    t.start()
    vc.advance(2)
    t.join(timeout=5)
    assert done == [True]


@pytest.mark.parametrize(
    "mode,delay,expect_block",
    [
        (ConsistencyMode.BSP, 0, True),
        (ConsistencyMode.SSP, 2, True),
        (ConsistencyMode.ASP, 0, False),
    ],
)
def test_consistency_gating(mode, delay, expect_block):
    cfg = ConsistencyConfig(mode=mode, max_delay=delay)
    ctl = ConsistencyController(cfg, num_workers=2)
    lead = delay if mode == ConsistencyMode.SSP else 0
    # worker 0 runs ahead: can start iterations 0..lead freely
    for t in range(lead + 1):
        assert ctl.wait_turn(0, t, timeout=0.1)
        ctl.finish_iteration(0)
    # next iteration must block (BSP/SSP) until worker 1 advances
    blocked = not ctl.wait_turn(0, lead + 1, timeout=0.1)
    assert blocked == expect_block
    if expect_block:
        ctl.finish_iteration(1)
        assert ctl.wait_turn(0, lead + 1, timeout=5)


def test_ssp_dead_worker_excluded():
    cfg = ConsistencyConfig(mode=ConsistencyMode.SSP, max_delay=1)
    ctl = ConsistencyController(cfg, num_workers=2)
    ctl.finish_iteration(0)
    ctl.finish_iteration(0)
    assert not ctl.wait_turn(0, 2, timeout=0.1)  # blocked on worker 1
    ctl.mark_dead(1)
    assert ctl.wait_turn(0, 2, timeout=5)  # dead worker no longer gates


@pytest.fixture
def turns(monkeypatch):
    """The ``ps.worker.turn`` spans ``core/clock.py`` opens, kept by an
    enabled tracer in place of its module-level ``span`` (the sink a profiler
    session is to the benchmark): ``[(attrs, seconds)]``."""
    from parameter_server_tpu.core import clock as clock_mod
    from parameter_server_tpu.utils.trace import Tracer

    tracer = Tracer()
    monkeypatch.setattr(clock_mod, "span", tracer.span)
    return lambda: [
        (attrs, dur) for name, _, dur, _, attrs in tracer.spans()
        if name == "ps.worker.turn"
    ]


@pytest.mark.parametrize(
    "mode,delay,spans",
    [
        (ConsistencyMode.BSP, 0, 4),
        (ConsistencyMode.SSP, 2, 4),
        (ConsistencyMode.ASP, 0, 0),
    ],
)
def test_a_turn_is_one_span_under_a_bound_and_none_without(
    turns, mode, delay, spans
):
    ctl = ConsistencyController(ConsistencyConfig(mode=mode, max_delay=delay), 1)
    for t in range(4):
        assert ctl.wait_turn(0, t, timeout=1)
        ctl.finish_iteration(0)
    got = turns()
    assert len(got) == spans
    # a lone worker is its own slowest: it leads by nothing and never waits
    assert [a for a, _ in got] == [
        {"worker": 0, "t": t, "lead": 0, "blocked": 0} for t in range(spans)
    ]
    assert ctl.counters() == {"turn_waits": 0, "turn_wait_s": 0.0}


def test_a_held_back_turn_says_so_and_only_it_is_counted(turns):
    ctl = ConsistencyController(
        ConsistencyConfig(mode=ConsistencyMode.SSP, max_delay=1), num_workers=2
    )
    for t in range(2):  # within the bound of the idle worker 1: open turns
        assert ctl.wait_turn(0, t, timeout=1)
        ctl.finish_iteration(0)
    assert ctl.counters()["turn_waits"] == 0
    release = threading.Timer(0.05, ctl.finish_iteration, args=(1,))
    release.start()
    try:
        assert ctl.wait_turn(0, 2, timeout=5)  # held until worker 1 steps
    finally:
        release.join()
    assert ctl.wait_turn(1, 1, timeout=1)  # the slow worker itself: open
    held, slow = turns()[2:]
    assert held[0] == {"worker": 0, "t": 2, "lead": 2, "blocked": 1}
    assert slow[0] == {"worker": 1, "t": 1, "lead": 0, "blocked": 0}
    got = ctl.counters()
    assert got["turn_waits"] == 1
    # the counter times the wait alone, inside the span
    assert 0.03 < got["turn_wait_s"] <= held[1]
    # a turn that times out waited too
    assert not ctl.wait_turn(0, 3, timeout=0.02)
    assert ctl.counters()["turn_waits"] == 2
    assert turns()[-1][0] == {"worker": 0, "t": 3, "lead": 2, "blocked": 1}


class SlowEcho(Customer):
    """Echo that answers after ``delay`` seconds (deadline-path fixture)."""

    delay = 0.5

    def handle_request(self, msg):
        time.sleep(self.delay)
        return msg.reply(values=[v * 2 for v in msg.values])


def test_cancel_frees_pending_and_ignores_late_response():
    van = LoopbackVan()
    try:
        server_post = Postoffice("S0", van)
        worker_post = Postoffice("W0", van)
        SlowEcho("echo", server_post)
        client = Customer("echo", worker_post)
        msg = Message(
            task=Task(TaskKind.PUSH, "echo"),
            recver="S0",
            values=[np.array([1.0])],
        )
        ts = client.submit([msg], keep_responses=True)
        assert not client.wait(ts, timeout=0.05)  # still cooking
        assert client.cancel(ts, "test deadline")
        assert client.wait(ts, timeout=1)  # finalized NOW
        assert client.pending_count() == 0  # nothing leaked
        assert client.errors(ts) == ["test deadline"]
        with pytest.raises(RuntimeError, match="test deadline"):
            client.check(ts)
        # the late response lands after cancel: ignored, no double-finish
        time.sleep(SlowEcho.delay + 0.3)
        assert client.take_responses(ts) == []
        assert client.cancel(ts) is False  # already completed
    finally:
        van.close()


def test_unknown_customer_request_gets_error_reply():
    """A request for a customer the receiving node never registered must
    complete the sender's wait with a reportable error — the reference
    logged and dropped it, hanging the requester's wait(ts) forever."""
    van = LoopbackVan()
    try:
        Postoffice("S0", van)  # node exists, but registers no customer
        client = Customer("nosuch", Postoffice("W0", van))
        ts = client.submit(
            [Message(task=Task(TaskKind.PUSH, "nosuch"), recver="S0")],
            keep_responses=True,
        )
        assert client.wait(ts, timeout=5)  # does NOT hang
        with pytest.raises(RuntimeError, match="unknown customer 'nosuch'"):
            client.check(ts)
    finally:
        van.close()


def test_callbacks_run_on_shared_executor_threads():
    """Completion callbacks ride a small shared daemon pool, not a fresh
    thread per callback (unbounded thread creation under async push rates)."""
    from parameter_server_tpu.utils.threads import CALLBACKS

    van, server, client = _make_pair()
    try:
        thread_names = []
        lock = threading.Lock()

        def cb(responses):
            with lock:
                thread_names.append(threading.current_thread().name)

        for i in range(50):
            client.submit(
                [
                    Message(
                        task=Task(TaskKind.PUSH, "echo"),
                        recver="S0",
                        values=[np.array([float(i)])],
                    )
                ],
                callback=cb,
            )
        deadline = time.time() + 10
        while time.time() < deadline:
            with lock:
                if len(thread_names) == 50:
                    break
            time.sleep(0.01)
        with lock:
            names = set(thread_names)
        assert len(thread_names) == 50
        assert all(n.startswith("ps-callback") for n in names)
        assert len(names) <= CALLBACKS.workers  # bounded pool, threads reused
    finally:
        van.close()


def test_wait_time_for_matches_reference_dag():
    bsp = ConsistencyController(ConsistencyConfig(ConsistencyMode.BSP), 1)
    ssp = ConsistencyController(
        ConsistencyConfig(ConsistencyMode.SSP, max_delay=3), 1
    )
    asp = ConsistencyController(ConsistencyConfig(ConsistencyMode.ASP), 1)
    assert bsp.wait_time_for(5) == 4  # depend on all prior
    assert ssp.wait_time_for(5) == 1  # t - 1 - tau
    assert asp.wait_time_for(5) == -1  # no deps
