"""TcpVan: native TCP transport — serde, round-trips, filters, processes.

The reference tests its transport implicitly via loopback-ZMQ launcher runs
(SURVEY.md §4); here the TCP Van gets direct coverage including a real
multi-process push/pull — the role ``script/local.sh`` played.
"""

import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from parameter_server_tpu import native

if native.load("tcpvan") is None:  # pragma: no cover
    pytest.skip("no native toolchain for tcpvan", allow_module_level=True)

import jax

from parameter_server_tpu.config import OptimizerConfig, TableConfig
from parameter_server_tpu.core.filters import make_chain
from parameter_server_tpu.core.messages import Message, Task, TaskKind
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.tcp_van import (
    TcpVan,
    deserialize_message,
    serialize_message,
)
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.kv.worker import KVWorker
from parameter_server_tpu.utils.keys import IdentityLocalizer


def _msg(recver="S0", sender="W0", time_=3, values=None, keys=None):
    return Message(
        task=Task(TaskKind.PUSH, "w", time=time_, payload={"tag": "t"}),
        sender=sender,
        recver=recver,
        keys=keys,
        values=values if values is not None else [np.ones(4, np.float32)],
    )


def test_serialize_roundtrip():
    m = _msg(
        keys=np.arange(10, dtype=np.uint64),
        values=[
            np.random.default_rng(0).normal(size=(10, 4)).astype(np.float32),
            np.arange(3, dtype=np.int32),
        ],
    )
    m2 = deserialize_message(memoryview(serialize_message(m)))
    assert m2.task.kind == TaskKind.PUSH and m2.task.time == 3
    assert m2.task.payload == {"tag": "t"}
    assert m2.sender == "W0" and m2.recver == "S0" and m2.is_request
    np.testing.assert_array_equal(m.keys, m2.keys)
    for a, b in zip(m.values, m2.values):
        np.testing.assert_array_equal(a, b)


def test_serialize_no_keys_empty_values():
    m = Message(task=Task(TaskKind.CONTROL, "mgr"), sender="H", recver="W0")
    m2 = deserialize_message(memoryview(serialize_message(m)))
    assert m2.keys is None and m2.values == []


def test_local_fast_path_no_socket():
    van = TcpVan()
    got = []
    ev = threading.Event()
    van.bind("S0", lambda m: (got.append(m), ev.set()))
    m = _msg()
    sent_before = van.bytes_sent()
    assert van.send(m)
    # delivery is async (the endpoint's own thread, like LoopbackVan) ...
    assert ev.wait(5)
    # ... but still zero-copy: same object, nothing hit the socket layer
    assert got and got[0] is m
    assert van.bytes_sent() == sent_before
    van.close()


def test_cross_van_roundtrip_and_reply():
    a, b = TcpVan(), TcpVan()
    try:
        ev = threading.Event()
        replies = []

        def server(msg):
            b.send(msg.reply([np.asarray(msg.values[0]) * 2]))

        def worker(msg):
            replies.append(msg)
            ev.set()

        a.bind("W0", worker)
        b.bind("S0", server)
        a.add_route("S0", b.address)
        b.add_route("W0", a.address)
        m = _msg(values=[np.arange(6, dtype=np.float32)])
        assert a.send(m)
        assert ev.wait(10)
        r = replies[0]
        assert not r.is_request and r.sender == "S0"
        np.testing.assert_allclose(r.values[0], np.arange(6) * 2.0)
        assert a.bytes_sent() > 0 and b.bytes_recv() > 0
    finally:
        a.close()
        b.close()


def test_unroutable_drops():
    van = TcpVan()
    try:
        assert not van.send(_msg(recver="S404"))
        assert van.dropped_messages == 1
        # route to a dead port: connect fails -> drop, not hang
        van.add_route("S1", ("127.0.0.1", 1))
        assert not van.send(_msg(recver="S1"))
    finally:
        van.close()


def test_filter_chain_applies_on_wire():
    from parameter_server_tpu.core.filters import CompressingFilter, FilterChain

    a = TcpVan(filter_chain=FilterChain([CompressingFilter()]))
    b = TcpVan(filter_chain=FilterChain([CompressingFilter()]))
    try:
        got = []
        ev = threading.Event()

        def handler(msg):
            got.append(msg)
            ev.set()

        b.bind("S0", handler)
        a.add_route("S0", b.address)
        vals = np.zeros(10000, np.float32)  # compresses well
        assert a.send(_msg(values=[vals]))
        assert ev.wait(60)  # a loaded host: the wait is no claim about speed
        np.testing.assert_array_equal(got[0].values[0], vals)
        assert a.bytes_sent() < vals.nbytes // 10  # actually compressed
    finally:
        a.close()
        b.close()


def test_many_messages_ordered_per_link():
    a, b = TcpVan(), TcpVan()
    try:
        seen = []
        done = threading.Event()

        def handler(msg):
            seen.append(msg.task.time)
            if len(seen) == 100:
                done.set()

        b.bind("S0", handler)
        a.add_route("S0", b.address)
        for t in range(100):
            assert a.send(_msg(time_=t))
        assert done.wait(15)
        assert seen == list(range(100))  # FIFO per link
    finally:
        a.close()
        b.close()


_CHILD = """
import sys, threading
import numpy as np
from parameter_server_tpu.core.tcp_van import TcpVan
from parameter_server_tpu.core.messages import Message, Task, TaskKind

parent_port = int(sys.argv[1])
van = TcpVan()
done = threading.Event()

def server(msg):
    if msg.task.payload.get("stop"):
        done.set()
        return
    van.send(msg.reply([np.asarray(msg.values[0]) + 100.0]))

van.bind("S0", server)
van.add_route("W0", ("127.0.0.1", parent_port))
# announce our port to the parent
van.send(Message(task=Task(TaskKind.CONTROL, "mgr", payload={"port": van.port}),
                 sender="S0", recver="W0"))
done.wait(30)
van.close()
"""


def test_multiprocess_push_pull():
    """Real two-process PS exchange over TCP — the local.sh analogue."""
    van = TcpVan()
    try:
        port_ev, reply_ev = threading.Event(), threading.Event()
        state = {}

        def worker(msg):
            if msg.task.kind == TaskKind.CONTROL:
                state["port"] = msg.task.payload["port"]
                port_ev.set()
            else:
                state["reply"] = msg
                reply_ev.set()

        van.bind("W0", worker)
        proc = subprocess.Popen(
            [sys.executable, "-c", _CHILD, str(van.port)],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
        )
        try:
            assert port_ev.wait(60), "child never announced itself"
            van.add_route("S0", ("127.0.0.1", state["port"]))
            assert van.send(_msg(values=[np.arange(5, dtype=np.float32)]))
            assert reply_ev.wait(30), "no reply from child process"
            np.testing.assert_allclose(
                state["reply"].values[0], np.arange(5) + 100.0
            )
            stop = Message(
                task=Task(TaskKind.CONTROL, "w", payload={"stop": True}),
                sender="W0",
                recver="S0",
            )
            van.send(stop)
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    finally:
        van.close()


def test_filtered_device_reply_plane_three_overlapped_steps():
    """The embedding plane as a hybrid trainer drives it, on sockets: a
    ``key_caching+int8`` chain on every link, servers that reply device
    arrays, the next step's pull in flight while this step's rows are used,
    one push in flight.  The bytes that crossed are counted, and fewer than
    the float32 rows both ways would be."""
    vocab, dim, steps, n_servers = 16384, 256, 3, 2
    # rows start non-zero: a zero table quantizes to nothing on the wire
    cfgs = {
        "emb": TableConfig(
            name="emb", rows=vocab, dim=dim, init_scale=0.02,
            optimizer=OptimizerConfig(kind="adagrad", learning_rate=0.05),
        )
    }
    vans = [
        TcpVan(filter_chain=make_chain("key_caching+int8"))
        for _ in range(n_servers + 1)
    ]
    van_w, van_s = vans[0], vans[1:]
    try:
        for s in range(n_servers):
            KVServer(
                Postoffice(f"S{s}", van_s[s]), cfgs, s, n_servers,
                device_replies=True,
            )
            van_w.add_route(f"S{s}", van_s[s].address)
            van_s[s].add_route("W0", van_w.address)
        worker = KVWorker(
            Postoffice("W0", van_w), cfgs, n_servers,
            localizers={"emb": IdentityLocalizer(vocab)},
        )
        rng = np.random.default_rng(0)
        toks = [
            (rng.zipf(1.2, size=(8, 256)) % vocab).astype(np.int64)
            for _ in range(steps + 1)
        ]
        sent0, recv0 = van_w.payload_bytes_sent(), van_w.payload_bytes_recv()
        ts_cur, push_prev = worker.pull("emb", toks[0]), None
        for i in range(steps):
            ts_next = worker.pull("emb", toks[i + 1])
            rows = worker.pull_result_device(ts_cur, timeout=120)
            assert isinstance(rows, jax.Array) and rows.shape == (8, 256, dim)
            assert np.all(np.isfinite(np.asarray(rows)))
            if push_prev is not None:
                assert worker.wait(push_prev, 120), "push not acknowledged"
            push_prev = worker.push_device(
                "emb", toks[i].reshape(-1), rows.reshape(-1, dim) * 0.01
            )
            ts_cur = ts_next
        assert worker.wait(push_prev, 120)
        worker.pull_result_device(ts_cur, timeout=120)
        wire = (
            van_w.payload_bytes_sent() - sent0
            + van_w.payload_bytes_recv() - recv0
        )
        raw_rows = sum(len(np.unique(t)) for t in toks[:steps]) * dim * 4
        assert 0 < wire < 2 * raw_rows, (wire, raw_rows)
    finally:
        for v in vans:
            v.close()
