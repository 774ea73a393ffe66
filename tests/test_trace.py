"""Tracing subsystem: spans, histograms, exports, KV-layer wiring."""

import builtins
import json
import threading
import time

import numpy as np

from parameter_server_tpu.config import OptimizerConfig, TableConfig
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.kv.worker import KVWorker
from parameter_server_tpu.utils.trace import (
    NULL_TRACER,
    LatencyHistogram,
    Tracer,
    resource_usage,
)


def test_span_recording_and_histogram():
    tr = Tracer()
    for i in range(20):
        with tr.span("op", i=i):
            time.sleep(0.001)
    h = tr.histogram("op")
    assert h["count"] == 20
    assert h["p50_us"] >= 1000  # slept >= 1ms
    assert h["p99_us"] >= h["p50_us"]
    assert h["max_us"] >= h["p99_us"]
    assert tr.histogram("missing")["count"] == 0
    assert "op" in tr.summary()


def test_span_thread_safety_and_capacity():
    tr = Tracer(capacity=100)

    def worker():
        for _ in range(100):
            with tr.span("w"):
                pass

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr.spans("w")) == 100  # bounded by capacity, no crash


def test_null_tracer_records_nothing():
    with NULL_TRACER.span("x"):
        pass
    NULL_TRACER.record("y", 0.5)
    assert NULL_TRACER.spans() == []


def test_exports(tmp_path):
    tr = Tracer()
    with tr.span("a", table="w"):
        pass
    tr.record("b", 0.002)
    chrome = tmp_path / "trace.json"
    tr.dump_chrome_trace(str(chrome))
    events = json.loads(chrome.read_text())["traceEvents"]
    assert {e["name"] for e in events} == {"a", "b"}
    assert all(e["ph"] == "X" and "dur" in e for e in events)
    assert any(e.get("args") == {"table": "w"} for e in events)
    assert [e["dur"] for e in events if e["name"] == "b"] == [2000.0]


def test_resource_usage_fields():
    ru = resource_usage()
    assert ru["rss_mb"] > 1.0
    assert ru["cpu_user_s"] >= 0.0
    assert ru["threads"] >= 1


def test_resource_usage_non_linux_fallback(monkeypatch):
    """No /proc (macOS/Windows): a time-only dict, never an exception."""
    real_open = builtins.open

    def fake_open(path, *args, **kwargs):
        if str(path).startswith("/proc/"):
            raise OSError("no /proc on this platform")
        return real_open(path, *args, **kwargs)

    monkeypatch.setattr(builtins, "open", fake_open)
    ru = resource_usage()
    assert set(ru) == {"time"}
    assert ru["time"] > 0


# ------------------------------------------------------- LatencyHistogram


def test_latency_histogram_exact_moments_and_bounded_percentiles():
    h = LatencyHistogram()
    values = [0.0005, 0.001, 0.002, 0.004, 0.008, 0.5]
    for v in values:
        h.record(v)
    assert h.count == len(values)
    assert abs(h.sum_s - sum(values)) < 1e-12  # count/sum/max are EXACT
    assert h.max_s == 0.5
    # percentiles are bucket upper bounds: >= the true quantile, <= max,
    # within the 25% bucket growth factor
    p50 = h.percentile(0.50)
    assert 0.002 <= p50 <= 0.002 * LatencyHistogram.GROWTH
    assert h.percentile(0.99) <= h.max_s
    assert h.percentile(1.0) == h.max_s
    # negative durations clamp to bucket 0, never throw
    h.record(-1.0)
    assert h.count == len(values) + 1


def test_latency_histogram_empty_and_extremes():
    h = LatencyHistogram()
    assert h.percentile(0.99) == 0.0
    assert h.stats() == {"count": 0}
    h.record(1e-9)  # below BASE -> bucket 0
    h.record(1e9)  # beyond the last bucket -> max stays exact, but
    # percentiles saturate at the last bucket's upper edge (<= max)
    assert h.max_s == 1e9
    assert h.percentile(1.0) <= h.max_s
    last_edge = LatencyHistogram.BASE * (
        LatencyHistogram.GROWTH ** (LatencyHistogram.NBUCKETS - 1)
    )
    assert h.percentile(1.0) == last_edge  # ~27 min: the range ceiling


def test_latency_histogram_merge_equals_union():
    a, b, u = LatencyHistogram(), LatencyHistogram(), LatencyHistogram()
    for i in range(50):
        v = 1e-5 * (i + 1)
        (a if i % 2 else b).record(v)
        u.record(v)
    a.merge(b)
    assert a.counts == u.counts
    assert a.count == u.count
    assert abs(a.sum_s - u.sum_s) < 1e-12
    assert a.percentile(0.9) == u.percentile(0.9)


def test_latency_histogram_dict_round_trip():
    h = LatencyHistogram()
    for v in (1e-5, 3e-4, 0.02, 1.5):
        h.record(v)
    d = h.to_dict()
    json.dumps(d)  # heartbeat-safe
    back = LatencyHistogram.from_dict(d)
    assert back.counts == h.counts
    assert back.count == h.count
    assert back.max_s == h.max_s


def test_tracer_histogram_survives_deque_wraparound():
    """The old bounded-deque histogram silently became 'stats of the last
    capacity spans'; the LatencyHistogram backing must count everything."""
    tr = Tracer(capacity=10)
    for _ in range(100):
        tr.record("op", 0.001)
    assert len(tr.spans("op")) == 10  # timeline stays bounded...
    assert tr.histogram("op")["count"] == 100  # ...aggregates do not
    assert tr.totals()["op"] >= 0.1 - 1e-9
    digests = tr.digests()
    assert digests["op"]["count"] == 100


def test_kv_layer_traced_push_pull():
    van = LoopbackVan()
    try:
        cfgs = {
            "w": TableConfig(
                name="w", rows=500, dim=2,
                optimizer=OptimizerConfig(kind="sgd", learning_rate=1.0),
            )
        }
        server_tracer = Tracer()
        worker_tracer = Tracer()
        servers = [
            KVServer(
                Postoffice(f"S{i}", van), cfgs, i, 2, tracer=server_tracer
            )
            for i in range(2)
        ]
        worker = KVWorker(
            Postoffice("W0", van), cfgs, 2, min_bucket=16, tracer=worker_tracer
        )
        keys = np.arange(40, dtype=np.uint64)
        for _ in range(3):
            worker.wait(
                worker.push("w", keys, np.ones((40, 2), np.float32)), timeout=10
            )
            worker.pull_sync("w", keys, timeout=10)
        s = worker_tracer.summary()
        assert s["ps.worker.push"]["count"] == 3
        assert s["ps.worker.pull"]["count"] == 3
        assert s["ps.worker.wait"]["count"] == 3  # the pulls': push() waits not
        assert s["ps.worker.submit"]["count"] == 6
        ss = server_tracer.summary()
        # both servers share the tracer: 3 pushes+pulls x 2 servers
        assert ss["ps.server.push"]["count"] == 6
        assert ss["ps.server.pull"]["count"] == 6
        assert ss["ps.server.dispatch"]["count"] == 12
        assert ss["ps.server.d2h"]["count"] == 6
        assert ss["ps.server.push"]["mean_us"] > 0
        # a server span and the worker's submit share the request's id
        reqs = {a["req"] for *_x, a in worker_tracer.spans("ps.worker.submit")}
        assert {a["req"] for *_x, a in server_tracer.spans("ps.server.pull")} <= reqs
    finally:
        van.close()
