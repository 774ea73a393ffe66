"""Dashboard: MFU column, tracer attribution, JSONL rows (VERDICT r2 #7);
transport_counters stack-merge semantics."""

import io
import json

import numpy as np

from parameter_server_tpu.utils import metrics as metrics_lib
from parameter_server_tpu.utils.metrics import transport_counters
from parameter_server_tpu.utils.trace import Tracer


def test_dashboard_mfu_per_iter():
    sink = io.StringIO()
    dash = metrics_lib.Dashboard(
        jsonl=sink,
        print_every=0,
        flops_per_example=1e6,
        peak_flops=1e12,
    )
    dash.record(1, 0.7, examples=1000)
    dash.record(2, 0.6, examples=1000)
    rows = [json.loads(line) for line in sink.getvalue().splitlines()]
    assert all("mfu_pct" in r for r in rows)
    assert all(r["mfu_pct"] > 0 for r in rows)
    # sanity: mfu = flops/interval/peak, so a 1e9-FLOP interval against a
    # 1e12 peak cannot exceed 100% unless the interval were under 1 ms
    assert rows[0]["mfu_pct"] <= 100.0 or rows[0]["sec"] < 0.001


def test_dashboard_auto_peak_flops_by_device_kind(monkeypatch):
    import jax

    from parameter_server_tpu.utils import platform

    # a device_kind the peak table does not know (every CPU): no MFU figure
    sink = io.StringIO()
    dash = metrics_lib.Dashboard(
        print_every=0, jsonl=sink, flops_per_example=10.0
    )
    dash.record(1, 0.5, examples=10)
    assert dash.peak_flops == 0.0
    assert "mfu_pct" not in json.loads(sink.getvalue())
    # a known kind fills peak_flops lazily at the first MFU computation
    kind = jax.devices()[0].device_kind
    monkeypatch.setitem(
        platform.DEVICE_PEAKS, kind, {"flops": 1e6, "hbm_gbps": 100.0}
    )
    dash.record(2, 0.4, examples=10)
    assert dash.peak_flops == 1e6
    assert json.loads(sink.getvalue().splitlines()[-1])["mfu_pct"] > 0


def test_dashboard_span_attribution():
    tracer = Tracer()
    with tracer.span("host.assemble"):
        pass
    with tracer.span("device.step"):
        pass
    with tracer.span("device.step"):
        pass
    sink = io.StringIO()
    dash = metrics_lib.Dashboard(jsonl=sink, print_every=1, tracer=tracer)
    attr = dash.attribution()
    assert set(attr) == {"host.assemble", "device.step"}
    assert all(v >= 0 for v in attr.values())
    dash.record(1, 1.0, examples=1)
    row = json.loads(sink.getvalue().splitlines()[0])
    assert "spans_s" in row and "device.step" in row["spans_s"]


def test_dashboard_no_mfu_when_unconfigured():
    sink = io.StringIO()
    dash = metrics_lib.Dashboard(jsonl=sink, print_every=0)
    dash.record(1, 0.5, examples=10)
    row = json.loads(sink.getvalue().splitlines()[0])
    assert "mfu_pct" not in row


# ---------------------------------------------------- transport_counters


class _FakeVan:
    def __init__(self, counters=None, inner=None):
        self.inner = inner
        self._counters = counters

    def counters(self):
        if isinstance(self._counters, Exception):
            raise self._counters
        return dict(self._counters or {})


def test_transport_counters_sums_across_layers():
    base = _FakeVan({"sent": 10, "shared": 1})
    mid = _FakeVan({"retransmits": 3, "shared": 2}, inner=base)
    top = _FakeVan({"wire_bytes": 100}, inner=mid)
    merged = transport_counters(top)
    assert merged == {
        "wire_bytes": 100, "retransmits": 3, "sent": 10, "shared": 3
    }


def test_transport_counters_terminates_on_inner_cycle():
    a = _FakeVan({"a": 1})
    b = _FakeVan({"b": 1}, inner=a)
    a.inner = b  # pathological cycle: the walk must not loop forever
    assert transport_counters(a) == {"a": 1, "b": 1}


def test_transport_counters_swallows_broken_layer():
    broken = _FakeVan(RuntimeError("boom"), inner=_FakeVan({"sent": 5}))
    assert transport_counters(broken) == {"sent": 5}
    assert transport_counters(object()) == {}  # no counters() at all


def test_transport_counters_real_observability_stack():
    """Metered + Reliable + Chaos + Loopback: one flat dict carrying every
    layer's counters, wire bytes included."""
    from parameter_server_tpu.core.chaos import ChaosVan
    from parameter_server_tpu.core.messages import Message, Task, TaskKind
    from parameter_server_tpu.core.netmon import MeteredVan
    from parameter_server_tpu.core.resender import ReliableVan
    from parameter_server_tpu.core.van import LoopbackVan

    van = MeteredVan(
        ReliableVan(ChaosVan(LoopbackVan(), seed=0), timeout=5.0)
    )
    try:
        van.bind("B", lambda m: None)
        van.send(
            Message(
                task=Task(TaskKind.PUSH, "kv"),
                sender="A", recver="B",
                keys=np.arange(4, dtype=np.int64),
                values=[np.ones(4, np.float32)],
            )
        )
        merged = transport_counters(van)
        for key in ("wire_msgs", "wire_bytes", "retransmits",
                    "chaos_drops", "chaos_slow", "sent"):
            assert key in merged, key
        assert merged["wire_bytes"] == 4 * 8 + 4 * 4
    finally:
        van.close()


def test_dashboard_bytes_per_example_and_throughput():
    """With a MeteredVan in the transport, rows carry bytes_per_example
    (cumulative wire bytes / examples) and per-interval wire_bytes_per_sec
    (first row has no prior interval, so only later rows carry it)."""

    class _Wire:
        def __init__(self):
            self.wire_bytes = 0

        def counters(self):
            return {"wire_bytes": self.wire_bytes}

    wire = _Wire()
    sink = io.StringIO()
    dash = metrics_lib.Dashboard(jsonl=sink, print_every=0, transport=wire)
    wire.wire_bytes = 4000
    dash.record(1, 0.5, examples=100)
    wire.wire_bytes = 10000
    dash.record(2, 0.4, examples=100)
    rows = [json.loads(line) for line in sink.getvalue().splitlines()]
    assert rows[0]["net"]["bytes_per_example"] == 40.0
    assert "wire_bytes_per_sec" not in rows[0]["net"]
    assert rows[1]["net"]["bytes_per_example"] == 50.0  # 10000 / 200
    assert rows[1]["net"]["wire_bytes_per_sec"] > 0  # 6000 over the interval
