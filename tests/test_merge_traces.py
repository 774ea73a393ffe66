"""Cross-node trace stitching: per-node chrome dumps -> one Perfetto
timeline (tools/merge_traces.py) with shared worker/server trace ids.

tools/ is not a package, so the module is loaded straight off disk.
"""

import importlib.util
import json
import os

import numpy as np
import pytest

from parameter_server_tpu.config import OptimizerConfig, TableConfig, TraceConfig
from parameter_server_tpu.core.postoffice import Postoffice
from parameter_server_tpu.core.van import LoopbackVan
from parameter_server_tpu.kv.server import KVServer
from parameter_server_tpu.kv.worker import KVWorker
from parameter_server_tpu.utils.trace import Tracer

_MT_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools", "merge_traces.py",
)


@pytest.fixture(scope="module")
def mt():
    spec = importlib.util.spec_from_file_location("merge_traces", _MT_PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_traced_cluster(tmp_path):
    """2 servers + 1 worker, per-node tracers, a few push/pulls; returns
    the per-node chrome-trace dump paths."""
    van = LoopbackVan()
    try:
        cfgs = {
            "w": TableConfig(
                name="w", rows=512, dim=2,
                optimizer=OptimizerConfig(kind="sgd", learning_rate=0.1),
            )
        }
        tracers = {"W0": Tracer(), "S0": Tracer(), "S1": Tracer()}
        for s in range(2):
            KVServer(
                Postoffice(f"S{s}", van), cfgs, s, 2, tracer=tracers[f"S{s}"]
            )
        worker = KVWorker(
            Postoffice("W0", van), cfgs, 2,
            min_bucket=16, tracer=tracers["W0"],
            trace=TraceConfig(sample_every=1),
        )
        keys = np.arange(40, dtype=np.uint64)
        for _ in range(2):
            assert worker.wait(
                worker.push("w", keys, np.ones((40, 2), np.float32)),
                timeout=30,
            )
            worker.pull_sync("w", keys, timeout=30)
        paths = []
        for nid, tr in tracers.items():
            p = str(tmp_path / f"trace_{nid}.json")
            tr.dump_chrome_trace(p, process_name=nid)
            paths.append(p)
        return paths
    finally:
        van.close()


def test_merged_timeline_validates_and_stitches(mt, tmp_path):
    """Acceptance (b): the merged doc passes schema validation, every node
    is its own pid with a process_name, and each worker ps.worker.push trace
    id reappears on ps.server.push spans of a DIFFERENT pid."""
    paths = _run_traced_cluster(tmp_path)
    merged = mt.merge_traces(paths)
    assert mt.validate_chrome_trace(merged) == []
    events = merged["traceEvents"]
    names = {
        e["args"]["name"] for e in events if e["name"] == "process_name"
    }
    assert names == {"W0", "S0", "S1"}
    pids = {e["pid"] for e in events}
    assert len(pids) == 3  # one Perfetto process per node

    def by_trace(name):
        out = {}
        for e in events:
            if e.get("ph") == "X" and e["name"] == name:
                tid = (e.get("args") or {}).get("trace")
                if tid:
                    out.setdefault(tid, []).append(e)
        return out

    pushes = by_trace("ps.worker.push")
    server_pushes = by_trace("ps.server.push")
    assert pushes and server_pushes
    for tid, worker_evs in pushes.items():
        assert tid in server_pushes, f"trace {tid} has no server-side span"
        worker_pids = {e["pid"] for e in worker_evs}
        server_pids = {e["pid"] for e in server_pushes[tid]}
        assert worker_pids.isdisjoint(server_pids)  # stitched ACROSS nodes
        # the 40 keys split over both servers: both server pids appear
        assert len(server_pids) == 2
        # origin attr names the worker node
        assert all(
            (e.get("args") or {}).get("origin") == "W0"
            for e in server_pushes[tid]
        )


def test_clock_rebase_keeps_order(mt, tmp_path):
    """Files with different clock epochs rebase onto the earliest one:
    relative offsets preserved, all ts non-negative."""
    def dump(path, node, t0, start):
        doc = {
            "traceEvents": [
                {"name": "op", "ph": "X", "ts": start * 1e6, "dur": 10.0,
                 "pid": 1, "tid": 1}
            ],
            "metadata": {"node": node, "clock_t0_s": t0},
        }
        with open(path, "w") as f:
            json.dump(doc, f)

    a = str(tmp_path / "a.json")
    b = str(tmp_path / "b.json")
    dump(a, "A", t0=100.0, start=0.5)  # absolute 100.5
    dump(b, "B", t0=103.0, start=0.25)  # absolute 103.25
    merged = mt.merge_traces([a, b])
    assert mt.validate_chrome_trace(merged) == []
    evs = {
        (e["args"]["name"] if e["name"] == "process_name" else None): e
        for e in merged["traceEvents"]
    }
    spans = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    ts = {e["pid"]: e["ts"] for e in spans}
    assert all(v >= 0 for v in ts.values())
    # B started 2.75s after A in absolute time; preserved after rebase
    assert abs((ts[2] - ts[1]) - 2.75e6) < 1.0
    del evs


def test_validate_catches_malformed_events(mt):
    bad = {
        "traceEvents": [
            {"name": "ok", "ph": "X", "ts": 0.0, "dur": 1.0,
             "pid": 1, "tid": 1},
            {"ph": "X", "ts": 0.0, "dur": 1.0, "pid": 1, "tid": 1},  # no name
            {"name": "neg", "ph": "X", "ts": 0.0, "dur": -5.0,
             "pid": 1, "tid": 1},
            {"name": "weird", "ph": "Q", "pid": 1},
            "not-an-object",
        ]
    }
    problems = mt.validate_chrome_trace(bad)
    assert len(problems) == 4
    assert mt.validate_chrome_trace({"traceEvents": []}) == []
    assert mt.validate_chrome_trace({}) == ["traceEvents missing or not a list"]


def _write_bundle(path, node, events, *, mono=100.0, wall=5000.0, off=0.0):
    doc = {
        "node": node,
        "wall_anchor_s": wall,
        "mono_anchor_s": mono,
        "clock_offset_s": off,
        "counters": {},
        "events": events,
    }
    with open(path, "w") as f:
        json.dump(doc, f)


def test_flightrec_bundle_bridges_as_instants(mt, tmp_path):
    """ISSUE 10 satellite: a flight-recorder bundle merges alongside a
    chrome trace as validated Perfetto instant events carrying the journal
    fields, on its own pid."""
    trace = str(tmp_path / "trace_W0.json")
    with open(trace, "w") as f:
        json.dump({
            "traceEvents": [
                {"name": "ps.worker.push", "ph": "X", "ts": 0.0, "dur": 10.0,
                 "pid": 1, "tid": 1}
            ],
            "metadata": {"node": "W0", "clock_t0_s": 100.0},
        }, f)
    bundle = str(tmp_path / "flightrec_S0.json")
    _write_bundle(bundle, "S0", [
        {"seq": 1, "t_mono_s": 100.5, "kind": "resend.retransmit",
         "node": "S0", "attempt": 2},
        {"seq": 2, "t_mono_s": 101.0, "kind": "slo.breach", "node": "S0"},
    ])
    merged = mt.merge_traces([trace, bundle])
    assert mt.validate_chrome_trace(merged) == []
    inst = [e for e in merged["traceEvents"] if e.get("ph") == "i"]
    assert [e["name"] for e in inst] == ["resend.retransmit", "slo.breach"]
    span_pid = next(
        e["pid"] for e in merged["traceEvents"] if e.get("ph") == "X"
    )
    assert all(e["pid"] != span_pid for e in inst)  # own Perfetto process
    assert inst[0]["args"]["attempt"] == 2  # journal fields preserved
    assert inst[0]["s"] == "p"
    # both files embed epoch 100.0 -> shared base; 0.5s after the anchor
    assert inst[0]["ts"] == pytest.approx(0.5e6)
    names = {
        e["args"]["name"] for e in merged["traceEvents"]
        if e["name"] == "process_name"
    }
    assert names == {"W0", "S0"}


def test_bundle_clock_offset_rebases_onto_scheduler_domain(mt, tmp_path):
    """A bundle whose node clock runs 2s ahead (clock_offset_s=2) lands 2s
    earlier after the rebase — aligned with the scheduler-domain trace."""
    trace = str(tmp_path / "trace_sched.json")
    with open(trace, "w") as f:
        json.dump({
            "traceEvents": [
                {"name": "op", "ph": "X", "ts": 0.0, "dur": 1.0,
                 "pid": 1, "tid": 1}
            ],
            "metadata": {"node": "SCHED", "clock_t0_s": 98.0},
        }, f)
    bundle = str(tmp_path / "flightrec_W1.json")
    _write_bundle(
        bundle, "W1",
        [{"seq": 1, "t_mono_s": 100.5, "kind": "fence.routing", "node": "W1"}],
        mono=100.0, off=2.0,
    )
    merged = mt.merge_traces([trace, bundle])
    assert mt.validate_chrome_trace(merged) == []
    inst = next(e for e in merged["traceEvents"] if e.get("ph") == "i")
    # scheduler-domain absolute time: 100.5 - 2.0 = 98.5 = base(98.0) + 0.5
    assert inst["ts"] == pytest.approx(0.5e6)


def test_validate_catches_malformed_instants(mt):
    bad = {
        "traceEvents": [
            {"name": "ok", "ph": "i", "ts": 1.0, "pid": 1, "tid": 0, "s": "p"},
            {"name": "nots", "ph": "i", "pid": 1, "tid": 0},        # no ts
            {"name": "scope", "ph": "i", "ts": 1.0, "pid": 1, "tid": 0,
             "s": "z"},                                             # bad scope
        ]
    }
    problems = mt.validate_chrome_trace(bad)
    assert len(problems) == 2


def test_cli_writes_merged_output(mt, tmp_path, capsys):
    paths = _run_traced_cluster(tmp_path)
    out = str(tmp_path / "merged.json")
    assert mt.main(["-o", out] + paths) == 0
    with open(out) as f:
        doc = json.load(f)
    assert mt.validate_chrome_trace(doc) == []
    assert "merged 3 node traces" in capsys.readouterr().out
